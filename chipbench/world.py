"""The benchmark's own synthetic RAG world and query streams.

Same semantics as the entity-attribute world HaS is evaluated on (entity-
centric embeddings, multi-attribute documents, the golden-document oracle,
Zipf query popularity), generated here so that a change to the program's
own data generator cannot move the yardstick:

* document ``i`` belongs to entity ``i // docs_per_entity`` and covers
  ``attrs_per_doc`` of the entity's ``attrs_per_entity`` attributes;
* its embedding is ``unit(ew * entity + awd * mean-ish(attrs) + nd *
  unit(noise))``, a query's is ``unit(ew * entity + awq * attr + nq *
  unit(noise))``;
* a document is golden for a query iff it has the query's entity and
  covers the query's attribute.

The corpus and the query embeddings are made on the device in one jitted
call from ``--seed``; the small integer tables (attribute selections,
coverage, the stream's entities and attributes) come from numpy.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


def row_chunk(n: int, cap: int = 65536) -> int:
    """Rows per step of a scan over ``n`` rows: the largest divisor of
    ``n`` up to ``cap``, a multiple of 8 where one exists (the TPU's f32
    tile is 8 rows, so the reshape into blocks moves no data)."""
    divisors = [c for c in range(min(n, cap), 0, -1) if n % c == 0]
    return next((c for c in divisors if c % 8 == 0), divisors[0])


@dataclasses.dataclass(frozen=True)
class WorldShape:
    """What ``launch/serve.py``'s builders read from ``world.cfg``."""
    n_entities: int
    docs_per_entity: int
    attrs_per_entity: int
    attrs_per_doc: int
    d: int

    @property
    def n_docs(self) -> int:
        return self.n_entities * self.docs_per_entity


def split_seed(seed: int) -> tuple[int, int]:
    """A seed of any size as (low 32 bits, the rest) for ``jax.random``."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return seed & 0xFFFFFFFF, seed >> 32


def host_tables(shape: WorldShape, seed: int):
    """(doc_sel [N, apd] int32, doc_attr_mask [N, A] bool,
    entity_attrs [E, A] bool) from numpy's generator."""
    rng = np.random.default_rng([seed, 1])
    e, dpe, a, apd = (shape.n_entities, shape.docs_per_entity,
                      shape.attrs_per_entity, shape.attrs_per_doc)
    doc_sel = np.empty((e * dpe, apd), np.int32)
    for i in range(dpe):
        sel = rng.random((e, a)).argsort(axis=1)[:, :apd]
        doc_sel[i::dpe] = sel
    mask = np.zeros((e * dpe, a), bool)
    np.put_along_axis(mask, doc_sel.astype(np.int64), True, axis=1)
    entity_attrs = mask.reshape(e, dpe, a).any(axis=1)
    return doc_sel, mask, entity_attrs


def zipf_ranks(n: int, a: float, n_entities: int, rng) -> np.ndarray:
    """``n`` 0-based popularity ranks, Zipf(a) truncated to the entities."""
    ranks = rng.zipf(a, size=4 * n)
    ranks = ranks[ranks <= n_entities][:n] - 1
    while len(ranks) < n:
        extra = rng.zipf(a, size=n) - 1
        ranks = np.concatenate([ranks, extra[extra < n_entities]])[:n]
    return ranks.astype(np.int64)


def sample_stream(n: int, traffic: dict, entity_attrs: np.ndarray,
                  seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(entities [n], attrs [n]) of one query stream.

    The sequence of popularity ranks comes from the traffic's own
    ``rank_seed``, so every ``--seed`` serves the same popularity profile
    (the same repeats at the same positions); which entity holds each rank,
    the attribute asked and every vector come from ``seed``.
    ``p_uncovered`` is the share of questions about an attribute that no
    document of the entity covers, scaled down for head entities as
    popular entities are better covered.
    """
    n_ent, n_attr = entity_attrs.shape
    ranks = zipf_ranks(n, traffic["zipf_a"], n_ent,
                       np.random.default_rng(traffic["rank_seed"]))
    perm = np.random.default_rng([seed, 2]).permutation(n_ent)
    ents = perm[ranks]
    r = ranks.astype(np.float64)
    p_unc = traffic["p_uncovered"] * (r / (r + 30.0)) * 1.35
    cov = entity_attrs[ents]                                  # [n, A]
    n_cov = cov.sum(axis=1)
    n_unc = n_attr - n_cov
    # one generator per draw, so a stream's prefix is the same whatever
    # its length
    ask_unc = (n_unc > 0) & (np.random.default_rng([seed, 3]).random(n)
                             < p_unc)
    pick = np.random.default_rng([seed, 4]).random(n)
    # the j-th covered (or uncovered) attribute, j uniform over the set
    want = np.where(ask_unc, ~cov.T, cov.T).T                 # [n, A]
    n_want = np.where(ask_unc, n_unc, n_cov)
    j = np.minimum((pick * np.maximum(n_want, 1)).astype(np.int64),
                   np.maximum(n_want - 1, 0))
    order = np.cumsum(want, axis=1) - 1                       # rank in set
    hit = want & (order == j[:, None])
    attrs = np.where(n_want > 0, hit.argmax(axis=1), 0)
    return ents.astype(np.int32), attrs.astype(np.int32)


@functools.partial(jax.jit, static_argnames=("shape", "profile"))
def _generate(key, doc_sel, q_ent, q_attr, shape: WorldShape,
              profile: tuple):
    """corpus [N, d] f32 and queries [n, d] f32, made on the device."""
    ew, awd, awq, nd, nq = profile
    k_ent, k_attr, k_doc, k_q = jax.random.split(key, 4)
    unit = lambda x: x / jnp.maximum(                          # noqa: E731
        jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-8)
    ent = unit(jax.random.normal(k_ent, (shape.n_entities, shape.d)))
    basis = unit(jax.random.normal(k_attr, (shape.attrs_per_entity,
                                            shape.d)))
    n = shape.n_docs
    rows = row_chunk(n)
    sel = doc_sel.reshape(n // rows, rows, shape.attrs_per_doc)

    def chunk(xs):
        i, s = xs
        doc = i * rows + jnp.arange(rows)
        mix = basis[s].sum(axis=1) / np.sqrt(shape.attrs_per_doc)
        noise = unit(jax.random.normal(jax.random.fold_in(k_doc, i),
                                       (rows, shape.d)))
        return unit(ew * ent[doc // shape.docs_per_entity] + awd * mix
                    + nd * noise)

    corpus = jax.lax.map(chunk, (jnp.arange(n // rows), sel))
    corpus = corpus.reshape(n, shape.d)

    def query(i, e, a):
        noise = unit(jax.random.normal(jax.random.fold_in(k_q, i),
                                       (shape.d,)))
        return unit(ew * ent[e] + awq * basis[a] + nq * noise)

    queries = jax.vmap(query)(jnp.arange(q_ent.shape[0]), q_ent, q_attr)
    return corpus, queries


class World:
    """Corpus on the device plus the oracle, shaped like the program's
    world object: ``cfg`` (n_docs, d), ``doc_emb``, ``golden_mask``."""

    def __init__(self, config: dict, seed: int, q_ent: np.ndarray,
                 q_attr: np.ndarray, tables=None):
        self.cfg = WorldShape(config["n_entities"], config["docs_per_entity"],
                              config["attrs_per_entity"],
                              config["attrs_per_doc"], config["d"])
        if config["n_docs"] != self.cfg.n_docs:
            raise ValueError(f"n_docs {config['n_docs']} != n_entities x "
                             f"docs_per_entity = {self.cfg.n_docs}")
        lo, hi = split_seed(seed)
        doc_sel, self.doc_attr_mask, self.entity_attrs = (
            tables if tables is not None else host_tables(self.cfg, seed))
        self.doc_entity = (np.arange(self.cfg.n_docs, dtype=np.int64)
                           // self.cfg.docs_per_entity)
        enc = config["encoder_profile"]
        profile = (enc["entity_weight"], enc["attr_weight_doc"],
                   enc["attr_weight_query"], enc["noise_doc"],
                   enc["noise_query"])
        key = jax.random.fold_in(jax.random.PRNGKey(np.uint32(lo)), hi)
        self.doc_emb, queries = _generate(
            key, jnp.asarray(doc_sel), jnp.asarray(q_ent),
            jnp.asarray(q_attr), shape=self.cfg, profile=profile)
        self.query_emb = np.asarray(queries)
        self.doc_emb.block_until_ready()

    def golden_mask(self, entity: int, attr: int,
                    doc_ids: np.ndarray) -> np.ndarray:
        return golden(self.doc_entity, self.doc_attr_mask, entity, attr,
                      doc_ids)


def golden(doc_entity, doc_attr_mask, entity, attr, doc_ids) -> np.ndarray:
    """G(d, q) = [E(d) = E(q)] and [A(q) in A(d)] for each id (-1: False);
    ``entity``/``attr`` may be scalars or arrays broadcasting against the
    leading axes of ``doc_ids``."""
    ids = np.asarray(doc_ids)
    ok = ids >= 0
    safe = np.where(ok, ids, 0)
    ent = np.asarray(entity)[..., None] if ids.ndim else entity
    att = np.asarray(attr)[..., None] if ids.ndim else attr
    return ok & (doc_entity[safe] == ent) & doc_attr_mask[safe, att]


def doc_hits(world, ents, attrs, served: np.ndarray) -> np.ndarray:
    """Per request: does its served id list hold a golden document."""
    return golden(world.doc_entity, world.doc_attr_mask, ents, attrs,
                  served).any(axis=-1)
