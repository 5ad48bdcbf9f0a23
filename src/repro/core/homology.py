"""Homology score + re-identification (paper §III-C).

Definition 5: s(q1, q2) = |D1 ∩ D2| / k — the overlap ratio between the two
queries' retrieval result sets.

The paper computes this through a document→query inverted index J (a hash
map).  Hash maps do not exist on TPU; the TPU-native equivalent is a dense
fixed-shape overlap count: the draft's k doc-ids are compared against the
cached doc-id table [H, k] with a tiled compare-reduce (Pallas kernel
``homology_score``; this module is its jnp oracle).  Complexity O(H·k²) int
comparisons — vector-unit-trivial at H=5000, k=10.  A faithful host-side
inverted index lives in serving/engine.py for the sequential reference path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def homology_scores(draft_ids: jax.Array, cache_doc_ids: jax.Array,
                    cache_valid: jax.Array) -> jax.Array:
    """Homology score of one draft against every cached query.

    draft_ids [k] int32, cache_doc_ids [H, k] int32 (-1 pad),
    cache_valid [H] bool -> scores [H] float32 in [0, 1].
    """
    k = draft_ids.shape[0]
    eq = (draft_ids[None, :, None] == cache_doc_ids[:, None, :])  # [H,k,k]
    eq &= (draft_ids[None, :, None] >= 0)
    overlap = jnp.sum(jnp.any(eq, axis=2), axis=1)                 # [H]
    s = overlap.astype(jnp.float32) / k
    return jnp.where(cache_valid, s, 0.0)


def homology_scores_batched(draft_ids: jax.Array, cache_doc_ids: jax.Array,
                            cache_valid: jax.Array) -> jax.Array:
    """draft_ids [B, k] -> scores [B, H]."""
    return jax.vmap(lambda d: homology_scores(d, cache_doc_ids, cache_valid))(
        draft_ids)


def rrf_draft_weights(ids: jax.Array, rrf_k: float) -> jax.Array:
    """Per-slot normalized RRF mass of a fused draft: ids [..., k] ->
    weights [..., k] f32 summing to 1 over the valid slots (0 if none).

    Position j of a fused list carries mass ``1/(rrf_k + j)``; invalid
    (-1) slots carry none.  Normalizing per draft makes the weighted
    homology score lie in [0, 1] like the unweighted overlap ratio, so the
    same ``tau`` threshold applies.
    """
    k = ids.shape[-1]
    w = 1.0 / (rrf_k + jnp.arange(k, dtype=jnp.float32))
    w = jnp.where(ids >= 0, w, 0.0)
    norm = jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-30)
    return w / norm


def homology_scores_weighted(draft_ids: jax.Array, cache_doc_ids: jax.Array,
                             cache_valid: jax.Array,
                             draft_weights: jax.Array) -> jax.Array:
    """Rank-weighted homology of one fused draft against the cache.

    draft_ids [k], draft_weights [k] (pre-normalized, e.g.
    :func:`rrf_draft_weights`), cache_doc_ids [H, k], cache_valid [H]
    -> scores [H] f32: the matched fraction of the draft's RRF mass.
    Rank-domain on both sides — invariant to any positive monotone
    transform of either channel's raw scores.
    """
    eq = (draft_ids[None, :, None] == cache_doc_ids[:, None, :])  # [H,k,k]
    eq &= (draft_ids[None, :, None] >= 0)
    hit = jnp.any(eq, axis=2).astype(jnp.float32)                 # [H, k]
    s = jnp.sum(hit * draft_weights[None, :], axis=1)
    return jnp.where(cache_valid, s, 0.0)


@functools.partial(jax.jit, static_argnames=())
def reidentify(draft_ids: jax.Array, cache_doc_ids: jax.Array,
               cache_valid: jax.Array, tau: jax.Array):
    """Threshold-based homologous-query re-identification.

    Returns (accept: bool, best_score: float32, best_slot: int32).
    Accept iff max_h s(q, q_h) > tau  (strict >, per Algorithm 1 line 11).
    """
    s = homology_scores(draft_ids, cache_doc_ids, cache_valid)
    best_slot = jnp.argmax(s)
    best = s[best_slot]
    return best > tau, best, best_slot.astype(jnp.int32)


def pairwise_homology(ids_a: jax.Array, ids_b: jax.Array) -> jax.Array:
    """s(q1,q2) for two result sets [k] -> scalar overlap ratio."""
    k = ids_a.shape[0]
    eq = (ids_a[:, None] == ids_b[None, :]) & (ids_a[:, None] >= 0)
    return jnp.sum(jnp.any(eq, axis=1)).astype(jnp.float32) / k
