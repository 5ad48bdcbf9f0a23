"""HaS pipeline state + two-channel speculation (paper §II-B, Algorithm 1).

All state lives in fixed-shape JAX arrays so every step jits:
  * query cache P = (query_emb [H,d], query_doc_ids [H,k], valid [H]) — a FIFO
    ring (the paper's FIFO replacement policy) with pointer ``q_ptr``.
  * cache channel C_c = FIFO ring of *deduplicated* documents previously
    retrieved from the full database (doc_emb [Dc,d], doc_ids [Dc]).
  * fuzzy channel C_f = an aggressively configured IVFIndex (see
    retrieval/ivf.py), optionally subset-compressed (Table VII).

Entry points (each records itself on :mod:`repro.core.dispatch` so the
serving layers' dispatch-count model is measurable, one record == one
host→device program launch):

``speculate_batch``
    Speculative retrieval of [B, d] queries (Algorithm 1 lines 1–14):
    two-channel top-k -> rerank/merge -> draft -> homology validation, in
    ONE jitted program behind a ``backend="pallas" | "xla"`` switch.  The
    sequential engine calls it at B=1, the scheduler at its speculation
    batch.

    * ``backend="xla"`` is the reference oracle (and the CPU default): a
      dense [B, Dc] cache-channel score matrix plus the jnp IVF search,
      whose bucket gather materializes [B, nprobe, cap, d] in HBM.
    * ``backend="pallas"`` dispatches the cache channel to the streaming
      ``topk_search`` kernel (the doc store never leaves VMEM tiles), the
      fuzzy channel to the scalar-prefetch ``ivf_scan`` kernel (centroid
      top-nprobe on the MXU, buckets DMA'd per grid step with no HBM
      materialization), and validation to the ``homology_score`` kernel.
      On CPU the kernels run in interpret mode (``interpret=None`` picks
      per platform), numerically identical to the TPU path.

    Dedup-merge, rerank and validation are fused into the same jitted
    program, so a batch of B queries costs exactly one device dispatch.
``cache_update``
    Insert one fallback full-retrieval result (Algorithm 1 line 16).
``cache_update_batched``
    Fold a whole full-retrieval batch (leaders + follower attribution from
    ``intra_batch_share``) into ``HasState`` with one donated-buffer
    ``lax.scan`` — exactly equivalent to a sequential fold of
    ``cache_update`` over the unmasked rows, in one dispatch.

Multi-tenant partitioning: :func:`init_tenant_states` stacks T independent
stores into one ``[T, ...]`` pytree (per-tenant ``q_ptr``/``d_ptr``), and
every batch entry point takes an optional ``tenant_ids [B]`` that gathers
each query's slice / scatters each ingest row inside the SAME single
jitted program (per-query group masking in the Pallas kernels, a dense
tenant-compare mask in the XLA oracle).  ``intra_batch_share`` masks its
pairwise homology matrix by tenant so leaders/followers never cross
tenants.  T == 1 reduces bit-exactly to the unpartitioned path.

Fused-list speculation (``HasConfig.fusion == "rrf"``): when the cloud
stage is the hybrid lexical+dense backend, the cached result lists are
*fused* lists whose per-channel raw scores live on incompatible scales — a
cosine similarity and a hashed-term match mass cannot be compared, so the
score-domain dedup-merge would be meaningless.  In rrf mode both
speculation channels merge in RANK domain (``_rrf_merge``: mass
``1/(rrf_k + rank)``, cross-channel duplicates combined onto the first
occurrence) and homology validation weighs each draft slot by its
normalized RRF mass (``homology_scores_weighted`` /
the ``draft_weights`` operand of the ``homology_score`` kernel) instead of
the uniform 1/k overlap ratio.  Acceptance decisions therefore depend only
on the channel *rankings*: any positive monotone transform of either
channel's raw scores leaves drafts, homology scores, and accept bits
bit-identical (pinned by tests/test_hybrid_fusion.py).  Fused lists flow
through ``cache_update*`` unchanged in shape — the cache ingests whatever
the cloud stage returns, so drafts reproduce fused results for homologous
queries.  The default ``fusion="score"`` keeps the pre-hybrid program
byte-identical.

The host-side serving loop (serving/engine.py) sequences these per query
exactly as Algorithm 1; serving/batched.py and serving/scheduler.py drive
them a batch at a time.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch
from repro.core.homology import (homology_scores, homology_scores_batched,
                                 homology_scores_weighted, rrf_draft_weights)
from repro.retrieval.ivf import IVFIndex, ivf_search


@dataclasses.dataclass(frozen=True)
class HasConfig:
    k: int = 10                    # documents per retrieval (draft size)
    tau: float = 0.2               # homology threshold
    h_max: int = 5000              # query-cache capacity (paper default)
    doc_capacity: int = 0          # doc-store slots; 0 -> h_max * k
    nprobe: int = 64               # fuzzy channel buckets probed
    n_buckets: int = 8192          # fuzzy channel total buckets
    use_fuzzy_validation: bool = True    # Table VI 'V'
    use_fuzzy_enhancement: bool = True   # Table VI 'E'
    d: int = 64                    # embedding dim
    fusion: str = "score"          # channel merge: "score" | "rrf"
    rrf_k: float = 60.0            # RRF rank constant (fusion == "rrf")

    @property
    def doc_cap(self) -> int:
        return self.doc_capacity or self.h_max * self.k


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class HasState:
    query_emb: jax.Array      # [H, d]
    query_doc_ids: jax.Array  # [H, k] int32
    query_valid: jax.Array    # [H] bool
    q_ptr: jax.Array          # scalar int32
    doc_emb: jax.Array        # [Dc, d]
    doc_ids: jax.Array        # [Dc] int32 (-1 = empty)
    d_ptr: jax.Array          # scalar int32

    def tree_flatten(self):
        return ((self.query_emb, self.query_doc_ids, self.query_valid,
                 self.q_ptr, self.doc_emb, self.doc_ids, self.d_ptr), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_has_state(cfg: HasConfig, dtype=jnp.float32) -> HasState:
    return HasState(
        query_emb=jnp.zeros((cfg.h_max, cfg.d), dtype),
        query_doc_ids=jnp.full((cfg.h_max, cfg.k), -1, jnp.int32),
        query_valid=jnp.zeros((cfg.h_max,), bool),
        q_ptr=jnp.zeros((), jnp.int32),
        doc_emb=jnp.zeros((cfg.doc_cap, cfg.d), dtype),
        doc_ids=jnp.full((cfg.doc_cap,), -1, jnp.int32),
        d_ptr=jnp.zeros((), jnp.int32),
    )


def init_tenant_states(cfg: HasConfig, n_tenants: int,
                       dtype=jnp.float32) -> HasState:
    """Tenant-partitioned store: a stacked ``[T, ...]`` :class:`HasState`.

    Every array gains a leading tenant axis (``q_ptr``/``d_ptr`` become
    ``[T]``), so each tenant owns an independent query cache + doc-store
    FIFO ring of the full per-tenant capacity (``h_max`` / ``doc_cap``
    EACH).  One tenant's churn can never evict another's homology window,
    and the tenant-batched entry points (:func:`speculate_batch` /
    :func:`cache_update_batched` with ``tenant_ids``) gather/scatter each
    query's slice inside one jitted program.  ``n_tenants == 1`` is
    bit-exactly the single-tenant path on a ``[1, ...]`` view.
    """
    if n_tenants < 1:
        raise ValueError(f"n_tenants must be >= 1, got {n_tenants}")
    return HasState(
        query_emb=jnp.zeros((n_tenants, cfg.h_max, cfg.d), dtype),
        query_doc_ids=jnp.full((n_tenants, cfg.h_max, cfg.k), -1, jnp.int32),
        query_valid=jnp.zeros((n_tenants, cfg.h_max), bool),
        q_ptr=jnp.zeros((n_tenants,), jnp.int32),
        doc_emb=jnp.zeros((n_tenants, cfg.doc_cap, cfg.d), dtype),
        doc_ids=jnp.full((n_tenants, cfg.doc_cap), -1, jnp.int32),
        d_ptr=jnp.zeros((n_tenants,), jnp.int32),
    )


def tenant_count(state: HasState) -> int:
    """Number of tenant partitions (1 for an unstacked single-tenant state)."""
    return state.q_ptr.shape[0] if state.q_ptr.ndim else 1


def tenant_slice(state: HasState, t) -> HasState:
    """View of one tenant's partition as an unstacked single-tenant state."""
    return jax.tree_util.tree_map(lambda a: a[t], state)


def default_backend() -> str:
    """Pallas kernels on TPU, the XLA oracle elsewhere (CPU containers)."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# ---------------------------------------------------------------------------
# Two-channel fast retrieval + homology validation
# ---------------------------------------------------------------------------

def _dedup_merge(s_a, i_a, s_b, i_b, k):
    """Merge two candidate lists, dropping b-entries duplicated in a."""
    dup = jnp.any(i_b[:, None] == i_a[None, :], axis=1) & (i_b >= 0)
    s_b = jnp.where(dup, -jnp.inf, s_b)
    s = jnp.concatenate([s_a, s_b])
    i = jnp.concatenate([i_a, i_b])
    ts, t = jax.lax.top_k(s, k)
    # a dup-masked (or bucket-starved) entry carries -inf but may retain a
    # stale positive doc id; normalize so validation never counts phantom
    # overlaps against the query cache
    return ts, jnp.where(jnp.isfinite(ts), i[t], -1)


def _rrf_merge(i_a, i_b, k, rrf_k):
    """Rank-domain RRF merge of two candidate lists (``fusion == "rrf"``).

    Every slot contributes mass ``1/(rrf_k + rank)`` within its channel;
    ids appearing in both channels sum their mass onto the FIRST occurrence
    (the duplicate slot carries 0 and can never win), and the merged top-k
    is ordered by total mass.  The output "scores" are RRF mass — a pure
    function of the channel *rankings*, so any positive monotone transform
    of either channel's raw scores leaves the fused list unchanged (the
    property that makes fused drafts comparable across channels with
    incompatible score scales).  Empty slots return (-inf, -1) like
    :func:`_dedup_merge`.
    """
    ka, kb = i_a.shape[0], i_b.shape[0]
    ids = jnp.concatenate([i_a, i_b])
    rank = jnp.concatenate(
        [jnp.arange(ka), jnp.arange(kb)]).astype(jnp.float32)
    pos = jnp.arange(ka + kb)
    valid = ids >= 0
    raw = jnp.where(valid, 1.0 / (rrf_k + rank), 0.0)
    same = (ids[:, None] == ids[None, :]) & valid[:, None] & valid[None, :]
    first = ~jnp.any(same & (pos[None, :] < pos[:, None]), axis=1)
    mass = jnp.sum(jnp.where(same, raw[None, :], 0.0), axis=1)
    mass = jnp.where(first & valid, mass, 0.0)
    ts, t = jax.lax.top_k(mass, k)
    return jnp.where(ts > 0, ts, -jnp.inf), jnp.where(ts > 0, ids[t], -1)


def _channel_merge(cfg: HasConfig):
    """The configured two-channel merge, vmapped over the batch axis."""
    if cfg.fusion == "rrf":
        return jax.vmap(
            lambda sa, ia, sb, ib: _rrf_merge(ia, ib, cfg.k, cfg.rrf_k))
    if cfg.fusion == "score":
        return jax.vmap(
            lambda sa, ia, sb, ib: _dedup_merge(sa, ia, sb, ib, cfg.k))
    raise ValueError(f"unknown fusion mode {cfg.fusion!r}")


def _tenant_layout(state: HasState, tenant, name: str) -> None:
    """Refuse a state whose layout does not match the tenant argument:
    ``tenant`` (``tenant_ids`` or ``tenant_id``, called ``name``) is given
    exactly when the state is a stacked :func:`init_tenant_states` store."""
    if tenant is None:
        if state.q_ptr.ndim != 0:
            raise ValueError(
                f"stacked tenant state requires {name} (or slice one "
                "tenant out with tenant_slice)")
    elif state.q_ptr.ndim != 1:
        raise ValueError(f"{name} requires a stacked init_tenant_states "
                         "state")


@functools.partial(
    jax.jit, static_argnames=("cfg", "backend", "interpret", "tile_c"))
def _speculate_batch_impl(cfg: HasConfig, state: HasState, index: IVFIndex,
                          q_embs: jax.Array,
                          tenant_ids: jax.Array | None = None, *,
                          backend: str, interpret: bool, tile_c: int):
    """Two-channel speculation and validation of [B, d] queries.

    Without ``tenant_ids`` the state's arrays are read as they are.  With
    ``tenant_ids [B]`` the state is a stacked ``[T, ...]`` store
    (:func:`init_tenant_states`): the doc store and the query-cache table
    flatten to ``[T*Dc]`` / ``[T*H]`` rows tagged with their tenant, and
    scoring masks the rows of other tenants (group masks in the Pallas
    kernels, a dense tenant-compare mask in the XLA oracle).  Which of the
    two is decided while tracing, so each is a program of its own.  The
    fuzzy channel is the corpus-derived IVF index, shared by construction
    (it holds no per-tenant state).
    """
    nprobe = min(cfg.nprobe, index.n_buckets)
    doc_emb, doc_ids = state.doc_emb, state.doc_ids
    qdi, qvalid = state.query_doc_ids, state.query_valid
    doc_group = row_group = None
    if tenant_ids is not None:
        t, dc = doc_ids.shape
        h = qvalid.shape[1]
        doc_emb = doc_emb.reshape(t * dc, q_embs.shape[1])
        doc_ids = doc_ids.reshape(t * dc)
        qdi = qdi.reshape(t * h, cfg.k)
        qvalid = qvalid.reshape(t * h)
        doc_group = jnp.repeat(jnp.arange(t, dtype=jnp.int32), dc)
        row_group = jnp.repeat(jnp.arange(t, dtype=jnp.int32), h)

    if backend == "pallas":
        from repro.kernels.homology_score import homology_score
        from repro.kernels.ivf_scan import ivf_scan
        from repro.kernels.topk_search import topk_search

        # cache channel: streaming tiled top-k, doc store stays in VMEM
        s_c, slots = topk_search(q_embs, doc_emb, cfg.k, tile_c=tile_c,
                                 valid=doc_ids >= 0, row_group=doc_group,
                                 q_group=tenant_ids, interpret=interpret)
        i_c = jnp.where(jnp.isfinite(s_c),
                        doc_ids[jnp.maximum(slots, 0)], -1)

        # fuzzy channel: centroid top-nprobe on the MXU, then the
        # scalar-prefetch bucket scan (no [B, nprobe, cap, d] gather)
        cscores = q_embs @ index.centroids.T                 # [B, C]
        _, probe = jax.lax.top_k(cscores, nprobe)
        s_f, i_f = ivf_scan(q_embs, probe.astype(jnp.int32),
                            index.bucket_vecs, index.bucket_ids, cfg.k,
                            interpret=interpret)
    elif backend == "xla":
        # reference oracle: dense score matrix + materialized bucket gather
        sc = q_embs @ doc_emb.T                              # [B, Dc]
        ok = doc_ids[None, :] >= 0
        if tenant_ids is not None:
            ok = ok & (doc_group[None, :] == tenant_ids[:, None])
        sc = jnp.where(ok, sc, -jnp.inf)
        s_c, slots = jax.lax.top_k(sc, cfg.k)
        i_c = jnp.where(jnp.isfinite(s_c), doc_ids[slots], -1)
        s_f, i_f = ivf_search(index, q_embs, nprobe=cfg.nprobe, k=cfg.k)
    else:
        raise ValueError(f"unknown backend {backend!r}")

    merge = _channel_merge(cfg)
    s_val, i_val = merge(s_c, i_c, s_f, i_f) \
        if cfg.use_fuzzy_validation else (s_c, i_c)
    s_out, i_out = merge(s_c, i_c, s_f, i_f) \
        if cfg.use_fuzzy_enhancement else (s_c, i_c)

    w_val = rrf_draft_weights(i_val, cfg.rrf_k) \
        if cfg.fusion == "rrf" else None
    if backend == "pallas":
        scores = homology_score(i_val, qdi, qvalid, row_group=row_group,
                                q_group=tenant_ids, draft_weights=w_val,
                                interpret=interpret)
    else:
        valid, axis = qvalid, None
        if tenant_ids is not None:
            valid = qvalid[None, :] \
                & (row_group[None, :] == tenant_ids[:, None])  # [B, T*H]
            axis = 0
        if w_val is not None:
            scores = jax.vmap(homology_scores_weighted,
                              in_axes=(0, None, axis, 0))(
                i_val, qdi, valid, w_val)
        else:
            scores = jax.vmap(homology_scores, in_axes=(0, None, axis))(
                i_val, qdi, valid)
    # with tenants, matched_slot is flat over [T*H]: slot s of tenant t is
    # t*h_max + s
    slot = jnp.argmax(scores, axis=1).astype(jnp.int32)      # [B]
    best = jnp.take_along_axis(scores, slot[:, None], axis=1)[:, 0]
    accept = best > jnp.float32(cfg.tau)

    return {"draft_ids": i_out, "draft_scores": s_out,
            "val_ids": i_val, "accept": accept,
            "homology": best, "matched_slot": slot}


def speculate_batch(cfg: HasConfig, state: HasState, index: IVFIndex,
                    q_embs: jax.Array, backend: str | None = None,
                    interpret: bool | None = None, tile_c: int = 1024,
                    tenant_ids: jax.Array | None = None):
    """Speculative retrieval (Algorithm 1 lines 1–14) of [B, d] queries in
    one device dispatch.

    ``backend=None`` auto-selects (:func:`default_backend`): the Pallas
    kernel pipeline on TPU, the XLA reference on CPU.  ``interpret=None``
    runs the kernels in interpret mode off-TPU.  Returns a dict of [B, ...]
    arrays: the draft ids/scores, the validation draft ``val_ids``, the
    accept flag, the best homology score and the matched cache slot.

    ``tenant_ids [B]`` (optional) routes each query through its tenant's
    partition of a stacked :func:`init_tenant_states` store — still one
    device dispatch per batch; ``matched_slot`` is then flat over ``[T*H]``
    (tenant t's slot s at ``t * h_max + s``).
    """
    if backend is None:
        backend = default_backend()
    if backend != "pallas":
        interpret = False                  # irrelevant: one jit cache entry
    elif interpret is None:
        interpret = jax.default_backend() != "tpu"
    dispatch.record("speculate_batch")
    _tenant_layout(state, tenant_ids, "tenant_ids")
    if tenant_ids is not None:
        tenant_ids = jnp.asarray(tenant_ids, jnp.int32)
    return _speculate_batch_impl(cfg, state, index, q_embs, tenant_ids,
                                 backend=backend, interpret=interpret,
                                 tile_c=tile_c)


# ---------------------------------------------------------------------------
# Intra-batch homology sharing (continuous-batching acceptance channel)
# ---------------------------------------------------------------------------

@jax.jit
def intra_batch_share(val_ids: jax.Array, rejected: jax.Array,
                      tau: jax.Array, pending: jax.Array | None = None,
                      tenant_ids: jax.Array | None = None):
    """Greedy leader election among the rejected drafts of a full batch.

    The snapshot semantics of micro-batched serving cannot let intra-batch
    queries re-identify each other through the cache; this scores them
    against *each other* instead: ``val_ids [B, k]`` are the validation
    drafts, ``rejected [B]`` marks queries awaiting a full retrieval.
    Scanning in admission order, each rejected query either becomes a
    *leader* (pays one full retrieval) or a *follower* of the best earlier
    leader with homology > tau, sharing that leader's full result instead
    of paying for its own (single-flight collapsing of homologous work).

    ``pending [B]`` optionally marks rows that are ALREADY leaders of
    earlier, still-unresolved full retrievals: they keep their leader role
    and serve as attach targets, letting a serving loop extend the election
    window from one batch to its whole reject queue.

    ``tau`` here may reasonably be lower than the validation threshold:
    validation scores a draft against a cached FULL result set, while
    sharing scores two k-item speculative drafts against each other, which
    systematically underestimates the queries' true homology (both sides
    are noisy subsets).

    ``tenant_ids [B]`` (optional) masks the pairwise homology matrix so the
    election never crosses tenants: a rejected query can only follow a
    leader of its own tenant (isolation — one tenant's retrieved documents
    are never served to another's queries), and within each tenant the
    election is unchanged.

    Returns dict(is_leader [B] bool, leader [B] int32, share_score [B]):
    rows neither rejected nor pending keep leader[i] == i with is_leader
    False.
    """
    b = val_ids.shape[0]
    if pending is None:
        pending = jnp.zeros((b,), bool)
    # pairwise homology: scores[i, j] = s(q_i, q_j), 0 on invalid columns
    scores = homology_scores_batched(val_ids, val_ids, rejected | pending)
    if tenant_ids is not None:
        # cross-tenant pairs score -1 < any tau: never elected as leader
        # for a follower of a different tenant
        scores = jnp.where(tenant_ids[:, None] == tenant_ids[None, :],
                           scores, -1.0)
    idx = jnp.arange(b)
    tau = jnp.float32(tau)

    def body(i, carry):
        is_leader, leader, share = carry
        s = jnp.where(is_leader & (idx < i), scores[i], -1.0)
        best = jnp.argmax(s).astype(jnp.int32)
        follow = rejected[i] & ~pending[i] & (s[best] > tau)
        lead = (rejected[i] & ~follow) | pending[i]
        return (is_leader.at[i].set(lead),
                leader.at[i].set(jnp.where(follow, best, i)),
                share.at[i].set(jnp.where(follow, s[best], 0.0)))

    is_leader, leader, share = jax.lax.fori_loop(
        0, b, body, (pending, idx.astype(jnp.int32),
                     jnp.zeros((b,), jnp.float32)))
    return {"is_leader": is_leader, "leader": leader, "share_score": share}


# ---------------------------------------------------------------------------
# Cache update on rejection (Algorithm 1 line 16)
# ---------------------------------------------------------------------------

def _cache_update_impl(cfg: HasConfig, state: HasState, q_emb: jax.Array,
                       full_ids: jax.Array, full_vecs: jax.Array,
                       tenant_id: jax.Array | None = None) -> HasState:
    if tenant_id is not None:
        # one tenant of a stacked store (decided while tracing): gather its
        # slice, update it, scatter it back; the id may be traced
        sl = _cache_update_impl(cfg, tenant_slice(state, tenant_id), q_emb,
                                full_ids, full_vecs)
        return jax.tree_util.tree_map(lambda a, b: a.at[tenant_id].set(b),
                                      state, sl)
    h = cfg.h_max
    slot = state.q_ptr % h
    query_emb = state.query_emb.at[slot].set(q_emb)
    query_doc_ids = state.query_doc_ids.at[slot].set(full_ids)
    query_valid = state.query_valid.at[slot].set(True)

    # doc dedup: only insert ids not already present in the store AND not
    # duplicated earlier in this full result (first occurrence wins —
    # in-batch duplicates must not burn extra ring slots)
    present = jnp.any(full_ids[:, None] == state.doc_ids[None, :], axis=1)
    pos_in = jnp.arange(full_ids.shape[0])
    dup_in_batch = jnp.any(
        (full_ids[:, None] == full_ids[None, :])
        & (pos_in[None, :] < pos_in[:, None]), axis=1)
    new = (~present) & (~dup_in_batch) & (full_ids >= 0)
    # ring positions for the new docs
    offs = jnp.cumsum(new.astype(jnp.int32)) - 1
    pos = (state.d_ptr + offs) % state.doc_ids.shape[0]
    pos = jnp.where(new, pos, state.doc_ids.shape[0])        # drop non-new
    doc_ids = state.doc_ids.at[pos].set(full_ids, mode="drop")
    doc_emb = state.doc_emb.at[pos].set(full_vecs, mode="drop")
    d_ptr = state.d_ptr + jnp.sum(new.astype(jnp.int32))

    return HasState(query_emb=query_emb, query_doc_ids=query_doc_ids,
                    query_valid=query_valid, q_ptr=state.q_ptr + 1,
                    doc_emb=doc_emb, doc_ids=doc_ids, d_ptr=d_ptr)


_cache_update_jit = functools.partial(
    jax.jit, static_argnames=("cfg",), donate_argnames=("state",))(
        _cache_update_impl)


def cache_update(cfg: HasConfig, state: HasState, q_emb: jax.Array,
                 full_ids: jax.Array, full_vecs: jax.Array,
                 tenant_id=None) -> HasState:
    """Insert (q, D_full) into P and the new docs into C_c (FIFO, dedup).

    ``tenant_id`` (optional) targets one partition of a stacked
    :func:`init_tenant_states` store; all other partitions are untouched.
    """
    dispatch.record("cache_update")
    _tenant_layout(state, tenant_id, "tenant_id")
    if tenant_id is not None:
        if not 0 <= int(tenant_id) < state.q_ptr.shape[0]:
            raise ValueError(
                f"tenant_id {int(tenant_id)} out of range for "
                f"{state.q_ptr.shape[0]} tenants")
        tenant_id = jnp.int32(tenant_id)
    return _cache_update_jit(cfg, state, q_emb, full_ids, full_vecs,
                             tenant_id)


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("state",))
def _cache_update_batched_jit(cfg: HasConfig, state: HasState,
                              q_embs: jax.Array, full_ids: jax.Array,
                              full_vecs: jax.Array, mask: jax.Array,
                              tenant_ids: jax.Array | None = None
                              ) -> HasState:
    def body(st, xs):
        q, ids, vecs, on, t = xs
        st = jax.lax.cond(
            on, lambda s: _cache_update_impl(cfg, s, q, ids, vecs, t),
            lambda s: s, st)
        return st, None

    state, _ = jax.lax.scan(
        body, state, (q_embs, full_ids, full_vecs, mask, tenant_ids))
    return state


def cache_update_batched(cfg: HasConfig, state: HasState, q_embs: jax.Array,
                         full_ids: jax.Array, full_vecs: jax.Array,
                         mask: jax.Array | None = None,
                         tenant_ids: jax.Array | None = None) -> HasState:
    """Fold a whole full-retrieval batch into the cache in ONE dispatch.

    q_embs [B,d], full_ids [B,k], full_vecs [B,k,d]; ``mask [B]`` (optional)
    marks real rows — padding rows (mask False) leave the state untouched,
    so serving layers can reuse one compiled shape for variable-size ingest
    batches.  Equivalent to folding :func:`cache_update` sequentially over
    the unmasked rows (a donated-buffer ``lax.scan`` of the same body), but
    costs one device dispatch instead of B.

    ``tenant_ids [B]`` (optional) scatters each row's ingest into its
    tenant's partition of a stacked :func:`init_tenant_states` store —
    equivalent to folding :func:`cache_update` with ``tenant_id`` per row,
    still in one dispatch.
    """
    if mask is None:
        mask = jnp.ones((q_embs.shape[0],), bool)
    dispatch.record("cache_update_batched")
    _tenant_layout(state, tenant_ids, "tenant_ids")
    if tenant_ids is not None:
        tenant_ids = jnp.asarray(tenant_ids, jnp.int32)
    return _cache_update_batched_jit(cfg, state, q_embs, full_ids,
                                     full_vecs, mask, tenant_ids)


def cache_update_chunked(cfg: HasConfig, state: HasState, q_embs, full_ids,
                         full_vecs=None, *, corpus=None, chunk: int,
                         tenant_ids=None) -> HasState:
    """Fold N host-side update rows through ``cache_update_batched``.

    The one pad-to-fixed-shape helper shared by every serving layer
    (scheduler ingest, batched-engine reject ingest, warm-standby delta
    replay): rows are chunked to ``chunk``, and EVERY chunk — including the
    final partial one — is zero-padded to ``[chunk, ...]`` with masked rows
    so a single compiled shape serves any N (the tail chunk never jits a
    second shape; tests assert this via the ``core/dispatch`` probe plus
    the jit cache size).  ``q_embs [N, d]`` and ``full_ids [N, k]`` are
    host arrays/lists; pass either ``full_vecs [N, k, d]`` explicitly or a
    device ``corpus`` to gather them from by id on device (one gather per
    chunk, no host round-trip).  ``tenant_ids [N]`` (optional) scatters
    each row into its tenant's partition of a stacked store.
    """
    q_embs = np.asarray(q_embs, np.float32)
    full_ids = np.asarray(full_ids, np.int32)
    n, k, d = len(q_embs), full_ids.shape[1], q_embs.shape[1]
    if full_vecs is not None:
        full_vecs = np.asarray(full_vecs, np.float32)
    if tenant_ids is not None:
        tenant_ids = np.asarray(tenant_ids, np.int32)
    # host chunks go to the jitted programs as numpy arrays: the compiled
    # call moves them to the device itself, without a ``jnp.asarray``'s
    # host overhead for each
    for i0 in range(0, n, chunk):
        m = min(chunk, n - i0)
        embs = np.zeros((chunk, d), np.float32)
        ids = np.zeros((chunk, k), np.int32)
        mask = np.zeros((chunk,), bool)
        embs[:m] = q_embs[i0:i0 + m]
        ids[:m] = full_ids[i0:i0 + m]
        mask[:m] = True
        if full_vecs is None:
            vecs = _gather_rows(corpus, ids)
        else:
            vecs = np.zeros((chunk, k, d), np.float32)
            vecs[:m] = full_vecs[i0:i0 + m]
        tids = None
        if tenant_ids is not None:
            tids = np.zeros((chunk,), np.int32)     # pad rows: tenant 0,
            tids[:m] = tenant_ids[i0:i0 + m]        # masked off anyway
        state = cache_update_batched(cfg, state, embs, ids, vecs, mask,
                                     tenant_ids=tids)
    return state


# ``corpus[ids]`` as one compiled program: eager indexing re-runs JAX's
# index normalisation on the host (about a millisecond) at every call
_gather_rows = jax.jit(lambda corpus, ids: corpus[ids])


def cache_memory_bytes(cfg: HasConfig) -> int:
    """Memory footprint of the cache (Table IX 'Mem' column)."""
    d = cfg.d
    per_query = d * 4 + cfg.k * 4 + 1
    per_doc = d * 4 + 4
    return cfg.h_max * per_query + cfg.doc_cap * per_doc


def speculation_bytes_moved(cfg: HasConfig, n_buckets: int, bucket_cap: int,
                            b: int, backend: str) -> int:
    """Analytic HBM traffic estimate for one ``speculate_batch`` call.

    Shared terms: the centroid matmul reads [C, d] once and validation reads
    the [H, k] id table once.  The backends differ on the two channels:

    * ``xla``   — the cache channel writes+reads a dense [B, Dc] score
      matrix on top of the doc-store stream, and the fuzzy channel's bucket
      gather materializes [B, nprobe, cap, d] in HBM (write + re-read for
      scoring), tripling bucket traffic.
    * ``pallas`` — the doc store streams through VMEM tiles once regardless
      of B, and each probed bucket is DMA'd and scored in place (read once).
    """
    d, k = cfg.d, cfg.k
    nprobe = min(cfg.nprobe, n_buckets)
    common = n_buckets * d * 4 + cfg.h_max * k * 4
    doc_stream = cfg.doc_cap * d * 4
    bucket_read = b * nprobe * bucket_cap * d * 4
    if backend == "pallas":
        return common + doc_stream + bucket_read
    score_mat = 2 * b * cfg.doc_cap * 4          # write + re-read
    return common + doc_stream + score_mat + 3 * bucket_read
