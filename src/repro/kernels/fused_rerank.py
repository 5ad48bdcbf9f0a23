"""Pallas TPU kernel: RRF fusion + diversification + rerank of a hybrid pool.

One grid step per query fuses the dense and lexical channels' top-k lists
entirely in rank domain:

  1. RRF mass: slot j of either channel contributes ``1 / (rrf_k + rank_j)``;
     duplicate doc ids across channels sum their mass onto the *first*
     occurrence (later occurrences get mass 0, so they can never be
     selected twice).  Rank-domain fusion is scale-free: any positive
     monotone transform of either channel's raw scores leaves the fused
     ordering unchanged.
  2. Greedy near-duplicate diversification: candidates are visited in
     descending RRF-mass order; a candidate survives only if its cosine
     similarity to every already-selected doc stays below
     ``diversify_sim`` (``None`` disables the pass — the ablation arm).
  3. Rerank: the final order is fused mass descending — the rank-domain
     fusion DECIDES — with the dense score ``pool_vec · q`` arbitrating
     exact-mass ties (slots holding the same rank in different channels
     carry identical mass; the dense model orders them instead of raw
     pool position).  Dropped slots (invalid, duplicate occurrences,
     diversity rejects) come back as ``-inf``.

The per-query pool is small (kd + kl slots), so the whole fusion state lives
in VMEM and the kernel is pure vector-unit work; the caller finishes with a
single two-key sort over [B, P] (same split as ``topk_search``'s final sort).

``_fuse_scores`` is shared with the XLA oracle
(``kernels/ref.py::fused_rerank_ref`` runs it per query via ``lax.map``), so
backends agree bit-for-bit on the fused output, invalid (-1) slots and
cross-channel duplicates included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fuse_scores(q, ids, ids_col, vecs, *, kd: int, kl: int, rrf_k: float,
                 diversify_sim: float | None):
    """Fuse one query's pool: q [1,d], ids [1,P] (and the same ids as a
    column, [P,1]), vecs [P,d] -> ([1,P], [1,P]) f32.

    Returns ``(mass, rscore)``: the fused RRF mass for selected docs
    (``-inf`` for dropped ones — invalid slots, duplicate occurrences,
    diversity rejects) and the dense rerank score used as the tie-break
    key.  Every value is 2-D and every pick a masked reduction, so the
    body lowers through Mosaic as written.  Shared by the kernel body and
    the XLA oracle.
    """
    p = kd + kl
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, p), 1)
    pos_col = jax.lax.broadcasted_iota(jnp.int32, (p, 1), 0)
    rank_col = jnp.where(pos_col < kd, pos_col, pos_col - kd).astype(
        jnp.float32)
    valid = ids >= 0
    valid_col = ids_col >= 0
    raw_col = jnp.where(valid_col, 1.0 / (rrf_k + rank_col), 0.0)   # [P, 1]
    # combine duplicate ids: all of an id's mass lands on its first slot
    # (same[j, i]: slot j holds slot i's id; the matrix is symmetric)
    same = (ids_col == ids) & valid_col & valid                     # [P, P]
    dup_before = jnp.max(jnp.where(same & (pos_col < pos), 1.0, 0.0),
                         axis=0, keepdims=True) > 0.0
    mass = jnp.sum(jnp.where(same, raw_col, 0.0), axis=0, keepdims=True)
    mass = jnp.where(valid & ~dup_before, mass, 0.0)                # [1, P]

    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    rscore = dot(q, vecs)                                           # [1, P]
    if diversify_sim is None:
        selected = mass > 0.0
    else:
        norm = jnp.sqrt(jnp.sum(vecs * vecs, axis=1, keepdims=True))
        vn = vecs / jnp.maximum(norm, 1e-12)
        sims = dot(vn, vn)                                          # [P, P]

        def body(i, carry):                # selected: 1.0 / 0.0 per slot
            selected, rem = carry
            top = jnp.max(rem, axis=1, keepdims=True)      # next-best mass
            c = jnp.argmax(rem, axis=1).astype(jnp.int32)[:, None]
            row = jnp.max(jnp.where(pos_col == c, sims, -jnp.inf), axis=0,
                          keepdims=True)                   # sims[c, :]
            msim = jnp.max(jnp.where(selected > 0.0, row, -jnp.inf),
                           axis=1, keepdims=True)
            keep = (top > 0.0) & (msim < diversify_sim)
            at = pos == c
            selected = jnp.where(at & keep, 1.0, selected)
            rem = jnp.where(at, 0.0, rem)
            return selected, rem

        selected, _ = jax.lax.fori_loop(
            0, p, body, (jnp.zeros((1, p), jnp.float32), mass))
        selected = selected > 0.0
    return jnp.where(selected, mass, -jnp.inf), rscore


def _fused_kernel(q_ref, ids_ref, ids_col_ref, vecs_ref, mass_ref,
                  rscore_ref, *, kd: int, kl: int, rrf_k: float,
                  diversify_sim: float | None):
    mass, rscore = _fuse_scores(q_ref[0], ids_ref[0], ids_col_ref[0],
                                vecs_ref[0], kd=kd, kl=kl, rrf_k=rrf_k,
                                diversify_sim=diversify_sim)
    mass_ref[0] = mass
    rscore_ref[0] = rscore


def _final_topk(sel_mass, rscore, pool_ids, k: int):
    """Two-key desc sort of the fused pool, then slice the top-k (outside
    the kernel): primary key fused mass, secondary key dense rerank score
    (both stable argsorts, so the composition is lexicographic and
    deterministic across backends)."""
    o2 = jnp.argsort(-rscore, axis=1, stable=True)
    m2 = jnp.take_along_axis(sel_mass, o2, axis=1)
    o1 = jnp.argsort(-m2, axis=1, stable=True)
    order = jnp.take_along_axis(o2, o1, axis=1)[:, :k]
    vals = jnp.take_along_axis(sel_mass, order, axis=1)
    ids = jnp.take_along_axis(pool_ids, order, axis=1)
    return vals, jnp.where(jnp.isfinite(vals), ids, -1)


@functools.partial(jax.jit, static_argnames=(
    "kd", "k", "rrf_k", "diversify_sim", "interpret"))
def fused_rerank(queries: jax.Array, pool_ids: jax.Array,
                 pool_vecs: jax.Array, kd: int, k: int,
                 rrf_k: float = 60.0, diversify_sim: float | None = None,
                 interpret: bool = False):
    """queries [B,d], pool_ids [B,P], pool_vecs [B,P,d] ->
    (scores [B,k] desc-sorted fused RRF masses, ids [B,k]).

    ``pool_ids[:, :kd]`` is the dense channel's list, the rest the lexical
    channel's; ``-1`` marks invalid slots (their ``pool_vecs`` rows must be
    zero).  Slots dropped by fusion come back as ``-inf`` / ``-1``.
    """
    b, p = pool_ids.shape
    d = queries.shape[1]
    kl = p - kd
    pool_ids = pool_ids.astype(jnp.int32)
    mass, rscore = pl.pallas_call(
        functools.partial(_fused_kernel, kd=kd, kl=kl, rrf_k=rrf_k,
                          diversify_sim=diversify_sim),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),  # this query
            pl.BlockSpec((1, 1, p), lambda i: (i, 0, 0)),  # its fused pool
            pl.BlockSpec((1, p, 1), lambda i: (i, 0, 0)),  # ... as a column
            pl.BlockSpec((1, p, d), lambda i: (i, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, 1, p), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, 1, p), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, 1, p), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, p), jnp.float32)],
        interpret=interpret,
    )(queries.astype(jnp.float32)[:, None, :], pool_ids[:, None, :],
      pool_ids[:, :, None], pool_vecs.astype(jnp.float32))
    mass, rscore = mass[:, 0], rscore[:, 0]
    return _final_topk(mass, rscore, pool_ids, k)
