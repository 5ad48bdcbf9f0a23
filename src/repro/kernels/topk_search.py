"""Pallas TPU kernel: streaming similarity + running top-k (the ENNS scan).

The retrieval hot-spot of the paper: scores = q @ corpus^T with top-k
selection, streamed over corpus tiles so the score matrix never leaves VMEM.

TPU mapping:
  * grid = corpus tiles; each step loads a [TILE_C, d] corpus block into
    VMEM and issues one [B, d] x [d, TILE_C] MXU matmul.
  * the corpus streams as it is stored: when N is no whole number of
    tiles, the last tile is a partial block read in place (no padded copy
    of the [N, d] array).  Its rows past N hold unspecified values (NaN
    included), never assumed to be zero: the kernel turns their scores
    into -inf with a select before any comparison.
  * the running top-k (vals/idx [B, K]) lives in the revisited output block
    (same index_map every step => stays resident in VMEM).
  * merge = K rounds of (tile argmax -> replace running argmin) — O(K·TILE)
    vector-unit compares, amortized against the O(d·TILE) MXU work; there is
    no general sort primitive in Mosaic, and for K<=128 this beats one.
  * the caller finishes with a single jnp.sort over [B, K] (K elements).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _topk_kernel(q_ref, c_ref, valid_ref, *rest, k: int, tile_c: int,
                 n_corpus: int, grouped: bool):
    if grouped:
        row_group_ref, q_group_ref, vals_ref, idx_ref = rest
    else:
        (vals_ref, idx_ref), row_group_ref, q_group_ref = rest, None, None
    step = pl.program_id(0)
    b = q_ref.shape[0]

    @pl.when(step == 0)
    def _init():
        vals_ref[...] = jnp.full((b, k), -jnp.inf, jnp.float32)
        idx_ref[...] = jnp.full((b, k), -1, jnp.int32)

    q = q_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    valid = valid_ref[...]                                # [TILE_C]
    scores = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # [B, TILE_C]
    base = step * tile_c
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    # mask the partial last tile's rows past N (unspecified values, so a
    # select and never a multiply) and invalid corpus rows (empty doc-store
    # ring slots when scanning a HaS cache channel)
    ok = (base + col < n_corpus) & valid[None, :]
    if grouped:
        # partitioned scan: row i may only win for queries of its group
        # (tenant) — one extra [B, TILE_C] int compare per tile
        ok &= row_group_ref[...][None, :] == q_group_ref[...][:, None]
    scores = jnp.where(ok, scores, -jnp.inf)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (b, k), 1)

    def merge(i, carry):
        scores, vals, idx = carry
        cur = jnp.max(scores, axis=1)                     # [B]
        arg = jnp.argmax(scores, axis=1).astype(jnp.int32)
        rmin = jnp.min(vals, axis=1)
        rarg = jnp.argmin(vals, axis=1).astype(jnp.int32)
        better = cur > rmin                               # [B]
        hit = (kcol == rarg[:, None]) & better[:, None]
        vals = jnp.where(hit, cur[:, None], vals)
        idx = jnp.where(hit, (base + arg)[:, None], idx)
        scores = jnp.where(col == arg[:, None], -jnp.inf, scores)
        return scores, vals, idx

    _, vals, idx = jax.lax.fori_loop(
        0, k, merge, (scores, vals_ref[...], idx_ref[...]))
    vals_ref[...] = vals
    idx_ref[...] = idx


@functools.partial(jax.jit, static_argnames=("k", "tile_c", "interpret"))
def topk_search(queries: jax.Array, corpus: jax.Array, k: int,
                tile_c: int = 1024, valid: jax.Array | None = None,
                row_group: jax.Array | None = None,
                q_group: jax.Array | None = None,
                interpret: bool = False):
    """queries [B,d], corpus [N,d] -> (vals [B,k] desc-sorted, idx [B,k]).

    ``valid`` ([N] bool, optional) masks corpus rows out of the result —
    used by the HaS cache channel, whose doc-store ring contains empty
    slots (doc_ids < 0) that must never win a top-k position.

    ``row_group`` ([N] int32) / ``q_group`` ([B] int32, both or neither)
    partition the scan: corpus row i may only win a top-k position for
    query b when ``row_group[i] == q_group[b]`` — the multi-tenant cache
    channel, where every tenant's doc-store slice scans in the SAME kernel
    launch but rows never cross tenants.  The group ids stream with the
    corpus tiles, so the partitioned scan stays one program launch with one
    extra [B, TILE_C] compare per tile.

    The grid is ``cdiv(N, tile_c)`` tiles of the arrays as passed: ``corpus``,
    ``valid`` and ``row_group`` are never padded to whole tiles.  The last
    tile is a partial block read in place, and the kernel masks its rows
    past N whatever they hold.
    """
    n, d = corpus.shape
    b = queries.shape[0]
    if (row_group is None) != (q_group is None):
        raise ValueError("row_group and q_group must be passed together")
    grouped = row_group is not None
    if valid is None:
        valid = jnp.ones((n,), bool)
    n_tiles = pl.cdiv(n, tile_c)

    in_specs = [
        pl.BlockSpec((b, d), lambda i: (0, 0)),        # queries resident
        pl.BlockSpec((tile_c, d), lambda i: (i, 0)),   # corpus stream
        pl.BlockSpec((tile_c,), lambda i: (i,)),       # validity stream
    ]
    operands = [queries, corpus, valid]
    if grouped:
        in_specs += [
            pl.BlockSpec((tile_c,), lambda i: (i,)),   # row groups stream
            pl.BlockSpec((b,), lambda i: (0,)),        # query groups resident
        ]
        operands += [row_group.astype(jnp.int32), q_group.astype(jnp.int32)]

    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, tile_c=tile_c, n_corpus=n,
                          grouped=grouped),
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((b, k), lambda i: (0, 0)),        # running top-k
            pl.BlockSpec((b, k), lambda i: (0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, k), jnp.float32),
                   jax.ShapeDtypeStruct((b, k), jnp.int32)],
        interpret=interpret,
    )(*operands)
    # final K-element sort outside the kernel
    order = jnp.argsort(-vals, axis=1)
    return jnp.take_along_axis(vals, order, axis=1), \
        jnp.take_along_axis(idx, order, axis=1)
