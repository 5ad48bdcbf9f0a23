"""Pallas TPU kernel: homology-score overlap counting (paper §III-C).

The TPU-native inverted index: draft doc-ids [B, k] are compared against the
cached doc-id table [H, k] with a tiled compare-reduce — O(H·k²) int
compares on the vector units, streamed over H tiles.  Replaces the paper's
CPU hash-map index J (DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _homology_kernel(draft_ref, cache_ref, *rest, k: int, grouped: bool,
                     weighted: bool):
    rest = list(rest)
    w_ref = rest.pop(0) if weighted else None
    if grouped:
        row_group_ref, q_group_ref, out_ref = rest
    else:
        (out_ref,), row_group_ref, q_group_ref = rest, None, None
    draft = draft_ref[...]                                 # [B, k]
    cache = cache_ref[...]                                 # [k, TILE_H]
    # draft slot j hits row i when any cached slot of row i holds its id:
    # k*k compares of [B, 1] against [1, TILE_H], all 2-D for Mosaic
    s = None
    for j in range(k):
        dj = draft[:, j:j + 1]                             # [B, 1]
        hit = dj == cache[0:1, :]
        for c in range(1, k):
            hit |= dj == cache[c:c + 1, :]
        hit = (hit & (dj >= 0)).astype(jnp.float32)        # [B, TILE_H]
        if weighted:
            # fused-list validation: each draft slot carries its
            # (normalized) RRF mass instead of 1/k — rank-domain,
            # score-scale free
            hit = hit * w_ref[:, j:j + 1]
        s = hit if s is None else s + hit
    if not weighted:
        s = s / k
    if grouped:
        # partitioned table: cached query row i only scores against drafts
        # of its own group (tenant) — cross-tenant rows read as 0 overlap
        s = jnp.where(row_group_ref[...] == q_group_ref[...], s, 0.0)
    out_ref[...] = s


@functools.partial(jax.jit, static_argnames=("tile_h", "interpret"))
def homology_score(draft_ids: jax.Array, cache_doc_ids: jax.Array,
                   cache_valid: jax.Array, tile_h: int = 512,
                   row_group: jax.Array | None = None,
                   q_group: jax.Array | None = None,
                   draft_weights: jax.Array | None = None,
                   interpret: bool = False):
    """draft [B,k] int32, cache [H,k] int32, valid [H] -> scores [B,H] f32.

    ``row_group`` ([H] int32) / ``q_group`` ([B] int32, both or neither)
    partition the cached-query table: row i contributes a non-zero score
    for draft b only when ``row_group[i] == q_group[b]`` (multi-tenant
    validation — every tenant's query-cache slice scores in the same
    kernel launch without cross-tenant re-identification).

    ``draft_weights`` ([B, k] f32, optional) switches the score from the
    uniform overlap ratio (1/k per matched slot) to per-slot weighted mass
    (the fused-list RRF validation of ``HasConfig.fusion == "rrf"``;
    weights pre-normalized by :func:`~repro.core.homology.rrf_draft_weights`).

    Invalid rows get id ``-2`` in every slot before the call: drafts only
    count ids ``>= 0``, so such a row scores exactly 0 with no validity
    stream.  The table streams transposed (``[k, H]``), so every block is
    2-D with a lane-aligned ``tile_h``.
    """
    b, k = draft_ids.shape
    h = cache_doc_ids.shape[0]
    if (row_group is None) != (q_group is None):
        raise ValueError("row_group and q_group must be passed together")
    grouped = row_group is not None
    weighted = draft_weights is not None
    n_tiles = pl.cdiv(h, tile_h)
    pad = n_tiles * tile_h - h
    cache_t = jnp.where(cache_valid[:, None], cache_doc_ids, -2).T   # [k, H]
    if pad:
        cache_t = jnp.pad(cache_t, ((0, 0), (0, pad)), constant_values=-2)
        if grouped:
            row_group = jnp.pad(row_group, (0, pad), constant_values=-1)

    in_specs = [
        pl.BlockSpec((b, k), lambda i: (0, 0)),
        pl.BlockSpec((k, tile_h), lambda i: (0, i)),
    ]
    operands = [draft_ids, cache_t]
    if weighted:
        in_specs += [pl.BlockSpec((b, k), lambda i: (0, 0))]  # weights resident
        operands += [draft_weights.astype(jnp.float32)]
    if grouped:
        in_specs += [
            pl.BlockSpec((1, tile_h), lambda i: (0, i)),   # row groups
            pl.BlockSpec((b, 1), lambda i: (0, 0)),        # query groups
        ]
        operands += [row_group.astype(jnp.int32)[None, :],
                     q_group.astype(jnp.int32)[:, None]]

    out = pl.pallas_call(
        functools.partial(_homology_kernel, k=k, grouped=grouped,
                          weighted=weighted),
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b, tile_h), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, n_tiles * tile_h), jnp.float32),
        interpret=interpret,
    )(*operands)
    return out[:, :h]
