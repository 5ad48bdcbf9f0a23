"""Pallas TPU kernel: IVF bucket gather-and-score (the fuzzy channel).

TPU mapping of Faiss's inverted-list probe: the probed bucket indices are
*scalar-prefetched* (PrefetchScalarGridSpec) so the BlockSpec index_map can
select which bucket block to DMA from HBM — a data-dependent gather with no
host round-trip.  Each grid step (query b, probe p) scores one bucket on
the MXU and folds it into the query's running top-k (revisited VMEM block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.layout import Format, Layout
from jax.experimental.pallas import tpu as pltpu


def bucket_format(shape, dtype, sharding) -> Format | None:
    """The format the Mosaic call reads a ``[C, cap, d]`` bucket array in:
    row-major, on ``sharding``.

    Returns None where the backend stores ``shape`` row-major already or
    has no tiled layouts (CPU), so there is nothing to ask for.  A TPU
    stores ``f32[2048, 977, 768]`` with the buckets second-minor (977 rows
    are no whole number of 8-row tiles); a program handed that array
    relays the whole of it out before the kernel can run.
    """
    device = next(iter(sharding.device_set))
    try:
        default = Layout.from_pjrt_layout(device.client.get_default_layout(
            jnp.dtype(dtype), sharding.shard_shape(tuple(shape)), device))
    except jax.errors.JaxRuntimeError:     # no layouts on this backend
        return None
    row_major = tuple(range(len(shape)))
    if not default.tiling or default.major_to_minor == row_major:
        return None
    return Format(Layout(row_major), sharding)


def _ivf_kernel(probe_ref, *refs, k: int, scaled: bool):
    """One (query, probed-bucket) grid step.  With ``scaled`` (compressed
    residency) a second scalar-prefetch operand carries the query-centroid
    probe score and a ``[2, cap]`` block the per-half int8 dequant scales:
    codes are centroid residuals, so scoring fuses the dequant as
    ``q.c + (q_lo.v8_lo)s_lo + (q_hi.v8_hi)s_hi`` — the int8 codes are the
    only per-slot HBM traffic.

    Every block is at least 2-D with full minor dims (Mosaic's tiling
    rule) and the merge picks the winning id with a masked reduction, not
    a dynamic vector gather (which Mosaic does not lower)."""
    if scaled:
        bias_ref, q_ref, vecs_ref, ids_ref, scales_ref, vals_ref, oidx_ref = refs
    else:
        q_ref, vecs_ref, ids_ref, vals_ref, oidx_ref = refs
    b, p = pl.program_id(0), pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        vals_ref[...] = jnp.full(vals_ref.shape, -jnp.inf, jnp.float32)
        oidx_ref[...] = jnp.full(oidx_ref.shape, -1, jnp.int32)

    q = q_ref[0].astype(jnp.float32)                       # [1, d]
    vecs = vecs_ref[0].astype(jnp.float32)                 # [cap, d]
    gids = ids_ref[0]                                      # [1, cap]
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if scaled:
        h = q.shape[1] // 2
        sc = scales_ref[0]                                 # [2, cap]
        scores = (dot(q[:, :h], vecs[:, :h]) * sc[0:1, :]
                  + dot(q[:, h:], vecs[:, h:]) * sc[1:2, :]
                  + bias_ref[b, p])                        # fused dequant
    else:
        scores = dot(q, vecs)                              # [1, cap]
    scores = jnp.where(gids >= 0, scores, -jnp.inf)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    cap_col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)

    def merge(i, carry):
        scores, vals, idx = carry                          # [1,cap], [1,k] x2
        cur = jnp.max(scores, axis=1, keepdims=True)       # [1, 1]
        arg = jnp.argmax(scores, axis=1).astype(jnp.int32)[:, None]
        rmin = jnp.min(vals, axis=1, keepdims=True)
        rarg = jnp.argmin(vals, axis=1).astype(jnp.int32)[:, None]
        at = cap_col == arg
        gid = jnp.max(jnp.where(at, gids, -1), axis=1, keepdims=True)
        hit = (kcol == rarg) & (cur > rmin)
        vals = jnp.where(hit, cur, vals)
        idx = jnp.where(hit, gid, idx)
        scores = jnp.where(at, -jnp.inf, scores)
        return scores, vals, idx

    _, vals, idx = jax.lax.fori_loop(
        0, k, merge, (scores, vals_ref[0], oidx_ref[0]))
    vals_ref[0] = vals
    oidx_ref[0] = idx


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def ivf_scan(queries: jax.Array, probe: jax.Array, bucket_vecs: jax.Array,
             bucket_ids: jax.Array, k: int, interpret: bool = False,
             bucket_scales: jax.Array | None = None,
             probe_bias: jax.Array | None = None):
    """queries [B,d], probe [B,P] int32, bucket_vecs [C,cap,d],
    bucket_ids [C,cap] -> (vals [B,k] desc, global ids [B,k]).

    ``bucket_scales [C,cap,2]`` + ``probe_bias [B,P]`` (optional, together)
    enable the compressed-residency path: ``bucket_vecs`` holds int8
    centroid-residual codes, ``probe_bias`` the query-centroid score of
    each probed bucket (the probe matmul already computed it), and each
    slot scores as ``bias + (q_lo.v8_lo)s_lo + (q_hi.v8_hi)s_hi`` inside
    the kernel (per-half scales factor out of the half inner products).
    """
    b, d = queries.shape
    nprobe = probe.shape[1]
    n_buckets, cap = bucket_ids.shape
    scaled = bucket_scales is not None
    if scaled and probe_bias is None:
        raise ValueError("bucket_scales (residual codes) requires probe_bias")
    # the scalar-prefetched operands (probe, and the bias when scaled) come
    # first in every index_map
    row = lambda bi, pi, *pre: (bi, 0, 0)                  # noqa: E731
    bucket = lambda bi, pi, probe, *pre: (probe[bi, pi], 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, 1, d), row),
                pl.BlockSpec((1, cap, d), bucket),
                pl.BlockSpec((1, 1, cap), bucket)]
    operands = [queries.reshape(b, 1, d), bucket_vecs,
                bucket_ids.reshape(n_buckets, 1, cap)]
    prefetch = [probe]
    if scaled:
        in_specs.append(pl.BlockSpec((1, 2, cap), bucket))
        operands.append(jnp.swapaxes(bucket_scales, 1, 2))  # [C, 2, cap]
        prefetch.append(probe_bias.astype(jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, nprobe),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, 1, k), row),
                   pl.BlockSpec((1, 1, k), row)],
    )
    vals, idx = pl.pallas_call(
        functools.partial(_ivf_kernel, k=k, scaled=scaled),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, 1, k), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, k), jnp.int32)],
        interpret=interpret,
    )(*prefetch, *operands)
    vals, idx = vals[:, 0], idx[:, 0]
    order = jnp.argsort(-vals, axis=1)
    return jnp.take_along_axis(vals, order, axis=1), \
        jnp.take_along_axis(idx, order, axis=1)
