"""IVF (inverted-file) approximate search in pure JAX.

Build: k-means over the corpus -> centroids; vectors re-ordered into
fixed-capacity buckets (power-law bucket sizes are padded/truncated so every
shape is static — the TPU adaptation of Faiss's variable-length inverted
lists; truncation loss is the deliberate 'fuzzy' accuracy trade of HaS).

Search: centroid matmul -> top-nprobe buckets -> bucket gather -> scoring ->
local top-k.  The gather+score inner loop is the Pallas ``ivf_scan`` kernel's
oracle.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format

from repro.kernels.ivf_scan import bucket_format
from repro.training.compression import quantize_int8


@dataclasses.dataclass
class IVFIndex:
    centroids: jax.Array     # [C, d]
    bucket_vecs: jax.Array   # [C, cap, d]
    bucket_ids: jax.Array    # [C, cap] int32 global ids (-1 = pad)
    bucket_counts: jax.Array  # [C] int32

    @property
    def n_buckets(self) -> int:
        return self.centroids.shape[0]

    @property
    def capacity(self) -> int:
        return self.bucket_ids.shape[1]

    def tree_flatten(self):
        return ((self.centroids, self.bucket_vecs, self.bucket_ids,
                 self.bucket_counts), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    IVFIndex, IVFIndex.tree_flatten, IVFIndex.tree_unflatten)


@dataclasses.dataclass
class CompressedIVFIndex:
    """IVF index with int8 residual-coded bucket storage.

    The compressed-residency mode of the ANN cloud backend: ``bucket_vecs``
    holds symmetric-int8 codes of the RESIDUAL ``v - centroid[bucket]``
    (residuals are much smaller than the vectors, so the int8 grid spends
    its 8 bits where the information is), with one dequant scale per
    d/2-dim half of each slot.  The scan operand is ~3.6x smaller than f32
    and the dequant fuses into scoring:

        ``q . v  =  q . c  +  (q_lo . v8_lo) s_lo  +  (q_hi . v8_hi) s_hi``

    — the centroid term is the probe score the search already computed, and
    the per-half scales factor out of the half inner products, so no f32
    vectors are ever materialized.
    """
    centroids: jax.Array      # [C, d] f32
    bucket_vecs: jax.Array    # [C, cap, d] int8 residual codes
    bucket_scales: jax.Array  # [C, cap, 2] f32 per-half dequant scales
    bucket_ids: jax.Array     # [C, cap] int32 global ids (-1 = pad)
    bucket_counts: jax.Array  # [C] int32

    @property
    def n_buckets(self) -> int:
        return self.centroids.shape[0]

    @property
    def capacity(self) -> int:
        return self.bucket_ids.shape[1]

    def tree_flatten(self):
        return ((self.centroids, self.bucket_vecs, self.bucket_scales,
                 self.bucket_ids, self.bucket_counts), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    CompressedIVFIndex, CompressedIVFIndex.tree_flatten,
    CompressedIVFIndex.tree_unflatten)


@functools.partial(jax.jit, static_argnames=("n_clusters",), donate_argnums=(1,))
def _kmeans_step(train, cents, n_clusters: int):
    assign = jnp.argmax(train @ cents.T, axis=1)          # [S]
    sums = jax.ops.segment_sum(train, assign, num_segments=n_clusters)
    cnts = jax.ops.segment_sum(jnp.ones((train.shape[0],)), assign,
                               num_segments=n_clusters)
    new = sums / jnp.maximum(cnts, 1.0)[:, None]
    # re-seed empty clusters from the previous centroids
    new = jnp.where((cnts > 0)[:, None], new, cents)
    return new / jnp.maximum(
        jnp.linalg.norm(new, axis=-1, keepdims=True), 1e-8)


def kmeans(vecs: jax.Array, n_clusters: int, iters: int = 10,
           seed: int = 0, sample: int = 131072) -> jax.Array:
    """Mini-batch-free Lloyd's k-means on (a sample of) the corpus."""
    key = jax.random.key(seed)
    n = vecs.shape[0]
    if n > sample:
        idx = jax.random.choice(key, n, (sample,), replace=False)
        train = vecs[idx]
    else:
        train = vecs
    init_idx = jax.random.choice(jax.random.fold_in(key, 1),
                                 train.shape[0], (n_clusters,), replace=False)
    cents = train[init_idx]
    for _ in range(iters):
        cents = _kmeans_step(train, cents, n_clusters)
    return cents


_assign_fn = jax.jit(lambda corpus, cents: jnp.argmax(corpus @ cents.T, axis=1))


def _gather_buckets(corpus, ids, block: int):
    """Corpus rows by bucket slot ([C, cap, d]), zero for pad (-1) slots.

    ``block`` buckets at a time are written in place into the output, so
    the only transient is one block: a whole-index gather would hold a
    second bucket-sized buffer for its relayout."""
    rows = jnp.where(ids >= 0, ids, corpus.shape[0])     # out of range -> 0

    def body(i, out):
        blk = jax.lax.dynamic_slice_in_dim(rows, i * block, block)
        vecs = corpus.at[blk].get(mode="fill", fill_value=0)
        return jax.lax.dynamic_update_slice_in_dim(out, vecs, i * block, 0)

    out = jnp.zeros(ids.shape + corpus.shape[1:], corpus.dtype)
    return jax.lax.fori_loop(0, ids.shape[0] // block, body, out)


def _first_slots(vecs, cap: int):
    return vecs[:, :cap]


@functools.lru_cache(maxsize=None)
def _bucket_program(fn, static: str, out_format: Format | None):
    """``fn`` as one program that writes its bucket array in ``out_format``
    (:func:`bucket_format`; None keeps the backend's default layout)."""
    out = {} if out_format is None else {"out_shardings": out_format}
    return jax.jit(fn, static_argnames=(static,), **out)


@contextlib.contextmanager
def _no_persistent_cache():
    """Compile inside without JAX's persistent compilation cache.

    An executable that JAX (0.9) reads back from that cache hands out a
    non-default output layout under the default layout's name, so a
    row-major bucket array would reach every later program described as
    the default one: on a TPU the first speculation call fails on the size
    mismatch, on the CPU it reads scrambled vectors.
    """
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _write_buckets(fn, static: str, shape, src, *args, **kwargs):
    """``fn(src, ...)`` as :func:`_bucket_program`, writing its ``shape``
    bucket array in :func:`bucket_format` on ``src``'s sharding.  A program
    that asks for a layout of its own is compiled afresh
    (:func:`_no_persistent_cache`)."""
    fmt = bucket_format(shape, src.dtype, src.sharding)
    program = _bucket_program(fn, static, fmt)
    if fmt is None:
        return program(src, *args, **kwargs)
    with _no_persistent_cache():
        return program(src, *args, **kwargs)


def _bucket_gather(corpus, ids, block: int):
    """:func:`_gather_buckets` written in the layout ``ivf_scan`` reads."""
    return _write_buckets(_gather_buckets, "block",
                          ids.shape + corpus.shape[1:], corpus, ids,
                          block=block)


_ASSIGN_CHUNK = 262144      # corpus rows per bucket-assignment program


def build_ivf(corpus: jax.Array, n_buckets: int, capacity_factor: float = 2.0,
              kmeans_iters: int = 10, seed: int = 0) -> IVFIndex:
    """Assign every corpus vector to its nearest centroid bucket.

    Assignment runs ``_ASSIGN_CHUNK`` rows at a time, so the transient
    [rows, C] score matrix stays bounded (a 1M-row corpus at C=2048 would
    otherwise need 8 GB of it).  The buckets are gathered on the device,
    so the corpus never round-trips through the host, and written in the
    layout the ``ivf_scan`` kernel reads (:func:`bucket_format`), so no
    speculation program relays them out.
    """
    n, d = corpus.shape
    n_buckets = max(1, min(n_buckets, n // 8))   # clamp for tiny corpora
    cents = kmeans(corpus, n_buckets, kmeans_iters, seed)
    assign = np.concatenate([
        np.asarray(_assign_fn(corpus[lo:lo + _ASSIGN_CHUNK], cents))
        for lo in range(0, n, _ASSIGN_CHUNK)])
    cap = int(np.ceil(n / n_buckets * capacity_factor))
    # vectorized bucket fill: sort by bucket, position-in-bucket via offsets
    order = np.argsort(assign, kind="stable")
    sorted_b = assign[order]
    starts = np.searchsorted(sorted_b, np.arange(n_buckets))
    pos = np.arange(n) - starts[sorted_b]
    keep = pos < cap
    bucket_ids = np.full((n_buckets, cap), -1, np.int32)
    bucket_ids[sorted_b[keep], pos[keep]] = order[keep]
    counts = np.bincount(sorted_b[keep], minlength=n_buckets).astype(np.int32)
    bucket_ids = jnp.asarray(bucket_ids)
    return IVFIndex(centroids=cents,
                    bucket_vecs=_bucket_gather(jnp.asarray(corpus),
                                               bucket_ids,
                                               block=math.gcd(n_buckets, 64)),
                    bucket_ids=bucket_ids,
                    bucket_counts=jnp.asarray(counts))


@jax.jit
def _quant_residual_halves(rows, cents_rows):
    """int8-code the residual ``rows - centroid`` with one symmetric scale
    per d/2-dim half.  Returns ``(codes [n, d] int8, scales [n, 2] f32)``."""
    r = rows - cents_rows
    h = r.shape[1] // 2
    q0, s0 = quantize_int8(r[:, :h], axis=-1)
    q1, s1 = quantize_int8(r[:, h:], axis=-1)
    return jnp.concatenate([q0, q1], axis=1), jnp.concatenate([s0, s1], axis=1)


def _build_ivf_arrays(corpus, n_buckets: int, capacity_factor: float = 2.0,
                      kmeans_iters: int = 10, seed: int = 0,
                      chunk: int = 65536, compressed: bool = False,
                      ids=None):
    """Streaming bucket build on HOST arrays (the backend keeps them as
    mutable mirrors for live ingest).  The corpus flows through k-means
    assignment ``chunk`` rows at a time; per-bucket fill cursors reproduce
    ``build_ivf``'s stable bucket order without ever materializing the
    [B, C] score matrix or (in compressed mode) f32 buckets.  Returns
    ``(centroids, bucket_vecs, bucket_scales | None, bucket_ids, counts)``
    as numpy arrays.
    """
    corpus_np = np.asarray(corpus)
    n, d = corpus_np.shape
    n_buckets = max(1, min(n_buckets, n // 8))   # clamp for tiny corpora
    cents = kmeans(jnp.asarray(corpus_np), n_buckets, kmeans_iters, seed)
    cap = int(np.ceil(n / n_buckets * capacity_factor))
    gids = (np.arange(n, dtype=np.int32) if ids is None
            else np.asarray(ids, np.int32))
    bucket_ids = np.full((n_buckets, cap), -1, np.int32)
    counts = np.zeros(n_buckets, np.int64)
    if compressed:
        bucket_vecs = np.zeros((n_buckets, cap, d), np.int8)
        bucket_scales = np.zeros((n_buckets, cap, 2), np.float32)
        cents_np = np.asarray(cents)
    else:
        bucket_vecs = np.zeros((n_buckets, cap, d), np.float32)
        bucket_scales = None
    for lo in range(0, n, chunk):
        rows = corpus_np[lo:lo + chunk]
        assign = np.asarray(_assign_fn(jnp.asarray(rows), cents))
        order = np.argsort(assign, kind="stable")
        sb = assign[order]
        starts = np.searchsorted(sb, np.arange(n_buckets))
        pos = counts[sb] + (np.arange(len(sb)) - starts[sb])
        keep = pos < cap
        rb, rp, ro = sb[keep], pos[keep].astype(np.int64), order[keep]
        bucket_ids[rb, rp] = gids[lo + ro]
        if compressed:
            q, scale = _quant_residual_halves(
                jnp.asarray(rows[ro]), jnp.asarray(cents_np[rb]))
            bucket_vecs[rb, rp] = np.asarray(q)
            bucket_scales[rb, rp] = np.asarray(scale)
        else:
            bucket_vecs[rb, rp] = rows[ro]
        counts = np.minimum(
            counts + np.bincount(sb, minlength=n_buckets), cap)
    return (np.asarray(cents), bucket_vecs, bucket_scales, bucket_ids,
            counts.astype(np.int32))


def build_ivf_streaming(corpus, n_buckets: int, capacity_factor: float = 2.0,
                        kmeans_iters: int = 10, seed: int = 0,
                        chunk: int = 65536, compressed: bool = False,
                        ids=None) -> IVFIndex | CompressedIVFIndex:
    """Chunked-assignment build; bucket contents identical to ``build_ivf``
    for the same (corpus, seed).  ``compressed=True`` returns a
    :class:`CompressedIVFIndex` with int8 bucket storage — the f32 buckets
    are never materialized, only one ``chunk``-row slice at a time."""
    cents, bvecs, bscales, bids, counts = _build_ivf_arrays(
        corpus, n_buckets, capacity_factor, kmeans_iters, seed, chunk,
        compressed, ids)
    if compressed:
        return CompressedIVFIndex(centroids=jnp.asarray(cents),
                                  bucket_vecs=jnp.asarray(bvecs),
                                  bucket_scales=jnp.asarray(bscales),
                                  bucket_ids=jnp.asarray(bids),
                                  bucket_counts=jnp.asarray(counts))
    return IVFIndex(centroids=jnp.asarray(cents),
                    bucket_vecs=jnp.asarray(bvecs),
                    bucket_ids=jnp.asarray(bids),
                    bucket_counts=jnp.asarray(counts))


def subset_index(index: IVFIndex, fraction: float, seed: int = 0) -> IVFIndex:
    """Keep only a fraction of each bucket (Table VII compression mode)."""
    if fraction >= 1.0:
        return index
    cap = index.capacity
    new_cap = max(1, int(cap * fraction))
    vecs = index.bucket_vecs
    return IVFIndex(centroids=index.centroids,
                    bucket_vecs=_write_buckets(
                        _first_slots, "cap",
                        (index.n_buckets, new_cap) + vecs.shape[2:], vecs,
                        cap=new_cap),
                    bucket_ids=index.bucket_ids[:, :new_cap],
                    bucket_counts=jnp.minimum(index.bucket_counts, new_cap))


def ivf_probe_scan(index: IVFIndex | CompressedIVFIndex, queries: jax.Array,
                   probe: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Gather + score the probed buckets (traceable; the XLA oracle of the
    Pallas ``ivf_scan`` kernel).  For a :class:`CompressedIVFIndex` the
    int8 dequant fuses into scoring: codes are centroid residuals with
    per-half scales, so scores are ``q.c + (q_lo.v8_lo)s_lo +
    (q_hi.v8_hi)s_hi`` — no f32 gather."""
    vecs = index.bucket_vecs[probe]                          # [B, np, cap, d]
    ids = index.bucket_ids[probe]                            # [B, np, cap]
    if isinstance(index, CompressedIVFIndex):
        h = queries.shape[1] // 2
        codes = vecs.astype(jnp.float32)
        scales = index.bucket_scales[probe]                  # [B, np, cap, 2]
        bias = jnp.einsum("bd,bpd->bp", queries, index.centroids[probe])
        s = (jnp.einsum("bd,bpcd->bpc", queries[:, :h], codes[..., :h])
             * scales[..., 0]
             + jnp.einsum("bd,bpcd->bpc", queries[:, h:], codes[..., h:])
             * scales[..., 1]
             + bias[:, :, None])
    else:
        s = jnp.einsum("bd,bpcd->bpc", queries, vecs)
    s = jnp.where(ids >= 0, s, -jnp.inf)
    b = queries.shape[0]
    s = s.reshape(b, -1)
    ids = ids.reshape(b, -1)
    if s.shape[1] < k:       # tiny probe pools (compressed fuzzy channel)
        pad = k - s.shape[1]
        s = jnp.concatenate([s, jnp.full((b, pad), -jnp.inf, s.dtype)], 1)
        ids = jnp.concatenate([ids, jnp.full((b, pad), -1, ids.dtype)], 1)
    top_s, top_i = jax.lax.top_k(s, k)
    return top_s, jnp.take_along_axis(ids, top_i, axis=1)


@functools.partial(jax.jit, static_argnames=("nprobe", "k"))
def ivf_search(index: IVFIndex | CompressedIVFIndex, queries: jax.Array, *,
               nprobe: int, k: int) -> tuple[jax.Array, jax.Array]:
    """queries [B, d] -> (scores [B, k], global ids [B, k])."""
    nprobe = min(nprobe, index.n_buckets)
    cscores = queries @ index.centroids.T                    # [B, C]
    _, probe = jax.lax.top_k(cscores, nprobe)                # [B, nprobe]
    return ivf_probe_scan(index, queries, probe, k)
