"""Exact nearest-neighbour search (ENNS) as sharded matmul + top-k.

On TPU, flat search over an embedding store IS a matmul: scores = q @ E^T.
The corpus shards over the ``corpus`` logical axes (data x model); the top-k
runs per shard and merges with a tree reduction (see distributed.py).  On a
single device the chunked variant bounds the transient score matrix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.utils import constrain

# Exact search scores in full f32.  At DEFAULT precision a TPU may run an
# f32 matmul as a single bf16 pass (~3 significant digits), which reorders
# near-tied neighbours of an exact scan.  A query batch does B flops per
# corpus byte, so the extra passes are expected to hide under the corpus
# read.  The CPU computes in f32 either way.
EXACT_PRECISION = jax.lax.Precision.HIGHEST


def flat_search(corpus: jax.Array, queries: jax.Array, k: int,
                rules=None, merge_chunks: int = 0) -> tuple[jax.Array, jax.Array]:
    """Exact top-k by inner product.

    corpus [N, d] (sharded over 'corpus'), queries [B, d] -> (scores [B,k],
    ids [B,k]).

    merge_chunks > 0 (set it to the corpus shard count) computes the top-k
    *per chunk locally* and merges the [B, chunks, k] candidates — §Perf
    iteration for has-rag: a plain top_k over the sharded N dim makes GSPMD
    all-gather the full [B, N] score matrix (~25 GB/device at 49.2M);
    chunk-local selection reduces the interconnect payload to B·chunks·k
    pairs (~MBs), the same tree-merge the shard_map path uses.
    """
    corpus = constrain(corpus, ("corpus", None), rules)
    scores = queries @ corpus.T                      # [B, N]
    scores = constrain(scores, (None, "corpus"), rules)
    b, n = scores.shape
    if merge_chunks and n % merge_chunks == 0:
        loc = n // merge_chunks
        sc = scores.reshape(b, merge_chunks, loc)
        sc = constrain(sc, (None, "corpus", None), rules)
        lv, li = jax.lax.top_k(sc, min(k, loc))      # [B, C, k] local
        li = li + (jnp.arange(merge_chunks) * loc)[None, :, None]
        lv = lv.reshape(b, -1)
        li = li.reshape(b, -1)
        v, pos = jax.lax.top_k(lv, k)                # tiny merge
        return v, jnp.take_along_axis(li, pos, axis=1)
    return jax.lax.top_k(scores, k)


def chunked_flat_search(corpus: jax.Array, queries: jax.Array, k: int,
                        chunk: int = 65536) -> tuple[jax.Array, jax.Array]:
    """Streaming exact top-k: scans corpus chunks with a running top-k merge.

    Bounds the transient score matrix to [B, chunk]; this is the pure-jnp
    oracle for the Pallas ``topk_search`` kernel.  The corpus is read where
    it lies: whole chunks are dynamic slices of the stored array, and a
    ragged tail (``N % chunk`` rows) is scored as a block of its own, so no
    call copies or pads the corpus.  Ties keep the lower id.
    """
    n = corpus.shape[0]
    b = queries.shape[0]
    n_full, tail = divmod(n, chunk)

    def merge(carry, block, base):
        best_s, best_i = carry
        s = jnp.dot(queries, block.T, precision=EXACT_PRECISION)   # [B, rows]
        ids = base + jnp.arange(block.shape[0], dtype=jnp.int32)
        cs = jnp.concatenate([best_s, s], axis=1)
        ci = jnp.concatenate([best_i, jnp.broadcast_to(ids, s.shape)], axis=1)
        ts, ti = jax.lax.top_k(cs, k)
        return ts, jnp.take_along_axis(ci, ti, axis=1)

    def body(j, carry):
        base = j * chunk
        block = jax.lax.dynamic_slice_in_dim(corpus, base, chunk, axis=0)
        return merge(carry, block, base)

    carry = (jnp.full((b, k), -jnp.inf, queries.dtype),
             jnp.full((b, k), -1, jnp.int32))
    if n_full:
        carry = jax.lax.fori_loop(0, n_full, body, carry)
    if tail:
        carry = merge(carry, corpus[n_full * chunk:], n_full * chunk)
    return carry


# ---------------------------------------------------------------------------
# int8 quantized store (TPU-native replacement for Faiss PQ)
# ---------------------------------------------------------------------------

def quantize_store(corpus: jax.Array) -> dict:
    """Per-vector symmetric int8 quantization: ~4x HBM compression."""
    scale = jnp.max(jnp.abs(corpus), axis=-1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(corpus / jnp.maximum(scale, 1e-8)), -127, 127)
    return {"q": q.astype(jnp.int8), "scale": scale[:, 0].astype(jnp.float32)}


def quantized_search(store: dict, queries: jax.Array, k: int,
                     rescore: jax.Array | None = None,
                     rescore_factor: int = 4) -> tuple[jax.Array, jax.Array]:
    """ADC-style scoring on the int8 store + optional exact re-rank.

    This is the ScaNN-substitute: approximate scores from the compressed
    store select ``rescore_factor * k`` candidates which are exactly
    re-scored against the fp corpus (if given).
    """
    approx = (queries @ store["q"].T.astype(queries.dtype)) \
        * store["scale"][None, :]
    if rescore is None:
        return jax.lax.top_k(approx, k)
    m = min(rescore_factor * k, approx.shape[1])
    _, cand = jax.lax.top_k(approx, m)                 # [B, m]
    cvecs = rescore[cand]                              # [B, m, d]
    exact = jnp.einsum("bd,bmd->bm", queries, cvecs)
    s, local = jax.lax.top_k(exact, k)
    return s, jnp.take_along_axis(cand, local, axis=1)
