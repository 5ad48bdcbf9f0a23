"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def topk_search_ref(queries: jax.Array, corpus: jax.Array, k: int):
    """Exact top-k by inner product. queries [B,d], corpus [N,d] ->
    (vals [B,k], idx [B,k])."""
    scores = (queries.astype(jnp.float32) @ corpus.astype(jnp.float32).T)
    return jax.lax.top_k(scores, k)


def homology_score_ref(draft_ids: jax.Array, cache_doc_ids: jax.Array,
                       cache_valid: jax.Array):
    """Overlap-ratio homology scores. draft [B,k], cache [H,k] -> [B,H]."""
    k = draft_ids.shape[1]
    eq = (draft_ids[:, None, :, None] == cache_doc_ids[None, :, None, :])
    eq &= (draft_ids[:, None, :, None] >= 0)
    overlap = jnp.sum(jnp.any(eq, axis=3), axis=2)       # [B, H]
    s = overlap.astype(jnp.float32) / k
    return jnp.where(cache_valid[None, :], s, 0.0)


def ivf_scan_ref(queries: jax.Array, probe: jax.Array, bucket_vecs: jax.Array,
                 bucket_ids: jax.Array, k: int,
                 bucket_scales: jax.Array | None = None,
                 probe_bias: jax.Array | None = None):
    """Gather probed buckets + exact local top-k.

    queries [B,d], probe [B,P] bucket indices, bucket_vecs [C,cap,d],
    bucket_ids [C,cap] -> (vals [B,k], global ids [B,k]).
    ``bucket_scales [C,cap,2]`` + ``probe_bias [B,P]`` (together) score the
    compressed corpus residency mode's int8 centroid-residual codes:
    ``bias + (q_lo.v8_lo)s_lo + (q_hi.v8_hi)s_hi`` per slot.
    """
    q = queries.astype(jnp.float32)
    vecs = bucket_vecs[probe]                             # [B,P,cap,d]
    ids = bucket_ids[probe]                               # [B,P,cap]
    if bucket_scales is not None:
        h = q.shape[1] // 2
        codes = vecs.astype(jnp.float32)
        sc = bucket_scales[probe]                         # [B,P,cap,2]
        s = (jnp.einsum("bd,bpcd->bpc", q[:, :h], codes[..., :h]) * sc[..., 0]
             + jnp.einsum("bd,bpcd->bpc", q[:, h:], codes[..., h:])
             * sc[..., 1]
             + probe_bias.astype(jnp.float32)[:, :, None])
    else:
        s = jnp.einsum("bd,bpcd->bpc", q, vecs.astype(jnp.float32))
    s = jnp.where(ids >= 0, s, -jnp.inf)
    b = queries.shape[0]
    s, ids = s.reshape(b, -1), ids.reshape(b, -1)
    if s.shape[1] < k:                # probed pool < k: pad like the kernel
        pad = k - s.shape[1]
        s = jnp.pad(s, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
    vals, pos = jax.lax.top_k(s, k)
    return vals, jnp.take_along_axis(ids, pos, axis=1)


def lexical_score_ref(q_terms: jax.Array, q_weights: jax.Array,
                      doc_terms: jax.Array, doc_weights: jax.Array, k: int,
                      tile_n: int = 512):
    """Tiled hashed-term lexical top-k, scanning the SAME tiles through the
    SAME merge as the Pallas kernel (shared helpers), so the two backends
    agree bit-for-bit including tie order.  q_terms/q_weights [B,T],
    doc_terms/doc_weights [N,L] -> (vals [B,k], row idx [B,k])."""
    from repro.kernels.lexical_score import (
        _final_sort, _merge_topk, _pad_postings, _tile_scores)
    b = q_terms.shape[0]
    q_terms = q_terms.astype(jnp.int32)
    q_weights = q_weights.astype(jnp.float32)
    doc_terms, doc_weights, n_tiles = _pad_postings(
        doc_terms.astype(jnp.int32), doc_weights.astype(jnp.float32), tile_n)
    l_w = doc_terms.shape[0]
    dt = doc_terms.reshape(l_w, n_tiles, tile_n).transpose(1, 0, 2)
    dw = doc_weights.reshape(l_w, n_tiles, tile_n).transpose(1, 0, 2)
    bases = jnp.arange(n_tiles, dtype=jnp.int32) * tile_n

    def body(carry, tile):
        vals, idx = carry
        dt_t, dw_t, base = tile
        s = _tile_scores(q_terms, q_weights, dt_t, dw_t)
        vals, idx = _merge_topk(s, vals, idx, base, k)
        return (vals, idx), None

    init = (jnp.full((b, k), -jnp.inf, jnp.float32),
            jnp.full((b, k), -1, jnp.int32))
    (vals, idx), _ = jax.lax.scan(body, init, (dt, dw, bases))
    return _final_sort(vals, idx)


def fused_rerank_ref(queries: jax.Array, pool_ids: jax.Array,
                     pool_vecs: jax.Array, kd: int, k: int,
                     rrf_k: float = 60.0,
                     diversify_sim: float | None = None):
    """RRF fusion + diversification + rerank, running the kernel's own
    per-query ``_fuse_scores`` sequentially via ``lax.map`` — bit-identical
    to the Pallas grid by construction."""
    import functools

    from repro.kernels.fused_rerank import _final_topk, _fuse_scores
    kl = pool_ids.shape[1] - kd
    fuse = functools.partial(_fuse_scores, kd=kd, kl=kl, rrf_k=rrf_k,
                             diversify_sim=diversify_sim)
    ids = pool_ids.astype(jnp.int32)
    mass, rscore = jax.lax.map(
        lambda x: fuse(*x),
        (queries.astype(jnp.float32)[:, None, :], ids[:, None, :],
         ids[:, :, None], pool_vecs.astype(jnp.float32)))
    mass, rscore = mass[:, 0], rscore[:, 0]
    return _final_topk(mass, rscore, pool_ids, k)


def embedding_bag_ref(table: jax.Array, ids: jax.Array,
                      weights: jax.Array | None = None, mode: str = "sum"):
    """Fixed-arity EmbeddingBag. table [V,d], ids [B,n] -> [B,d]."""
    vecs = table[ids]                                     # [B,n,d]
    if weights is not None:
        vecs = vecs * weights[..., None]
    out = jnp.sum(vecs, axis=1)
    if mode == "mean":
        out = out / ids.shape[1]
    return out
