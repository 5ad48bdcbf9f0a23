"""Pallas TPU kernel: streaming hashed-term lexical scoring + running top-k.

The sparse (lexical) retrieval channel of the hybrid cloud stage: every doc
carries a short postings row of hashed term ids and weights, and a batch of
queries (each with its own hashed terms) is scored as

    s[b, doc] = sum_t qw[b, t] * sum_l dw[doc, l] * [dt[doc, l] == qt[b, t]]

with ``-1`` term ids inert on both sides.  A doc with no positive matched
mass is *invalid* for that query (scored ``-inf``, id ``-1``) — lexical
retrieval has no notion of "closest" doc when nothing matches, unlike the
dense channel.

TPU mapping (same shape as ``topk_search``):
  * grid = postings tiles; each step streams an [L, TILE_N] block of doc
    terms + weights (postings transposed, lane-dense) into VMEM while the
    query terms stay resident.
  * the match is L·T vector-unit integer compares per tile (T = query terms,
    L = doc postings width — both single digits), no MXU work at all: the
    channel is bandwidth-bound on the postings stream, which is the point
    (``LatencyModel.hybrid_scale`` charges exactly those bytes).
  * the running top-k lives in the revisited output block and merges with
    the same K-round argmax/argmin exchange as ``topk_search``.

``_tile_scores``/``_merge_topk`` are shared with the XLA oracle
(``kernels/ref.py::lexical_score_ref`` scans the identical tiles through the
identical merge), so the two backends agree bit-for-bit including tie order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _tile_scores(q_terms, q_weights, doc_terms, doc_weights):
    """Hashed-term match mass for one postings tile.

    q_terms/q_weights [B, T], doc_terms/doc_weights [L, C] (postings
    transposed: one row per postings slot) -> [B, C] f32, with
    non-positive mass (no term matched) masked to ``-inf``.  Every
    intermediate is a 2-D [B, C] tile, so no [B, C, L] block ever lands in
    VMEM.  Shared by the kernel body and the XLA oracle so the math is
    identical by construction.
    """
    s = None
    for t in range(q_terms.shape[1]):                      # static: T is tiny
        qt = q_terms[:, t:t + 1]                           # [B, 1]
        mass = None
        for l in range(doc_terms.shape[0]):                # static: L is tiny
            dt = doc_terms[l:l + 1, :]                     # [1, C]
            hit = (dt == qt) & (dt >= 0) & (qt >= 0)
            m = jnp.where(hit, doc_weights[l:l + 1, :].astype(jnp.float32),
                          0.0)
            mass = m if mass is None else mass + m
        term = q_weights[:, t:t + 1] * mass
        s = term if s is None else s + term
    return jnp.where(s > 0.0, s, -jnp.inf)


def _merge_topk(scores, vals, idx, base, k: int):
    """K-round merge of a [B, C] score tile into the running [B, k] top-k.

    Identical exchange to ``topk_search``: tile argmax replaces the running
    argmin when strictly better, so earlier tiles win ties and within a tile
    the lowest column wins — deterministic, and shared with the oracle.
    """
    b = scores.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (b, k), 1)

    def merge(i, carry):
        scores, vals, idx = carry
        cur = jnp.max(scores, axis=1)                      # [B]
        arg = jnp.argmax(scores, axis=1).astype(jnp.int32)
        rmin = jnp.min(vals, axis=1)
        rarg = jnp.argmin(vals, axis=1).astype(jnp.int32)
        better = cur > rmin
        hit = (kcol == rarg[:, None]) & better[:, None]
        vals = jnp.where(hit, cur[:, None], vals)
        idx = jnp.where(hit, (base + arg)[:, None], idx)
        scores = jnp.where(col == arg[:, None], -jnp.inf, scores)
        return scores, vals, idx

    _, vals, idx = jax.lax.fori_loop(0, k, merge, (scores, vals, idx))
    return vals, idx


def _final_sort(vals, idx):
    """Desc-sort the [B, k] running buffer; ids of -inf slots forced to -1."""
    order = jnp.argsort(-vals, axis=1)
    vals = jnp.take_along_axis(vals, order, axis=1)
    idx = jnp.take_along_axis(idx, order, axis=1)
    return vals, jnp.where(jnp.isfinite(vals), idx, -1)


def _pad_postings(doc_terms, doc_weights, tile_n: int):
    """Pad postings rows to a tile multiple with inert (-1 / 0) rows and
    transpose them to ``[L, N_pad]`` (the layout the kernel streams)."""
    n = doc_terms.shape[0]
    n_tiles = pl.cdiv(n, tile_n)
    pad = ((0, n_tiles * tile_n - n), (0, 0))
    doc_terms = jnp.pad(doc_terms, pad, constant_values=-1)
    doc_weights = jnp.pad(doc_weights, pad)
    return doc_terms.T, doc_weights.T, n_tiles


def _lexical_kernel(qt_ref, qw_ref, dt_ref, dw_ref, vals_ref, idx_ref, *,
                    k: int, tile_n: int):
    step = pl.program_id(0)
    b = qt_ref.shape[0]

    @pl.when(step == 0)
    def _init():
        vals_ref[...] = jnp.full((b, k), -jnp.inf, jnp.float32)
        idx_ref[...] = jnp.full((b, k), -1, jnp.int32)

    scores = _tile_scores(qt_ref[...], qw_ref[...].astype(jnp.float32),
                          dt_ref[...], dw_ref[...])
    vals, idx = _merge_topk(scores, vals_ref[...], idx_ref[...],
                            step * tile_n, k)
    vals_ref[...] = vals
    idx_ref[...] = idx


@functools.partial(jax.jit, static_argnames=("k", "tile_n", "interpret"))
def lexical_score(q_terms: jax.Array, q_weights: jax.Array,
                  doc_terms: jax.Array, doc_weights: jax.Array, k: int,
                  tile_n: int = 512, interpret: bool = False):
    """q_terms/q_weights [B,T], doc_terms/doc_weights [N,L] ->
    (vals [B,k] desc-sorted, row idx [B,k]).

    Rows that match no query term score ``-inf`` / id ``-1`` — including
    empty postings rows (all ``-1`` terms) and the pad tail, which need no
    separate validity stream because inert terms can never accumulate
    positive mass.
    """
    b, t_q = q_terms.shape
    q_terms = q_terms.astype(jnp.int32)
    q_weights = q_weights.astype(jnp.float32)
    doc_terms, doc_weights, n_tiles = _pad_postings(
        doc_terms.astype(jnp.int32), doc_weights.astype(jnp.float32), tile_n)
    l_w = doc_terms.shape[0]

    vals, idx = pl.pallas_call(
        functools.partial(_lexical_kernel, k=k, tile_n=tile_n),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((b, t_q), lambda i: (0, 0)),      # query terms resident
            pl.BlockSpec((b, t_q), lambda i: (0, 0)),
            pl.BlockSpec((l_w, tile_n), lambda i: (0, i)),  # postings stream
            pl.BlockSpec((l_w, tile_n), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((b, k), lambda i: (0, 0)),        # running top-k
            pl.BlockSpec((b, k), lambda i: (0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, k), jnp.float32),
                   jax.ShapeDtypeStruct((b, k), jnp.int32)],
        interpret=interpret,
    )(q_terms, q_weights, doc_terms, doc_weights)
    return _final_sort(vals, idx)
