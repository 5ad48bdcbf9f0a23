"""Distributed exact top-k over a sharded corpus (shard_map + collectives).

The production path for full-database retrieval: each model-axis shard holds a
corpus slice, computes a local streaming top-k, and the k·(value,id) pairs are
merged with an all-gather tree (O(shards·k) bytes on the interconnect instead
of O(N) scores).  This is how the paper's 'slow full-database retrieval on
the cloud' lowers onto a TPU pod.

Shards smaller than k: a shard with fewer than ``k`` rows can only produce
``rows`` local candidates, so every local candidate set is padded to exactly
``k`` columns with ``-inf`` scores / ``-1`` ids before the all-gather.  The
global merge then always sees a rectangular [B, shards·k] candidate matrix
and returns ``-1`` ids only when the whole corpus holds fewer than ``k``
rows — the same contract as ``chunked_flat_search``.

:func:`sharded_topk_reference` is the mesh-free oracle: the identical
local-top-k + candidate-merge math on one device, used by
``retrieval/service.py::ShardedMeshBackend`` when no multi-device mesh is
available (and by the parity tests as the middle term between the shard_map
path and ``chunked_flat_search``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.retrieval.flat import EXACT_PRECISION, chunked_flat_search


def _pad_candidates(s: jax.Array, i: jax.Array, k: int):
    """Pad local [B, kk<=k] candidates to [B, k] with -inf scores / -1 ids."""
    kk = s.shape[-1]
    if kk >= k:
        return s, i
    pad = k - kk
    s = jnp.concatenate(
        [s, jnp.full(s.shape[:-1] + (pad,), -jnp.inf, s.dtype)], axis=-1)
    i = jnp.concatenate(
        [i, jnp.full(i.shape[:-1] + (pad,), -1, i.dtype)], axis=-1)
    return s, i


def distributed_flat_search(mesh: Mesh, corpus_axes: tuple[str, ...] = ("data", "model")):
    """Returns a jit-able fn(corpus [N,d], queries [B,d]) -> (scores, ids [B,k]).

    corpus is sharded over ``corpus_axes`` (row-wise); queries replicated.
    N must divide evenly by the number of shards (the shard_map contract).
    """
    axes = corpus_axes

    def search(corpus, queries, k: int):
        n_shards = 1
        for a in axes:
            n_shards *= mesh.shape[a]
        shard_rows = corpus.shape[0] // n_shards

        def local(corpus_blk, q):
            # corpus_blk: [N/shards, d] local slice
            s, i = jax.lax.top_k(
                jnp.dot(q, corpus_blk.T, precision=EXACT_PRECISION),
                min(k, corpus_blk.shape[0]))
            # global ids: offset by this shard's row start
            idx = jax.lax.axis_index(axes)
            i = i + (idx * shard_rows).astype(i.dtype)
            # a shard smaller than k yields a ragged candidate set — pad to
            # k columns (-inf / -1) so the gathered matrix is rectangular
            s, i = _pad_candidates(s, i, k)
            # all-gather the candidate sets over the corpus axes, then merge
            s_all = jax.lax.all_gather(s, axes, axis=1, tiled=True)
            i_all = jax.lax.all_gather(i, axes, axis=1, tiled=True)
            ts, ti = jax.lax.top_k(s_all, k)
            return ts, jnp.take_along_axis(i_all, ti, axis=1)

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axes), P()),
            out_specs=(P(), P()),
            check_vma=False,   # post-all-gather results are replicated
        )(corpus, queries)

    return search


@functools.partial(jax.jit, static_argnames=("k", "n_shards", "chunk"))
def sharded_topk_reference(corpus: jax.Array, queries: jax.Array, k: int,
                           n_shards: int,
                           chunk: int = 32768) -> tuple[jax.Array, jax.Array]:
    """Single-device oracle for :func:`distributed_flat_search`.

    Splits the corpus into ``n_shards`` row blocks, runs the *streaming*
    chunked scan per shard (the transient score matrix stays [B, chunk],
    never [B, N]), offsets the local ids, pads each candidate set to ``k``
    (-inf / -1) and merges — the exact candidate layout the all-gather
    produces, so ids/scores match the mesh path and ``chunked_flat_search``
    bit-for-bit.
    """
    n, _ = corpus.shape
    b = queries.shape[0]
    rows = max(1, -(-n // n_shards))
    kk = min(k, rows)
    cand_s, cand_i = [], []
    for sh in range(n_shards):
        live = min(rows, n - sh * rows)
        if live <= 0:                   # more shards than rows: empty shard
            lv = jnp.full((b, k), -jnp.inf, queries.dtype)
            li = jnp.full((b, k), -1, jnp.int32)
        else:
            blk = jax.lax.slice_in_dim(corpus, sh * rows, sh * rows + live)
            lv, li = chunked_flat_search(blk, queries, kk,
                                         chunk=min(chunk, live))
            li = jnp.where(li >= 0, li + sh * rows, -1)   # global ids
            lv, li = _pad_candidates(lv, li, k)
        cand_s.append(lv)
        cand_i.append(li)
    v, pos = jax.lax.top_k(jnp.concatenate(cand_s, axis=1), k)  # merge
    return v, jnp.take_along_axis(jnp.concatenate(cand_i, axis=1), pos,
                                  axis=1)
