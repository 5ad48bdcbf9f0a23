"""Plain reference of what the served path must produce.

Nothing here imports the program.  Scores are float64 on the host:

* ``exact_topk``: the exact top-k of a flat inner-product index.  A device
  pass at full float32 precision proposes ``cand`` candidates per query;
  the host rescores them in float64 and certifies the result when the
  k-th float64 score clears the last candidate's float32 score by
  ``margin`` (far above float32 rounding), and otherwise scans that query's
  whole corpus on the host;
* ``CacheReplay``: HaS's cache ingest (Algorithm 1 line 16): a FIFO ring of
  (query, result ids), and a FIFO ring of deduplicated documents, where a
  result's documents already in the store, or repeated within the result,
  take no slot;
* ``ivf_mismatch``: whether an IVF table is one: every corpus row listed
  once, in a bucket whose centroid is its nearest up to ``margin``, or
  left out only where such a bucket is full; every listed slot holding its
  row's vector.  Any set of centroids makes a valid IVF, so the centroids
  are taken as given;
* ``speculate``: for one query on a replayed cache, a lower bound on the
  draft (the exact top-k over the doc store and over the IVF buckets that
  any float32 scoring of the centroids must probe) and the homology score
  of a given validation draft: the most of its ids any valid cached result
  holds, over k.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.world import row_chunk

HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("k", "rows", "mode"))
def device_topk(corpus, queries, k: int, rows: int, mode: str = "highest"):
    """Top-k by inner product, scanning ``rows`` corpus rows at a time.

    ``mode``: ``highest`` (float32), ``high`` (three bfloat16 passes:
    hi*hi + hi*lo + lo*hi, float32 accumulation) or ``bf16`` (one pass),
    written out so that every platform computes the same products.  The
    high part is cut with ``reduce_precision``: a round trip through
    bfloat16 is an excess-precision no-op that XLA may drop, which would
    leave the low part zero.
    """
    n, d = corpus.shape
    blocks = corpus.reshape(n // rows, rows, d)
    bf = jnp.bfloat16

    def split(x):
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        return hi.astype(bf), (x - hi).astype(bf)

    def dot(a, b):
        return jnp.dot(a, b.T, preferred_element_type=jnp.float32)

    q_hi, q_lo = split(queries)

    def body(carry, xs):
        best_s, best_i = carry
        block, base = xs
        if mode == "highest":
            s = jnp.dot(queries, block.T, precision=HIGHEST)
        else:
            c_hi, c_lo = split(block)
            s = dot(q_hi, c_hi)
            if mode == "high":
                s = s + dot(q_hi, c_lo) + dot(q_lo, c_hi)
        ids = base + jnp.arange(rows, dtype=jnp.int32)
        cs = jnp.concatenate([best_s, s], axis=1)
        ci = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, s.shape)], axis=1)
        ts, ti = jax.lax.top_k(cs, k)
        return (ts, jnp.take_along_axis(ci, ti, axis=1)), None

    b = queries.shape[0]
    init = (jnp.full((b, k), -jnp.inf, jnp.float32),
            jnp.full((b, k), -1, jnp.int32))
    bases = jnp.arange(n // rows, dtype=jnp.int32) * rows
    (s, i), _ = jax.lax.scan(body, init, (blocks, bases))
    return s, i


def device_topk_all(corpus, queries: np.ndarray, k: int, mode: str,
                    batch: int = 256) -> tuple[np.ndarray, np.ndarray]:
    """``device_topk`` over every query, ``batch`` queries per call (the
    last call padded with zero rows)."""
    n = corpus.shape[0]
    rows = row_chunk(n)
    m = len(queries)
    out_s = np.zeros((m, k), np.float32)
    out_i = np.zeros((m, k), np.int32)
    for lo in range(0, m, batch):
        q = np.zeros((batch, queries.shape[1]), np.float32)
        q[:min(batch, m - lo)] = queries[lo:lo + batch]
        s, i = device_topk(corpus, jnp.asarray(q), k=k, rows=rows, mode=mode)
        hi = min(lo + batch, m)
        out_s[lo:hi] = np.asarray(s)[:hi - lo]
        out_i[lo:hi] = np.asarray(i)[:hi - lo]
    return out_s, out_i


def scores64(corpus_np: np.ndarray, queries: np.ndarray,
             ids: np.ndarray, block: int = 2048) -> np.ndarray:
    """float64 ``<q_r, corpus[ids[r, j]]>``; -inf where an id is invalid.
    ``block`` rows at a time, so that the float64 copies stay small."""
    ok = (ids >= 0) & (ids < len(corpus_np))
    safe = np.where(ok, ids, 0)
    s = np.empty(ids.shape, np.float64)
    for lo in range(0, len(ids), block):
        rows = corpus_np[safe[lo:lo + block]].astype(np.float64)
        s[lo:lo + block] = np.einsum(
            "rkd,rd->rk", rows, queries[lo:lo + block].astype(np.float64))
    return np.where(ok, s, -np.inf)


def exact_topk(corpus, corpus_np: np.ndarray, queries: np.ndarray, k: int,
               cand: int = 32, margin: float = 1e-4):
    """float64 exact top-k: (ids [m, k], scores [m, k] descending, number
    of queries scanned whole on the host)."""
    cs, ci = device_topk_all(corpus, queries, cand, "highest")
    s64 = scores64(corpus_np, queries, ci)
    order = np.argsort(-s64, axis=1, kind="stable")[:, :k]
    ids = np.take_along_axis(ci, order, axis=1)
    top = np.take_along_axis(s64, order, axis=1)
    doubtful = np.flatnonzero(top[:, -1] - cs[:, -1] <= margin)
    if len(doubtful):
        q64 = queries[doubtful].astype(np.float64)
        full = np.empty((len(doubtful), len(corpus_np)), np.float64)
        step = row_chunk(len(corpus_np))
        for lo in range(0, len(corpus_np), step):
            full[:, lo:lo + step] = q64 @ corpus_np[lo:lo + step].astype(
                np.float64).T
        o = np.argsort(-full, axis=1, kind="stable")[:, :k]
        ids[doubtful] = o
        top[doubtful] = np.take_along_axis(full, o, axis=1)
    return ids, top, len(doubtful)


def shortfall(ref_scores: np.ndarray, got_ids: np.ndarray,
              got_scores: np.ndarray) -> np.ndarray:
    """Per row, the widest gap by which the j-th returned document's exact
    score lies below the exact j-th best (2.0, above any gap of unit
    vectors, for a list that is not k distinct valid ids)."""
    gap = np.max(ref_scores - got_scores, axis=1)
    srt = np.sort(got_ids, axis=1)
    bad = ((got_ids < 0).any(axis=1) | (np.diff(srt, axis=1) == 0).any(axis=1)
           | ~np.isfinite(got_scores).all(axis=1))
    return np.where(bad, 2.0, np.maximum(gap, 0.0))


@functools.partial(jax.jit, static_argnames=("rows",))
def _centroid_gaps(corpus, centroids, listed, full, rows: int):
    """Per corpus row, at float32 HIGHEST: its best centroid score less the
    score of the bucket that lists it (``listed``, -1 for none), and less
    the best score over the buckets that are ``full``."""
    n, d = corpus.shape

    def body(_, xs):
        x, b = xs
        s = jnp.dot(x, centroids.T, precision=HIGHEST)
        best = s.max(axis=1)
        at = jnp.take_along_axis(s, jnp.maximum(b, 0)[:, None], axis=1)[:, 0]
        in_full = jnp.where(full[None, :], s, -jnp.inf).max(axis=1)
        return None, (best - at, best - in_full)

    _, (g_at, g_full) = jax.lax.scan(
        body, None, (corpus.reshape(n // rows, rows, d),
                     listed.reshape(n // rows, rows)))
    return g_at.reshape(n), g_full.reshape(n)


@jax.jit
def _bucket_vecs_wrong(corpus, bucket_vecs, bucket_ids):
    """Listed slots whose vector is not, bit for bit, its corpus row."""
    n = corpus.shape[0]

    def body(_, xs):
        vecs, ids = xs
        want = corpus.at[jnp.where(ids >= 0, ids, n)].get(mode="fill",
                                                          fill_value=0)
        return None, ((vecs != want).any(axis=-1) & (ids >= 0)).sum()

    _, wrong = jax.lax.scan(body, None, (bucket_vecs, bucket_ids))
    return wrong.sum()


def bucket_vecs_wrong(corpus, bucket_vecs, bucket_ids) -> int:
    return int(_bucket_vecs_wrong(corpus, bucket_vecs, bucket_ids))


def ivf_mismatch(corpus, corpus_np: np.ndarray, centroids: np.ndarray,
                 bucket_ids: np.ndarray, margin: float,
                 doubt: float = 1e-4) -> dict:
    """Rows of an IVF table that break nearest-centroid assignment.

    A device pass at float32 HIGHEST scores every corpus row against every
    centroid; a row whose verdict lies within ``doubt`` of ``margin``
    (far above float32 rounding) is scored again in float64 on the host."""
    n = corpus_np.shape[0]
    held = bucket_ids >= 0
    bucket, _ = np.nonzero(held)
    ids = bucket_ids[held]
    bad_id = ids >= n
    ids, bucket = ids[~bad_id], bucket[~bad_id]
    times = np.bincount(ids, minlength=n)
    listed = np.full(n, -1, np.int32)
    listed[ids] = bucket
    full = held.sum(axis=1) == bucket_ids.shape[1]
    g_at, g_full = (np.asarray(g, np.float64) for g in _centroid_gaps(
        corpus, jnp.asarray(centroids), jnp.asarray(listed),
        jnp.asarray(full), rows=row_chunk(n)))
    gap = np.where(listed >= 0, g_at, g_full)
    doubtful = np.flatnonzero(np.abs(gap - margin) <= doubt)
    cents64 = np.asarray(centroids, np.float64)
    for r in doubtful:
        s = cents64 @ corpus_np[r].astype(np.float64)
        pool = s[listed[r]] if listed[r] >= 0 else np.max(
            s[full], initial=-np.inf)
        gap[r] = s.max() - pool
    return {"ivf_bad_ids": int(bad_id.sum() + (bucket_ids < -1).sum()),
            "ivf_twice": int(np.maximum(times - 1, 0).sum()),
            "ivf_far": int(((listed >= 0) & (gap > margin)).sum()),
            "ivf_dropped": int(((listed < 0) & (gap > margin)).sum()),
            "ivf_unlisted": int((listed < 0).sum()),
            "ivf_doubtful": len(doubtful)}


class CacheReplay:
    """The HaS cache after a sequence of ingests, replayed on the host."""

    def __init__(self, h_max: int, k: int, doc_cap: int, d: int):
        self.h_max, self.k, self.doc_cap = h_max, k, doc_cap
        self.query_emb = np.zeros((h_max, d), np.float32)
        self.query_ids = np.full((h_max, k), -1, np.int32)
        self.query_valid = np.zeros(h_max, bool)
        self.doc_ids = np.full(doc_cap, -1, np.int32)
        self.q_ptr = 0
        self.d_ptr = 0
        self._slot_of: dict[int, int] = {}

    def ingest(self, q: np.ndarray, ids: np.ndarray) -> None:
        slot = self.q_ptr % self.h_max
        self.query_emb[slot] = q
        self.query_ids[slot] = ids
        self.query_valid[slot] = True
        self.q_ptr += 1
        seen: set[int] = set()
        new = []
        for x in (int(v) for v in ids):
            if x >= 0 and x not in self._slot_of and x not in seen:
                new.append(x)
            seen.add(x)
        for j, x in enumerate(new):
            pos = (self.d_ptr + j) % self.doc_cap
            old = int(self.doc_ids[pos])
            if old >= 0 and self._slot_of.get(old) == pos:
                del self._slot_of[old]
            self.doc_ids[pos] = x
            self._slot_of[x] = pos
        self.d_ptr += len(new)

    def doc_emb(self, corpus_np: np.ndarray) -> np.ndarray:
        """The doc store's vectors: copies of the corpus rows it holds."""
        out = np.zeros((self.doc_cap, corpus_np.shape[1]), np.float32)
        ok = self.doc_ids >= 0
        out[ok] = corpus_np[self.doc_ids[ok]]
        return out


def state_mismatch(ref: CacheReplay, got: dict, corpus_np) -> dict:
    """Rows where a program cache state (host arrays named as HasState's
    fields) differs from the replay, bit for bit; pointer mismatches
    count one each.  A state without the vector fields (``query_emb``,
    ``doc_emb``) is compared on the others."""
    valid = ref.query_valid
    q_rows = ((got["query_valid"] != valid)
              | (got["query_doc_ids"] != ref.query_ids).any(axis=1))
    if "query_emb" in got:
        q_rows |= valid & (got["query_emb"] != ref.query_emb).any(axis=1)
    held = ref.doc_ids >= 0
    d_rows = got["doc_ids"] != ref.doc_ids
    if "doc_emb" in got:
        d_rows |= held & (got["doc_emb"] != ref.doc_emb(corpus_np)).any(
            axis=1)
    return {"query_rows": int(q_rows.sum()), "doc_rows": int(d_rows.sum()),
            "pointers": int(int(got["q_ptr"]) != ref.q_ptr)
            + int(int(got["d_ptr"]) != ref.d_ptr)}


def homology_best(val_ids: np.ndarray, cache: CacheReplay) -> int:
    """The most of ``val_ids``' valid positions whose id a valid cached
    result holds (the homology score times k)."""
    rows = cache.query_ids[cache.query_valid]
    if not len(rows):
        return 0
    hit = (val_ids[None, :, None] == rows[:, None, :]).any(axis=2)
    hit &= val_ids[None, :] >= 0
    return int(hit.sum(axis=1).max())


def int8_codes(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Each row scaled by its own largest magnitude to [-127, 127] and
    rounded: (codes as float64 [n, d], scales [n])."""
    x = np.atleast_2d(np.asarray(x, np.float64))
    scale = np.maximum(np.abs(x).max(axis=-1), 1e-30) / 127
    return np.rint(x / scale[:, None]), scale


def int8_scores(codes, q: np.ndarray) -> np.ndarray:
    """Inner products of the rows' int8 ``codes`` with ``q``'s, summed
    exactly (integer products stay far inside float64's 53 bits) and
    scaled back."""
    rc, rs = codes
    qc, qs = int8_codes(q)
    return (rc @ qc[0]) * rs * qs[0]


class DraftBound:
    """The best draft a speculation on one replayed cache can give.

    For a query: the exact scores, descending, of the best k documents over
    the doc store and the buckets that any float32 scoring of the
    centroids probes: those among the top ``nprobe`` whose centroid score
    beats the next bucket's by more than ``margin``.  A draft's j-th
    document scores no lower than its j-th entry, up to its own rounding.
    With ``control``, also the exact scores of the k that int8 codes rank
    first among the same candidates: the reference one precision below the
    program's.  Bounds are made for all queries at once (``queries``),
    so that the doc store is scored in one pass."""

    def __init__(self, cache: CacheReplay, corpus_np: np.ndarray,
                 centroids: np.ndarray, bucket_ids: np.ndarray, k: int,
                 nprobe: int, margin: float, queries: np.ndarray,
                 control: bool = False):
        self.store_ids = cache.doc_ids[cache.doc_ids >= 0]
        store = corpus_np[self.store_ids].astype(np.float64)
        q64 = np.asarray(queries, np.float64)
        self.store_s = q64 @ store.T                    # [m, S]
        self.cent_s = q64 @ np.asarray(centroids, np.float64).T
        self.store_low = None
        if control:
            (sc, ss), (qc, qs) = int8_codes(store), int8_codes(q64)
            self.store_low = (qc @ sc.T) * qs[:, None] * ss[None, :]
        self.bucket_ids, self.corpus = bucket_ids, corpus_np
        self.k, self.nprobe, self.margin = k, nprobe, margin

    def __call__(self, r: int, q: np.ndarray):
        """The bound for query ``r`` of ``queries`` (``q``)."""
        k, nprobe = self.k, self.nprobe
        q64 = q.astype(np.float64)
        cs = self.cent_s[r]
        order = np.argsort(-cs, kind="stable")
        edge = cs[order[nprobe]] if nprobe < len(order) else -np.inf
        sure = [b for b in order[:nprobe] if cs[b] - edge > self.margin]
        members = [self.bucket_ids[b][self.bucket_ids[b] >= 0] for b in sure]
        rows = self.corpus[np.concatenate(members or [np.zeros(0, int)])]
        ids = np.concatenate([self.store_ids, *members])
        s = np.concatenate([self.store_s[r], rows.astype(np.float64) @ q64])
        _, first = np.unique(ids, return_index=True)
        pad = np.full(k, -np.inf)
        lb = np.concatenate([np.sort(s[first])[::-1][:k], pad])[:k]
        if self.store_low is None:
            return lb
        low = np.concatenate([self.store_low[r],
                              int8_scores(int8_codes(rows), q)])
        pick = s[first][np.argsort(-low[first], kind="stable")[:k]]
        return lb, np.concatenate([pick, pad])[:k]
