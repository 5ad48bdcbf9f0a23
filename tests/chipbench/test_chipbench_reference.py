"""The plain reference against the program it judges, at small sizes."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, reference as ref


def _unit(x):
    return (x / np.linalg.norm(x, axis=-1, keepdims=True)).astype(np.float32)


def test_cache_replay_matches_the_programs_ingest():
    """Ring wrap, documents already held, repeats inside one result and
    -1 padding: the replay and ``cache_update_chunked`` agree bit for
    bit."""
    from repro.core.has import HasConfig, cache_update_chunked, \
        init_has_state
    rng = np.random.default_rng(0)
    n, d, k = 300, 16, 10
    corpus = _unit(rng.normal(size=(n, d)))
    cfg = HasConfig(k=k, h_max=7, doc_capacity=23, d=d)
    rows = 40
    q = _unit(rng.normal(size=(rows, d)))
    ids = rng.integers(0, 60, size=(rows, k)).astype(np.int32)
    ids[3, 4:] = ids[3, 0]                       # repeats in one result
    ids[5, -2:] = -1                             # padding
    state = cache_update_chunked(cfg, init_has_state(cfg), q, ids,
                                 corpus=jnp.asarray(corpus), chunk=8)
    got = {f: np.asarray(getattr(state, f)) for f in (
        "query_emb", "query_doc_ids", "query_valid", "q_ptr", "doc_emb",
        "doc_ids", "d_ptr")}
    replay = ref.CacheReplay(7, k, 23, d)
    for a, b in zip(q, ids):
        replay.ingest(a, b)
    assert ref.state_mismatch(replay, got, corpus) == {
        "query_rows": 0, "doc_rows": 0, "pointers": 0}
    # one row folded twice is seen
    replay.ingest(q[-1], ids[-1])
    assert sum(ref.state_mismatch(replay, got, corpus).values()) > 0


def test_exact_topk_and_shortfall():
    rng = np.random.default_rng(1)
    corpus = _unit(rng.normal(size=(4096, 64)))
    q = _unit(rng.normal(size=(50, 64)))
    ids, top, host = ref.exact_topk(jnp.asarray(corpus), corpus, q, 10)
    full = q.astype(np.float64) @ corpus.astype(np.float64).T
    want = np.argsort(-full, axis=1, kind="stable")[:, :10]
    np.testing.assert_array_equal(ids, want)
    assert host == 0
    got = ref.scores64(corpus, q, ids)
    assert ref.shortfall(top, ids, got).max() == 0.0
    swapped = ids.copy()
    swapped[:, [0, 9]] = swapped[:, [9, 0]]
    assert (ref.shortfall(top, swapped, ref.scores64(corpus, q, swapped))
            > 0).all()
    dup = ids.copy()
    dup[:, 1] = dup[:, 0]
    assert (ref.shortfall(top, dup, ref.scores64(corpus, q, dup)) == 2.0
            ).all()


def _near_ties(m, n, d, gap, seed):
    """Queries whose 16 best documents lie ``gap`` apart in exact score."""
    rng = np.random.default_rng(seed)
    q = _unit(rng.normal(size=(m, d)))
    corpus = _unit(rng.normal(size=(n, d))) * 0.3
    corpus = _unit(corpus)
    for r in range(m):
        for j in range(16):
            c = 0.9 - j * gap
            o = rng.normal(size=d)
            o -= (o @ q[r]) * q[r]
            o /= np.linalg.norm(o)
            corpus[r * 16 + j] = c * q[r] + np.sqrt(1 - c * c) * o
    return corpus.astype(np.float32), q


def test_one_bf16_pass_fails_the_scan_tolerance():
    """Near-ties 1e-4 apart: the reference's own top-k at one bfloat16 pass,
    in the served answers' place, trails the float64 top-k by more than the
    stated float32 tolerance, while float32 answers stay within it.  Three
    bfloat16 passes are read too, for the record."""
    limit = check.load_limits({"limits": {"scan_gap": 2.0 ** -22}})[
        "scan_gap"]
    corpus, q = _near_ties(64, 8192, 768, gap=1e-4, seed=2)
    served = ref.device_topk_all(jnp.asarray(corpus), q, 10, "highest")[1]
    out = check.scan_numbers(jnp.asarray(corpus), corpus, q, served, 10,
                             control=True)
    assert out["scan_gap"] <= limit
    assert out["control_bf16_scan_gap"] > limit
    assert out["control_bf16_rows_wrong"] > 0
    assert out["control_high_scan_gap"] >= 0.0


def test_ivf_mismatch_reads_the_programs_table():
    """The program's IVF build reads 0, its overflow rows left out of full
    buckets; a row dropped from a bucket that is not full, a row moved to
    a far bucket and a row listed twice each count."""
    from repro.retrieval.ivf import build_ivf
    rng = np.random.default_rng(4)
    n, d = 4096, 32
    corpus = _unit(rng.normal(size=(n, d)))
    idx = build_ivf(jnp.asarray(corpus), 64, capacity_factor=1.0)
    cents = np.asarray(idx.centroids)
    ids = np.asarray(idx.bucket_ids)

    def read(table):
        return ref.ivf_mismatch(jnp.asarray(corpus), corpus, cents, table,
                                margin=1e-2)
    base = read(ids)
    assert base["ivf_unlisted"] > 0             # capacity 64: some overflow
    assert base["ivf_far"] == base["ivf_dropped"] == base["ivf_twice"] == 0
    held = (ids >= 0).sum(axis=1)
    part, full = int(held.argmin()), int(held.argmax())
    assert held[full] == ids.shape[1] > held[part] > 1
    dropped = ids.copy()
    dropped[part, held[part] - 1] = -1
    assert read(dropped)["ivf_dropped"] == 1
    # the base's overflow rows are excused by their full bucket; one slot
    # of it emptied, the row taken out and that bucket's overflow count
    short = ids.copy()
    short[full, -1] = -1
    assert read(short)["ivf_dropped"] > 1
    s = corpus @ cents.T
    moved = ids.copy()
    row = ids[part, 0]
    room = np.flatnonzero(held < ids.shape[1])
    far = int(room[np.argmin(s[row, room])])
    assert s[row].max() - s[row, far] > 0.1
    moved[far, held[far]] = row
    moved[part, 0] = -1
    assert read(moved)["ivf_far"] == 1
    twice = ids.copy()
    twice[part, held[part]] = ids[part, 0]
    assert read(twice)["ivf_twice"] == 1


def test_spec_numbers_on_a_replayed_cache():
    rng = np.random.default_rng(3)
    n, d, k = 2000, 32, 10
    corpus = _unit(rng.normal(size=(n, d)))
    has = {"k": k, "tau": 0.2, "nprobe": 2}
    cache = ref.CacheReplay(50, k, 500, d)
    for _ in range(30):
        cache.ingest(_unit(rng.normal(size=d)),
                     rng.choice(n, k, replace=False).astype(np.int32))
    cent = _unit(rng.normal(size=(8, d)))
    assign = np.argmax(corpus @ cent.T, axis=1)
    cap = np.bincount(assign).max()
    buckets = np.full((8, cap), -1, np.int32)
    for b in range(8):
        m = np.flatnonzero(assign == b)
        buckets[b, :len(m)] = m
    q = _unit(rng.normal(size=(5, d)))
    # the program's answer, by brute force: top-k over store + all buckets
    pool = np.unique(np.concatenate([cache.doc_ids[cache.doc_ids >= 0],
                                     np.arange(n)]))
    s = q @ corpus[pool].T
    draft = pool[np.argsort(-s, axis=1)[:, :k]].astype(np.int32)
    best = np.array([ref.homology_best(v, cache) for v in draft])
    prog = {"val_ids": draft, "draft_ids": draft, "accept": best / k > 0.2,
            "homology": best / k}
    out = check.spec_numbers(q, prog, cache, corpus, cent, buckets, has)
    assert out["accept_bad"] == 0 and out["draft_gap"] < 1e-6
    bad = dict(prog, accept=~prog["accept"])
    assert check.spec_numbers(q, bad, cache, corpus, cent, buckets,
                              has)["accept_bad"] == 5
    worse = dict(prog, draft_ids=draft[:, ::-1].copy(),
                 val_ids=draft[:, ::-1].copy())
    assert check.spec_numbers(q, worse, cache, corpus, cent, buckets,
                              has)["draft_gap"] > 1e-3
