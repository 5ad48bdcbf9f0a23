"""Batch-native speculation + fused-ingest parity suite.

``speculate_batch(backend="pallas", interpret=True)`` must be bit-equal to
the XLA reference (``backend="xla"``) on random AND adversarial inputs —
all-invalid cache, duplicate ids across channels, tail tiles — and
``cache_update_batched`` must equal a sequential fold of ``cache_update``.
Also covers the two dedup satellite fixes (in-batch doc dedup, stale-id
normalization in ``_dedup_merge``) and the dispatch-count model.
"""
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch
from repro.core.has import (HasConfig, _dedup_merge, cache_update,
                            cache_update_batched, init_has_state,
                            speculate_batch)
from repro.retrieval.ivf import build_ivf

RNG = np.random.default_rng(11)


def _world(cfg, n_corpus=256, seed=0, n_ingests=6):
    """Unit corpus + IVF index + a state warmed with real full results."""
    rng = np.random.default_rng(seed)
    corpus = rng.normal(size=(n_corpus, cfg.d)).astype(np.float32)
    corpus /= np.linalg.norm(corpus, axis=1, keepdims=True)
    index = build_ivf(jnp.asarray(corpus), cfg.n_buckets, seed=0)
    state = init_has_state(cfg)
    for _ in range(n_ingests):
        q = rng.normal(size=(cfg.d,)).astype(np.float32)
        ids = np.argsort(-(corpus @ q))[:cfg.k].astype(np.int32)
        state = cache_update(cfg, state, jnp.asarray(q), jnp.asarray(ids),
                             jnp.asarray(corpus[ids]))
    return corpus, index, state


def _assert_outputs_equal(a, b):
    for key in ("accept", "homology", "matched_slot", "val_ids",
                "draft_ids", "draft_scores"):
        x, y = np.asarray(a[key]), np.asarray(b[key])
        if np.issubdtype(x.dtype, np.floating):
            np.testing.assert_allclose(x, y, rtol=1e-6, atol=1e-6,
                                       err_msg=key)
        else:
            np.testing.assert_array_equal(x, y, err_msg=key)


@pytest.mark.parametrize("b,tile_c", [(1, 64), (5, 64), (8, 1024)])
def test_backend_parity_random(b, tile_c):
    cfg = HasConfig(k=5, tau=0.2, h_max=32, doc_capacity=96, nprobe=2,
                    n_buckets=8, d=16)
    corpus, index, state = _world(cfg)
    q = jnp.asarray(RNG.normal(size=(b, cfg.d)), jnp.float32)
    out_x = speculate_batch(cfg, state, index, q, backend="xla")
    out_p = speculate_batch(cfg, state, index, q, backend="pallas",
                            interpret=True, tile_c=tile_c)
    _assert_outputs_equal(out_x, out_p)


def test_backend_parity_all_invalid_cache():
    """Empty doc store + no valid cached queries: every channel must mask,
    nothing accepts, and no phantom ids leak into the drafts."""
    cfg = HasConfig(k=4, tau=0.2, h_max=16, doc_capacity=64, nprobe=2,
                    n_buckets=8, d=16)
    corpus, index, _ = _world(cfg, n_ingests=0)
    state = init_has_state(cfg)                      # all doc_ids == -1
    q = jnp.asarray(RNG.normal(size=(3, cfg.d)), jnp.float32)
    out_x = speculate_batch(cfg, state, index, q, backend="xla")
    out_p = speculate_batch(cfg, state, index, q, backend="pallas",
                            interpret=True, tile_c=64)
    _assert_outputs_equal(out_x, out_p)
    assert not np.asarray(out_p["accept"]).any()
    # cache-channel contribution fully masked: only fuzzy (corpus) ids
    # survive, every non-finite score carries id -1
    for out in (out_x, out_p):
        scores = np.asarray(out["draft_scores"])
        ids = np.asarray(out["draft_ids"])
        assert np.all(ids[~np.isfinite(scores)] == -1)


def test_backend_parity_duplicate_ids():
    """Doc store seeded from real full results so the fuzzy channel returns
    the same ids -> the dedup-merge path is exercised in both backends."""
    cfg = HasConfig(k=6, tau=0.1, h_max=16, doc_capacity=64, nprobe=4,
                    n_buckets=8, d=16)
    corpus, index, state = _world(cfg, n_ingests=8)
    # queries aimed at cached docs maximize cache/fuzzy overlap
    docs = np.asarray(state.doc_emb)[np.asarray(state.doc_ids) >= 0]
    q = jnp.asarray(docs[:4] + 0.01 * RNG.normal(size=(4, cfg.d)),
                    jnp.float32)
    out_x = speculate_batch(cfg, state, index, q, backend="xla")
    out_p = speculate_batch(cfg, state, index, q, backend="pallas",
                            interpret=True, tile_c=64)
    _assert_outputs_equal(out_x, out_p)
    # sanity: no draft row may contain a live duplicate id
    for row_ids in np.asarray(out_p["draft_ids"]):
        live = row_ids[row_ids >= 0]
        assert live.size == np.unique(live).size


def test_backend_parity_tail_tile():
    """doc_capacity not a multiple of tile_c: the kernel's padded tail tile
    must never contribute candidates."""
    cfg = HasConfig(k=4, tau=0.2, h_max=13, doc_capacity=100, nprobe=2,
                    n_buckets=8, d=16)
    corpus, index, state = _world(cfg, n_ingests=12)
    q = jnp.asarray(RNG.normal(size=(5, cfg.d)), jnp.float32)
    out_x = speculate_batch(cfg, state, index, q, backend="xla")
    out_p = speculate_batch(cfg, state, index, q, backend="pallas",
                            interpret=True, tile_c=64)    # 100 -> 64 + 36
    _assert_outputs_equal(out_x, out_p)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_single_query_consistency(backend):
    """A query alone at B=1 (the sequential engine's program) gets the
    same outputs as the same query as one row of a B=8 batch (the
    scheduler's)."""
    cfg = HasConfig(k=5, tau=0.2, h_max=32, doc_capacity=96, nprobe=2,
                    n_buckets=8, d=16)
    corpus, index, state = _world(cfg)
    docs = np.asarray(state.doc_emb)[np.asarray(state.doc_ids) >= 0]
    q = np.concatenate([docs[:4] + 0.01 * RNG.normal(size=(4, cfg.d)),
                        RNG.normal(size=(4, cfg.d))]).astype(np.float32)
    kw = dict(backend=backend, interpret=True, tile_c=64)
    out_b = speculate_batch(cfg, state, index, jnp.asarray(q), **kw)
    assert np.asarray(out_b["accept"]).any()
    for i in range(len(q)):
        out_1 = speculate_batch(cfg, state, index, jnp.asarray(q[i:i + 1]),
                                **kw)
        _assert_outputs_equal({key: np.asarray(v)[i:i + 1]
                               for key, v in out_b.items()}, out_1)


# -- fused ingest ----------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 3, 9])
def test_cache_update_batched_equals_sequential_fold(seed):
    rng = np.random.default_rng(seed)
    cfg = HasConfig(k=4, h_max=5, doc_capacity=16, d=8)
    B = 11
    qe = rng.normal(size=(B, cfg.d)).astype(np.float32)
    fids = rng.integers(0, 30, size=(B, cfg.k)).astype(np.int32)
    fids[1, 2] = fids[1, 0]                      # in-batch duplicate
    fvecs = rng.normal(size=(B, cfg.k, cfg.d)).astype(np.float32)
    mask = rng.random(B) > 0.3
    mask[0] = True

    seq = init_has_state(cfg)
    for i in range(B):
        if mask[i]:
            seq = cache_update(cfg, seq, jnp.asarray(qe[i]),
                               jnp.asarray(fids[i]), jnp.asarray(fvecs[i]))
    bat = cache_update_batched(cfg, init_has_state(cfg), jnp.asarray(qe),
                               jnp.asarray(fids), jnp.asarray(fvecs),
                               jnp.asarray(mask))
    for f in ("query_emb", "query_doc_ids", "query_valid", "q_ptr",
              "doc_emb", "doc_ids", "d_ptr"):
        np.testing.assert_array_equal(np.asarray(getattr(seq, f)),
                                      np.asarray(getattr(bat, f)),
                                      err_msg=f)


def test_cache_update_batched_default_mask():
    cfg = HasConfig(k=3, h_max=4, doc_capacity=16, d=4)
    qe = jnp.asarray(RNG.normal(size=(2, 4)), jnp.float32)
    fids = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    fvecs = jnp.ones((2, 3, 4))
    out = cache_update_batched(cfg, init_has_state(cfg), qe, fids, fvecs)
    assert int(out.q_ptr) == 2 and int(out.d_ptr) == 6


# -- satellite dedup fixes -------------------------------------------------

def test_cache_update_dedups_within_incoming_batch():
    """Duplicate ids inside one full result must occupy ONE ring slot."""
    cfg = HasConfig(k=4, h_max=4, doc_capacity=16, d=4)
    state = init_has_state(cfg)
    ids = jnp.asarray([5, 5, 7, 5], jnp.int32)
    state = cache_update(cfg, state, jnp.ones((4,)), ids, jnp.ones((4, 4)))
    live = np.asarray(state.doc_ids)
    live = live[live >= 0]
    assert sorted(live.tolist()) == [5, 7]
    assert int(state.d_ptr) == 2                 # no wasted capacity


def test_dedup_merge_normalizes_stale_ids():
    """A dup-masked b-entry keeps -inf score AND id -1 in the merge."""
    s_a = jnp.asarray([1.0, -jnp.inf], jnp.float32)
    i_a = jnp.asarray([3, -1], jnp.int32)
    s_b = jnp.asarray([0.9, 0.8], jnp.float32)
    i_b = jnp.asarray([3, 3], jnp.int32)          # both duplicate id 3
    ts, ti = _dedup_merge(s_a, i_a, s_b, i_b, 3)
    ts, ti = np.asarray(ts), np.asarray(ti)
    assert ti[0] == 3 and np.isfinite(ts[0])
    # every non-finite merged score must carry id -1, never a stale 3
    assert np.all(ti[~np.isfinite(ts)] == -1)
    assert np.sum(ti == 3) == 1


# -- dispatch model --------------------------------------------------------

def test_batch_entry_points_are_single_dispatch():
    cfg = HasConfig(k=4, tau=0.2, h_max=16, doc_capacity=64, nprobe=2,
                    n_buckets=8, d=16)
    corpus, index, state = _world(cfg)
    q = jnp.asarray(RNG.normal(size=(4, cfg.d)), jnp.float32)
    with dispatch.capture() as probe:
        speculate_batch(cfg, state, index, q, backend="xla")
    assert probe.counts() == {"speculate_batch": 1}
    with dispatch.capture() as probe:
        cache_update_batched(
            cfg, init_has_state(cfg), q,
            jnp.zeros((4, cfg.k), jnp.int32), jnp.zeros((4, cfg.k, cfg.d)),
            jnp.zeros((4,), bool))
    assert probe.counts() == {"cache_update_batched": 1}


# -- benchmark smoke (slow: exercises the full sweep machinery) ------------

@pytest.mark.slow
def test_roofline_backend_sweep_smoke(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCH_FAST", "1")
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    try:
        from benchmarks.retrieval_roofline import sweep_backends
        out = tmp_path / "BENCH_speculate.json"
        rows = sweep_backends(out_path=str(out), batches=(1, 4), reps=2)
    finally:
        sys.path.pop(0)
    assert out.exists()
    import json
    data = json.loads(out.read_text())
    assert len(data["sweep"]) == 4               # 2 backends x 2 batches
    assert all(r["dispatches_per_batch"] == 1 for r in data["sweep"])
    assert any("dispatch_verdict" in r["name"] and "PASS" in r["derived"]
               for r in rows)
