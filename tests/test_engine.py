"""Serving-engine integration: HaS vs baselines on a small world (fast)."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.has import (HasConfig, HasState, cache_memory_bytes,
                            cache_update, init_has_state, speculate_batch)
from repro.data.synthetic import DATASETS, SyntheticWorld, WorldConfig
from repro.serving.engine import (ANNSEngine, CRAGEngine, FullRetrievalEngine,
                                  HasEngine, ReuseEngine, RetrievalService)
from repro.serving.latency import LatencyModel


@pytest.fixture(scope="module")
def service():
    world = SyntheticWorld(WorldConfig(n_entities=800, seed=0))
    return RetrievalService(world, LatencyModel(), k=10, chunk=2048)


@pytest.fixture(scope="module")
def queries(service):
    ds = DATASETS["granola"]
    return service.world.sample_queries(
        500, pattern=ds["pattern"], zipf_a=ds["zipf_a"],
        p_uncovered=ds["p_uncovered"], seed=1)


def _has(service, **kw):
    cfg = HasConfig(k=10, tau=kw.pop("tau", 0.2), h_max=kw.pop("h_max", 800),
                    nprobe=8, n_buckets=128, d=service.world.cfg.d, **kw)
    return HasEngine(service, cfg)


def test_step_matches_algorithm1_calls(service, queries):
    """``HasEngine.step`` serves what ``speculate_batch``, the backend's
    ``search`` and ``cache_update``, called directly in Algorithm 1's order
    with eager device arrays, serve, and leaves the same cache bit for
    bit."""
    eng = _has(service, h_max=64)
    cfg, state = eng.cfg, init_has_state(eng.cfg)
    seen = set()
    for q in queries[:80]:
        ids, accept, _, homology = eng.step(q["emb"])
        qd = jnp.asarray(q["emb"])
        out = speculate_batch(cfg, state, eng.index, qd[None],
                              backend=eng.backend)
        want = bool(out["accept"][0])
        if want:
            want_ids = np.asarray(out["draft_ids"][0])
        else:
            full = service.backend.search(qd[None])[1][0]
            want_ids = np.asarray(full)
            state = cache_update(cfg, state, qd, full, service.corpus[full])
        assert accept == want
        assert homology == float(out["homology"][0])
        np.testing.assert_array_equal(ids, want_ids)
        seen.add(accept)
    assert seen == {True, False}
    for f in dataclasses.fields(HasState):
        np.testing.assert_array_equal(np.asarray(getattr(eng.state, f.name)),
                                      np.asarray(getattr(state, f.name)))


def test_has_reduces_latency_vs_full(service, queries):
    full = FullRetrievalEngine(service).serve(queries[:150]).summary()
    has = _has(service).serve(queries).summary()
    assert has["avg_latency_s"] < full["avg_latency_s"] * 0.95
    assert has["dar"] > 0.1
    # accuracy within a few points (paper: 1-2%)
    assert has["doc_hit_rate"] > full["doc_hit_rate"] - 0.08


def test_l_at_da_much_smaller_than_l_at_dr(service, queries):
    s = _has(service).serve(queries).summary()
    # fast path ~= edge RTT + fuzzy scan; slow path ~= cloud RTT + full scan
    assert s["l_at_da"] < 0.4 < s["l_at_dr"]


def test_higher_tau_stricter(service, queries):
    lo = _has(service, tau=0.1).serve(queries).summary()
    hi = _has(service, tau=0.5).serve(queries).summary()
    assert hi["dar"] <= lo["dar"] + 1e-9
    assert hi["avg_latency_s"] >= lo["avg_latency_s"] - 0.02


def test_larger_cache_more_acceptance(service, queries):
    small = _has(service, h_max=50).serve(queries).summary()
    large = _has(service, h_max=800).serve(queries).summary()
    assert large["dar"] >= small["dar"] - 0.02
    assert cache_memory_bytes(HasConfig(h_max=800, d=64)) > \
        cache_memory_bytes(HasConfig(h_max=50, d=64))


def test_reuse_engines_run(service, queries):
    for method, kw in [("proximity", dict(theta=0.85)),
                       ("saferadius", dict(alpha=2.0)),
                       ("mincache", dict(t_lex=0.5, t_sem=0.85))]:
        s = ReuseEngine(service, method, h_max=800, **kw).serve(
            queries[:200]).summary()
        assert np.isfinite(s["avg_latency_s"])
        # reuse-based methods never beat HaS on DAR (homology >> identity)
    prox = ReuseEngine(service, "proximity", h_max=800, theta=0.85)
    sp = prox.serve(queries).summary()
    sh = _has(service).serve(queries).summary()
    assert sh["dar"] > sp["dar"]


def test_crag_pays_evaluator_latency(service, queries):
    crag = CRAGEngine(service).serve(queries[:100]).summary()
    has = _has(service).serve(queries[:100]).summary()
    # the 0.7s LLM judge makes even accepted drafts slow
    assert crag["l_at_da"] > 0.55
    assert has["l_at_da"] < 0.2


def test_anns_engine_edge_vs_cloud(service, queries):
    edge = ANNSEngine(service, "ivf", n_buckets=128, nprobe=4,
                      on_edge=True).serve(queries[:100]).summary()
    cloud = ANNSEngine(service, "ivf", n_buckets=128, nprobe=40,
                       on_edge=False).serve(queries[:100]).summary()
    assert edge["avg_latency_s"] < cloud["avg_latency_s"]
    assert cloud["doc_hit_rate"] >= edge["doc_hit_rate"] - 0.05


def test_has_with_anns_fallback(service, queries):
    fallback = ANNSEngine(service, "ivf", n_buckets=128, nprobe=40,
                          on_edge=False)
    combo = HasEngine(service, HasConfig(k=10, tau=0.2, h_max=800, nprobe=8,
                                         n_buckets=128, d=64),
                      fallback=fallback).serve(queries).summary()
    plain = ANNSEngine(service, "ivf", n_buckets=128, nprobe=40,
                       on_edge=False).serve(queries).summary()
    assert combo["avg_latency_s"] < plain["avg_latency_s"]
