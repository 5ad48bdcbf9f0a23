"""Fig 1 analogue + kernel accounting: retrieval cost vs generation TTFT.

Measures the real (CPU) wall time of the jitted retrieval substrate at
several corpus scales, derives the paper-scale latency via the calibrated
bandwidth model, and reports HLO flops/bytes of the retrieval step (the
per-kernel roofline terms used in EXPERIMENTS.md §Roofline).

``--sweep-backend`` (also folded into ``run()``) additionally sweeps the
batch-native speculation pipeline over backend × batch size — the XLA
reference vs the Pallas kernel path (interpret mode off-TPU) — records
p50/p95 step latency, host→device dispatch counts from the
:mod:`repro.core.dispatch` probe, and the analytic bytes-moved model
(:func:`repro.core.has.speculation_bytes_moved`), and writes the
``BENCH_speculate.json`` artifact so the perf trajectory has a recorded
baseline.  The sweep asserts the dispatch model: one ``speculate_batch``
call is ONE dispatch regardless of B, vs the O(B) launches of per-query
serving.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import FAST, get_service, row
from repro.retrieval.flat import chunked_flat_search


def _time(fn, *args, reps=5):
    fn(*args)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _sweep_state(cfg, n_corpus, rng):
    """A fully-warmed HasState + IVF index over a random unit corpus."""
    from repro.core.has import HasState
    from repro.retrieval.ivf import build_ivf

    corpus = rng.normal(size=(n_corpus, cfg.d)).astype(np.float32)
    corpus /= np.linalg.norm(corpus, axis=1, keepdims=True)
    corpus = jnp.asarray(corpus)
    index = build_ivf(corpus, cfg.n_buckets, seed=0)
    # saturated cache: every doc slot and query row live
    doc_ids = rng.permutation(n_corpus)[:cfg.doc_cap].astype(np.int32)
    qids = rng.integers(0, n_corpus, (cfg.h_max, cfg.k)).astype(np.int32)
    state = HasState(
        query_emb=jnp.asarray(
            rng.normal(size=(cfg.h_max, cfg.d)).astype(np.float32)),
        query_doc_ids=jnp.asarray(qids),
        query_valid=jnp.ones((cfg.h_max,), bool),
        q_ptr=jnp.asarray(cfg.h_max, jnp.int32),
        doc_emb=corpus[jnp.asarray(doc_ids)],
        doc_ids=jnp.asarray(doc_ids),
        d_ptr=jnp.asarray(cfg.doc_cap, jnp.int32))
    return state, index, corpus


def sweep_backends(out_path: str = "BENCH_speculate.json",
                   batches=(1, 8, 32), reps: int = 5):
    """Backend × batch-size sweep of ``speculate_batch`` -> CSV rows + JSON.

    Asserts the acceptance dispatch model: for B >= 32 the batch-native
    path issues <= 3 device dispatches per speculation batch (it issues
    exactly 1), where a per-query loop of B=1 calls issues B.
    """
    from repro.core import dispatch
    from repro.core.has import (HasConfig, speculate_batch,
                                speculation_bytes_moved)

    rng = np.random.default_rng(0)
    n_corpus = 20_000 if FAST else 50_000
    cfg = HasConfig(k=10, tau=0.2, h_max=1024 if FAST else 2048,
                    doc_capacity=4096 if FAST else 8192,
                    nprobe=4, n_buckets=128 if FAST else 256, d=64)
    state, index, _ = _sweep_state(cfg, n_corpus, rng)
    interpret = jax.default_backend() != "tpu"
    backends = ["xla", "pallas"]

    # per-query path: B calls at B=1, O(B) dispatches under host
    # iteration — backend-independent, so measured once per batch size
    legacy = {}
    for b in batches:
        q = jnp.asarray(rng.normal(size=(b, 1, cfg.d)), jnp.float32)
        jax.block_until_ready(speculate_batch(cfg, state, index, q[0]))
        with dispatch.capture() as legacy_probe:
            for i in range(b):
                jax.block_until_ready(
                    speculate_batch(cfg, state, index, q[i]))
        legacy[b] = legacy_probe.total()

    rows, records = [], []
    verdict_ok = True
    for backend in backends:
        for b in batches:
            q = jnp.asarray(rng.normal(size=(b, cfg.d)), jnp.float32)
            # compile, then measure; one capture verifies the dispatch count
            jax.block_until_ready(
                speculate_batch(cfg, state, index, q, backend=backend))
            with dispatch.capture() as probe:
                jax.block_until_ready(
                    speculate_batch(cfg, state, index, q, backend=backend))
            dispatches = probe.total()
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                out = speculate_batch(cfg, state, index, q, backend=backend)
                jax.block_until_ready(out)
                times.append(time.perf_counter() - t0)
            p50 = float(np.percentile(times, 50))
            p95 = float(np.percentile(times, 95))
            legacy_dispatches = legacy[b]
            rec = {
                "backend": backend, "batch": b, "interpret": bool(interpret),
                "p50_step_s": p50, "p95_step_s": p95,
                "dispatches_per_batch": dispatches,
                "legacy_dispatches_per_batch": legacy_dispatches,
                "bytes_moved_est": speculation_bytes_moved(
                    cfg, index.n_buckets, index.capacity, b, backend),
            }
            records.append(rec)
            rows.append(row(
                f"roofline/speculate_batch/{backend}/B={b}", p50,
                f"p95={p95 * 1e6:.1f}us;dispatches={dispatches};"
                f"legacy_dispatches={legacy_dispatches};"
                f"bytes={rec['bytes_moved_est']:.3e}"))
            if b >= 32 and dispatches > 3:
                verdict_ok = False

    rows.append(row(
        "roofline/speculate_dispatch_verdict", 0.0,
        f"{'PASS' if verdict_ok else 'FAIL'}"
        f"(batch-native<=3 dispatches at B>=32, legacy=O(B))"))
    # persist the artifact BEFORE asserting, so a failing verdict still
    # leaves the sweep data on disk to diagnose
    with open(out_path, "w") as f:
        json.dump({"config": {"n_corpus": n_corpus, "k": cfg.k,
                              "h_max": cfg.h_max, "doc_cap": cfg.doc_cap,
                              "nprobe": cfg.nprobe,
                              "n_buckets": index.n_buckets,
                              "backend_default_interpret": bool(interpret)},
                   "sweep": records}, f, indent=1)
    print(f"# wrote {out_path} ({len(records)} sweep points)")
    assert verdict_ok, "batch-native speculation exceeded 3 dispatches/batch"
    return rows


def run():
    rows = []
    svc = get_service()
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 64)), jnp.float32)

    for n in (10_000, 50_000, svc.world.cfg.n_docs):
        corpus = svc.corpus[:n]
        fn = jax.jit(lambda c, qq: chunked_flat_search(c, qq, 10, 8192))
        t = _time(fn, corpus, q)
        lowered = fn.lower(corpus, q)
        cost = lowered.compile().cost_analysis()
        rows.append(row(
            f"roofline/flat_scan/n={n}", t,
            f"flops={cost.get('flops', 0):.3e};"
            f"bytes={cost.get('bytes accessed', 0):.3e};"
            f"GB/s={n * 64 * 4 / t / 1e9:.2f}"))

    # paper-scale extrapolation (Fig 1's point: retrieval >> bare-LLM TTFT)
    full_t = svc.latency.full_scan_time()
    rows.append(row("roofline/full_db_49.2M_extrapolated", full_t,
                    f"vs_bare_llm_ttft~0.1s_x{full_t / 0.1:.1f}"))

    # HaS fast path budget: cache scan + validation at paper scale
    from repro.core.has import HasConfig, init_has_state, speculate_batch
    from repro.retrieval.ivf import build_ivf
    cfg = HasConfig(k=10, tau=0.2, h_max=5000, nprobe=16, n_buckets=512,
                    d=64)
    state = init_has_state(cfg)
    index = build_ivf(svc.corpus[:50_000], 512, seed=0)
    jax.block_until_ready(speculate_batch(cfg, state, index, q))  # compile
    t0 = time.perf_counter()
    for _ in range(10):
        out = speculate_batch(cfg, state, index, q)
    jax.block_until_ready(out)
    t_spec = (time.perf_counter() - t0) / 10
    rows.append(row("roofline/has_fast_path", t_spec,
                    f"doc_store={cfg.doc_cap};H={cfg.h_max}"))

    # backend × batch-size sweep of the batch-native speculation pipeline
    rows.extend(sweep_backends())
    return rows


if __name__ == "__main__":
    import sys

    from benchmarks.common import fmt_rows
    if "--sweep-backend" in sys.argv:
        print(fmt_rows(sweep_backends()))
    else:
        print(fmt_rows(run()))
