"""``seq``: one client in a closed loop through ``HasEngine.step``.

The paper's Algorithm 1: each request speculates (one fused program at
B=1), and a rejected draft pays an exact full-corpus scan and a cache
ingest before the step returns.  ``step`` returns host ids and the accept
flag, so it has waited for its device work: the wall time around it is the
request's latency.

Set-up warms the query cache as a long-running client's would be: the
exact answers of the stream's first ``warm_requests`` requests, found by
the program's own batched scan (``warm_batch`` at a time), are folded in
by its batched ingest; then ``warm_steps`` requests (more, until one has
been accepted and one rejected) go through ``step`` itself, which compiles
every program the window runs.  The window continues the stream.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from chipbench.drivers import Lifetime, Warm, Window, stack_rows

ENGINE = "has"
LOAD = "closed loop, one client: lateness is the host gap between a reply and the next send"


def install(probe, engine) -> None:
    """Wrap the entries ``HasEngine.step`` calls."""
    from repro.serving import engine as loop
    probe.patch(loop, "speculate_batch", "spec")
    # cache_update(cfg, state, q_emb [d], full_ids [k], full_vecs, ...)
    probe.patch(loop, "cache_update", "ingest",
                record=lambda args, kwargs, out: (args[2], args[3]))
    probe.patch(engine.s.backend, "search", "cloud_scan",
                record=lambda args, kwargs, out: (args[0], out))


def spec_batch(engine) -> int:
    return 1


def spec_backend(engine):
    return engine.backend


def warm(engine, stream, traffic) -> Warm:
    from repro.core.has import cache_update_chunked

    w, b, k = traffic["warm_requests"], traffic["warm_batch"], engine.cfg.k
    emb = stream.emb
    bulk = np.zeros((w, k), np.int32)
    for lo in range(0, w, b):
        m = min(b, w - lo)
        block = np.zeros((b, emb.shape[1]), np.float32)
        block[:m] = emb[lo:lo + m]
        bulk[lo:lo + m] = np.asarray(
            engine.s.backend.search(jnp.asarray(block))[1])[:m]
    engine.state = cache_update_chunked(engine.cfg, engine.state, emb[:w],
                                        bulk, corpus=engine.s.corpus,
                                        chunk=b)
    ids, acc = [], []
    i = w
    while (i < w + traffic["warm_steps"]
           or not (any(acc) and not all(acc))):
        got, accept, _, _ = engine.step(emb[i])
        ids.append(got)
        acc.append(accept)
        i += 1
    acc = np.array(acc, bool)
    return Warm(rows=np.arange(i), ids=np.concatenate([bulk, np.stack(ids)]),
                accepts=np.concatenate([np.zeros(w, bool), acc]),
                cloud=np.concatenate([np.ones(w, bool), ~acc]),
                preloaded=w)


def window(engine, stream, start: int, seconds: float, traffic,
           seed: int) -> Window:
    emb = stream.emb
    n_max = len(emb) - start
    lat = np.zeros(n_max)
    gap = np.zeros(n_max)
    model = np.zeros(n_max)
    acc = np.zeros(n_max, bool)
    ids = np.zeros((n_max, engine.cfg.k), np.int32)
    t0 = time.perf_counter()
    prev = t0
    n = 0
    while True:
        if n == n_max:
            raise RuntimeError(f"stream of {len(emb)} requests ran out "
                               f"after {n} in the window")
        t = time.perf_counter()
        got, accept, modeled, _ = engine.step(emb[start + n])
        t1 = time.perf_counter()
        lat[n], gap[n], acc[n], ids[n] = t1 - t, t - prev, accept, got
        model[n] = modeled
        prev = t1
        n += 1
        if t1 - t0 >= seconds:
            break
    wall = t1 - t0
    rejected = int(n - acc[:n].sum())
    lat = lat[:n]
    return Window(
        rows=start + np.arange(n), n=n, wall_s=wall, served=ids[:n],
        accepts=acc[:n],
        exact_rows=np.flatnonzero(~acc[:n]), cloud=~acc[:n],
        spec_calls=n, spec_rows=n, scan_calls=rejected, scan_rows=rejected,
        e2e={"latency_mean_ms": 1e3 * wall / n,
             "latency_p95_ms": 1e3 * float(np.percentile(lat, 95))},
        lateness_s=gap[:n],
        detail={"latency_p50_ms": 1e3 * float(np.median(lat)),
                "latency_max_ms": 1e3 * float(lat.max()),
                "over_100ms": int((lat > 0.1).sum()),
                "over_100ms_s": float(lat[lat > 0.1].sum())},
        modeled={"step_latency_mean_s": float(model[:n].mean())})


def final_state(engine):
    return engine.state


def lifetimes(probe, warm: Warm, win: Window, emb) -> list[Lifetime]:
    """One cache for the whole run: set-up's bulk fold, then every
    rejected request's ingest, in stream order."""
    kept = probe.kept("ingest")
    d, k = emb.shape[1], warm.ids.shape[1]
    return [Lifetime(rows=np.arange(len(warm.rows) + win.n),
                     ingest_q=stack_rows([q for q, _ in kept], d, np.float32),
                     ingest_ids=stack_rows([i for _, i in kept], k, np.int32),
                     preloaded=warm.preloaded)]
