"""Run the HaS serving path once on a TPU and check what it serves.

    python chip_smoke.py              # one chip: the whole serving path
    python chip_smoke.py --chips 4    # four chips: the mesh-sharded cloud scan

One chip: a seeded 1,000,000-passage corpus at d=768 (200,000 entities x 5
passages) goes through the objects ``launch/serve.py`` builds — the
synthetic world, a ``RetrievalService`` over the exact flat scan, and the
continuous-batching scheduler with its Pallas speculation pipeline — and
the scheduler serves an open-loop granola stream.  Two plain references
then check the results:

* the ids of the full-channel requests equal a host-numpy exact top-k over
  the same corpus (float64), except where score gaps are within 1e-5;
* one speculation batch on the final cache state gives the same accept
  flags and draft ids through the Pallas kernels and through the XLA
  oracle, except where two candidates' exact scores are within 1e-2.

Four chips: the corpus is placed row-wise over a (1, 4) ("data", "model")
mesh by the CLI's sharded backend; its ids are checked against the
single-device ``LocalFlatBackend`` and the host reference, and the
scheduler serves a short stream through it.

Times marked "host" are wall clock; the scheduler's latencies come from its
virtual clock and are marked "modeled".  The script runs in one process,
fails when JAX finds no TPU, and prints one JSON object as its last line.
Compiled programs persist in ``$JAX_COMPILATION_CACHE_DIR`` or, where that
is unset, in ``.jax_cache`` next to this file.
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ENTITIES = 200_000            # x 5 passages = 1,000,000 passages
DIM = 768                     # the paper's encoder width
FULL_TOL = 1e-5               # exact scan vs float64 host scores
SPEC_TOL = 1e-2               # Pallas vs XLA speculation: near-tie band
SPEC_CHECK_BATCH = 8          # the XLA oracle's temporaries grow with B


class CheckFailed(Exception):
    pass


def require_tpu(n: int):
    """The first ``n`` devices, which must be TPUs; never a CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise CheckFailed(f"needs a TPU; JAX found platform "
                          f"{devices[0].platform!r}")
    if len(devices) < n:
        raise CheckFailed(f"needs {n} TPU devices, JAX found {len(devices)}")
    return devices[:n]


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class CompileMeter:
    """Backend compile time and persistent-cache hits, from JAX's events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits


def hbm(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_limit")}


def gb(n) -> str:
    return "n/a" if n is None else f"{n / 1e9:.3f}GB"


def host_peak_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


def exact_scores(doc_emb: np.ndarray, q: np.ndarray,
                 chunk: int = 65536) -> np.ndarray:
    """Host float64 scores ``q @ doc_emb.T`` -> [m, N], in row chunks."""
    q = np.asarray(q, np.float64)
    out = np.empty((q.shape[0], doc_emb.shape[0]))
    for lo in range(0, doc_emb.shape[0], chunk):
        out[:, lo:lo + chunk] = q @ doc_emb[lo:lo + chunk].astype(
            np.float64).T
    return out


def check_exact_topk(ids: np.ndarray, scores: np.ndarray, k: int,
                     tol: float) -> int:
    """Each row of ``ids`` must be an exact top-k under ``scores`` up to
    ``tol``: k distinct ids, none below the k-th best score minus tol, in
    descending order within tol.  Returns how many rows match the
    reference's id list exactly."""
    exact = 0
    for r in range(ids.shape[0]):
        row = ids[r]
        ref = np.argsort(-scores[r], kind="stable")[:k]
        kth = scores[r, ref[-1]]
        if len(set(row.tolist())) != k or (row < 0).any():
            raise CheckFailed(f"row {r}: ids {row.tolist()} are not k "
                              "distinct documents")
        got = scores[r, row]
        if (got < kth - tol).any() or (np.diff(got) > tol).any():
            raise CheckFailed(
                f"row {r}: served {row.tolist()} scores {got.tolist()} vs "
                f"reference {ref.tolist()} (k-th {kth})")
        exact += int(np.array_equal(row, ref))
    return exact


def check_speculation(cfg, state, index, q: np.ndarray, doc_emb) -> dict:
    """Pallas vs XLA ``speculate_batch`` on one batch: equal accept flags
    and draft ids, except where candidates tie within ``SPEC_TOL``."""
    import jax.numpy as jnp

    from repro.core.has import speculate_batch
    qj = jnp.asarray(q)
    outs = {be: {key: np.asarray(v) for key, v in speculate_batch(
        cfg, state, index, qj, backend=be).items()}
        for be in ("pallas", "xla")}
    p, x = outs["pallas"], outs["xla"]
    ties, gap = 0, 0.0
    for field in ("draft_ids", "val_ids"):
        for r, (a, b) in enumerate(zip(p[field], x[field])):
            for j in np.flatnonzero(a != b):
                if a[j] < 0 or b[j] < 0:
                    raise CheckFailed(f"{field} row {r} slot {j}: "
                                      f"{a[j]} vs {b[j]}")
                g = abs(float(doc_emb[a[j]].astype(np.float64) @ q[r])
                        - float(doc_emb[b[j]].astype(np.float64) @ q[r]))
                gap = max(gap, g)
                if g > SPEC_TOL:
                    raise CheckFailed(
                        f"{field} row {r} slot {j}: {a[j]} vs {b[j]}, "
                        f"exact score gap {g}")
                ties += field == "draft_ids"
    same_val = (p["val_ids"] == x["val_ids"]).all(axis=1)
    if (p["accept"] != x["accept"])[same_val].any():
        raise CheckFailed(f"accept flags differ: {p['accept'].tolist()} vs "
                          f"{x['accept'].tolist()}")
    return dict(rows=len(q), accepts=int(p["accept"].sum()),
                accept_equal=bool((p["accept"] == x["accept"]).all()),
                draft_tie_slots=ties, max_tie_gap=gap)


def serve_args(extra: list[str], entities: int, queries: int, qps: float):
    from repro.launch import serve
    return serve.parse_args(
        ["--engine", "sched", "--dataset", "granola", "--entities",
         str(entities), "--dim", str(DIM), "--queries", str(queries),
         "--qps", str(qps), "--seed", "0", *extra])


def build_world(args):
    from repro.launch import serve
    t = time.perf_counter()
    world = serve.build_world(args)
    log("world", passages=world.cfg.n_docs, d=world.cfg.d,
        host_s=f"{time.perf_counter() - t:.1f}",
        host_peak=f"{host_peak_gb():.2f}GB")
    return world


def serve_stream(args, world, engine, meter):
    from repro.core import dispatch
    from repro.launch import serve
    from repro.serving.scheduler import poisson_arrivals
    queries, _ = serve.build_stream(args, world)
    arrivals = poisson_arrivals(len(queries), qps=args.qps,
                                seed=args.seed + 3)
    dispatch.reset()
    c0 = meter.snapshot()
    t = time.perf_counter()
    result = engine.serve(queries, arrivals, dataset=args.dataset,
                          seed=args.seed)
    wall = time.perf_counter() - t
    c1 = meter.snapshot()
    s = result.summary()
    chans, counts = np.unique(result.channels, return_counts=True)
    log("serve", requests=len(queries), qps=args.qps,
        host_wall_s=f"{wall:.2f}", compiles_in_window=c1[1] - c0[1],
        dar=f"{s['dar']:.4f}", doc_hit=f"{s['doc_hit_rate']:.4f}")
    log("serve", channels=dict(zip(chans.tolist(), counts.tolist())),
        spec_batches=result.spec_batches, full_batches=result.full_batches)
    log("serve", dispatches=dispatch.counts())
    log("serve", modeled_p50_s=f"{s['p50_latency_s']:.4f}",
        modeled_p99_s=f"{s['p99_latency_s']:.4f}",
        modeled_throughput_qps=f"{s['throughput_qps']:.2f}")
    return queries, result


def check_full_channel(world, queries, result, k: int) -> None:
    full = np.flatnonzero(result.channels == "full")
    if len(full) < 16:
        raise CheckFailed(f"only {len(full)} full-channel requests; the "
                          "reference check needs 16")
    q = np.stack([queries[i]["emb"] for i in full])
    scores = exact_scores(world.doc_emb, q)
    exact = check_exact_topk(result.served_ids[full], scores, k, FULL_TOL)
    log("check", full_channel_requests=len(full),
        ids_equal_reference=exact, within_tol=len(full) - exact,
        tol=FULL_TOL, ok=True)


def one_chip(entities: int, queries: int, qps: float) -> None:
    import jax

    from repro.core.has import _speculate_batch_impl, default_backend
    from repro.kernels.ops import auto_interpret
    from repro.launch import serve
    from repro.utils import use_compile_cache

    device = require_tpu(1)[0]
    log("device", platform=device.platform, kind=repr(device.device_kind),
        count=len(jax.devices()), host_peak=f"{host_peak_gb():.2f}GB")
    log("cache", dir=use_compile_cache())
    meter = CompileMeter()
    args = serve_args([], entities, queries, qps)
    world = build_world(args)

    c0, t = meter.snapshot(), time.perf_counter()
    svc = serve.build_service(args, world)
    engine = serve.build_engine(args, svc)
    c1 = meter.snapshot()
    mem = hbm(device)
    log("load", host_s=f"{time.perf_counter() - t:.1f}",
        compile_s=f"{c1[0] - c0[0]:.1f}", compiles=c1[1] - c0[1],
        persistent_cache_hits=c1[2] - c0[2])
    log("hbm", resident=gb(mem["bytes_in_use"]),
        limit=gb(mem["bytes_limit"]),
        corpus=gb(svc.corpus.nbytes),
        ivf=gb(engine.index.bucket_vecs.nbytes),
        state=gb(sum(a.nbytes for a in jax.tree.leaves(engine.state))))
    log("ivf", bucket_vecs_format=engine.index.bucket_vecs.format)

    # the chip path, not a fallback: Pallas kernels, compiled by Mosaic
    if default_backend() != "pallas" or engine.spec_backend != "pallas":
        raise CheckFailed(f"speculation took {engine.spec_backend!r}")
    if auto_interpret():
        raise CheckFailed("Pallas kernels would run in interpret mode")
    cfg, sc = engine.cfg, engine.sched
    compiled = _speculate_batch_impl.lower(
        cfg, engine.state, engine.index,
        np.zeros((sc.max_spec_batch, cfg.d), np.float32), backend="pallas",
        interpret=False, tile_c=1024).compile()
    n_custom = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    if n_custom < 3:
        raise CheckFailed(f"speculation program holds {n_custom} "
                          "tpu_custom_call(s), expected 3")
    log("pallas", backend=engine.spec_backend, interpret=False,
        tpu_custom_calls=n_custom,
        temp=gb(compiled.memory_analysis().temp_size_in_bytes))

    queries_, result = serve_stream(args, world, engine, meter)
    check_full_channel(world, queries_, result, cfg.k)
    q = np.stack([queries_[i]["emb"] for i in range(SPEC_CHECK_BATCH)])
    spec = check_speculation(cfg, engine.state, engine.index, q,
                             world.doc_emb)
    log("check", speculation_pallas_vs_xla=spec, ok=True)
    mem = hbm(device)
    log("hbm", peak=gb(mem["peak_bytes_in_use"]),
        in_use=gb(mem["bytes_in_use"]), host_peak=f"{host_peak_gb():.2f}GB")


def four_chips(entities: int, queries: int, qps: float) -> None:
    import jax.numpy as jnp

    from repro.launch import serve
    from repro.retrieval.service import LocalFlatBackend
    from repro.utils import use_compile_cache

    devices = require_tpu(4)
    log("device", platform=devices[0].platform,
        kind=repr(devices[0].device_kind), count=len(devices),
        host_peak=f"{host_peak_gb():.2f}GB")
    log("cache", dir=use_compile_cache())
    meter = CompileMeter()
    args = serve_args(["--retrieval-backend", "sharded", "--shards", "4",
                       "--workers", "2"], entities, queries, qps)
    world = build_world(args)
    t = time.perf_counter()
    svc = serve.build_service(args, world)
    backend = svc.backend
    if backend.mesh is None:
        raise CheckFailed("the sharded backend fell back to one device")
    shards = backend.corpus.addressable_shards
    rows = sorted({s.data.shape[0] for s in shards})
    spread = {str(s.device): s.data.shape[0] for s in shards}
    n = world.cfg.n_docs
    if len({s.device for s in shards}) != 4 or rows != [n // 4]:
        raise CheckFailed(f"corpus placement {spread}")
    log("mesh", shape=dict(backend.mesh.shape), rows_per_device=spread,
        host_s=f"{time.perf_counter() - t:.1f}")

    # sharded ids vs the single-device flat scan and the host reference
    qs, _ = serve.build_stream(args, world)
    q = np.stack([x["emb"] for x in qs[:64]])
    local = LocalFlatBackend(svc.corpus, args.k, svc.latency)
    _, ids_mesh = backend.search(jnp.asarray(q))
    _, ids_local = local.search(jnp.asarray(q))
    ids_mesh, ids_local = np.asarray(ids_mesh), np.asarray(ids_local)
    scores = exact_scores(world.doc_emb, q)
    exact = check_exact_topk(ids_mesh, scores, args.k, FULL_TOL)
    check_exact_topk(ids_local, scores, args.k, FULL_TOL)
    same = int((ids_mesh == ids_local).all(axis=1).sum())
    log("check", queries=len(q), mesh_ids_equal_local=same,
        mesh_ids_equal_reference=exact, tol=FULL_TOL, ok=True)

    engine = serve.build_engine(args, svc)
    log("hbm", **{str(dev): gb(hbm(dev)["bytes_in_use"]) for dev in devices})
    queries_, result = serve_stream(args, world, engine, meter)
    check_full_channel(world, queries_, result, args.k)
    log("hbm", peak={str(dev): gb(hbm(dev)["peak_bytes_in_use"])
                     for dev in devices})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    opts = ap.parse_args(argv)
    import jax
    try:
        if opts.chips == 1:
            one_chip(ENTITIES, queries=300, qps=20.0)
        else:
            four_chips(ENTITIES, queries=96, qps=20.0)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
