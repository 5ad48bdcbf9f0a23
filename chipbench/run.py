"""The HaS chip benchmark: one cell, one run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) is a deployment configuration
(``configs/*.json``) under a traffic mix (``traffic/*.json``), whose
``path`` names the driver module of its serving path (``drivers/``): the
engine it builds, the attributes its loop calls, how it warms up and
serves, and what each cache lifetime took in.  The run makes the corpus
and the query stream on the device from ``--seed``, builds the engine
through ``launch/serve.py``'s builders, warms it up (all of which is
``setup_s``), then serves for ``--seconds`` on the host clock.
``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` records
the window with the profiler and prints its per-layer metrics.  Every run
then compares what the window served with the plain reference
(``check.py``) and prints each compared number beside its limit, on the
last lines of stderr and under ``checks`` in the result.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``busy_s`` and ``window_s`` when
traced), ``breakdown`` (traced) and ``checks``.  The run fails, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.
Compiled programs persist in ``chipbench/.jax_cache``.

``--control`` runs the comparison with the control in the program's place:
the reference's own answers at the precisions below the configuration's
stand where the served answers stood, and ``correct`` has to come out
false.  The benchmark's own runs never pass it.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, ".jax_cache")
TRACE_DIR = os.path.join(HERE, ".traces")
SPEC_SAMPLE = 64          # speculations compared on the final cache
MAX_SCAN_ROWS = 8192      # exact-scan answers compared (seeded sample)
WARM_SAMPLE = 512         # set-up's bulk-folded answers compared (sample)
STATE_FIELDS = ("query_emb", "query_doc_ids", "query_valid", "q_ptr",
                "doc_emb", "doc_ids", "d_ptr")


class NoChip(RuntimeError):
    pass


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          file=sys.stderr, flush=True)


def require_chips(n: int):
    """The first ``n`` devices, which must be TPUs; never a CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"needs {n} TPU chips, JAX found {len(devices)}")
    return devices[:n]


def use_cache() -> None:
    """Persist every compiled program in the checkout's fixed cache."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileMeter:
    """Backend compiles, their seconds and persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.hits


def _hbm(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_limit")}


def _check_build(engine, config: dict) -> None:
    """The program must run the configuration as its file states it."""
    has, cfg = config["has"], engine.cfg
    got = {"k": cfg.k, "tau": cfg.tau, "h_max": cfg.h_max,
           "doc_capacity": cfg.doc_cap, "nprobe": cfg.nprobe,
           "n_buckets": engine.index.n_buckets,
           "bucket_capacity": engine.index.capacity}
    bad = {k: (v, has[k]) for k, v in got.items() if v != has[k]}
    if bad or cfg.d != config["d"]:
        raise ValueError(f"program built {bad or cfg.d}, configuration "
                         f"states {has}, d={config['d']}")


def _speculate_sample(engine, q, batch: int, backend) -> dict:
    """The timed speculation program, at the window's batch size, on the
    cache state the window left."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.has import speculate_batch
    out = {key: [] for key in ("accept", "homology", "val_ids", "draft_ids")}
    for lo in range(0, len(q), batch):
        block = np.zeros((batch, q.shape[1]), np.float32)
        m = min(batch, len(q) - lo)
        block[:m] = q[lo:lo + m]
        res = speculate_batch(engine.cfg, engine.state, engine.index,
                              jnp.asarray(block), backend=backend)
        for key in out:
            out[key].append(np.asarray(res[key])[:m])
    return {key: np.concatenate(v) for key, v in out.items()}


def _window_spec(lives, rows: np.ndarray, k: int) -> dict | None:
    """The recorded speculation of the served table's ``rows`` (None where
    the path records none), gathered from the lifetimes that hold them."""
    import numpy as np
    if any(life.spec is None for life in lives):
        return None
    n = int(max(int(life.rows.max()) for life in lives)) + 1
    tab = {"val_ids": np.full((n, k), -1, np.int32),
           "draft_ids": np.full((n, k), -1, np.int32),
           "accept": np.zeros(n, bool), "seen": np.zeros(n, np.int32)}
    for life in lives:
        for key in tab:
            tab[key][life.rows] = life.spec[key]
    out = {key: v[rows] for key, v in tab.items()}
    out["stray"] = sum(life.spec["stray"] for life in lives)
    return out


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        control: bool = False, *, bench=None, config=None, traffic=None,
        peaks=None, chips_required: bool = True, cache: bool = True,
        trace_dir: str = TRACE_DIR, t0: float = T0,
        driver_dir: str | None = None) -> dict:
    """One run of one cell; returns the result object."""
    import jax
    import numpy as np

    from chipbench import check, drivers, reference, spans, spec, tracing
    from chipbench import work, world

    bench = bench or spec.load_benchmark()
    cell = spec.cell(bench, cell_name)
    config = config or spec.config(bench, cell)
    traffic = traffic or spec.traffic(cell["traffic"])
    devices = (require_chips(cell["chips"]) if chips_required
               else jax.devices()[:cell["chips"]])
    device = devices[0]
    log("device", platform=device.platform, kind=repr(device.device_kind),
        count=len(devices))
    peaks = peaks or work.peaks_for(device.device_kind)
    if cache:
        use_cache()
    meter = CompileMeter()
    drv = drivers.load(traffic["path"], driver_dir or drivers.HERE)

    # -- set-up: world, program, warm-up -----------------------------------
    t = time.perf_counter()
    shape = world.WorldShape(config["n_entities"], config["docs_per_entity"],
                             config["attrs_per_entity"],
                             config["attrs_per_doc"], config["d"])
    tables = world.host_tables(shape, seed)
    ents, attrs = world.sample_stream(traffic["stream_requests"], traffic,
                                      tables[2], seed)
    w = world.World(config, seed, ents, attrs, tables)
    stream = types.SimpleNamespace(emb=w.query_emb, ents=ents, attrs=attrs)
    log("world", passages=shape.n_docs, d=shape.d, queries=len(ents),
        host_s=f"{time.perf_counter() - t:.2f}")

    from repro.launch import serve
    t = time.perf_counter()
    args = serve.parse_args(["--engine", drv.ENGINE, *config["builder_args"]])
    svc = serve.build_service(args, w)
    engine = serve.build_engine(args, svc)
    _check_build(engine, config)
    mem = _hbm(device)
    log("build", host_s=f"{time.perf_counter() - t:.2f}",
        resident_bytes=mem["bytes_in_use"], limit_bytes=mem["bytes_limit"])

    probe = spans.Probe(trace)
    drv.install(probe, engine)
    t = time.perf_counter()
    warm = drv.warm(engine, stream, traffic)
    start = int(warm.rows.max()) + 1
    log("warm", requests=len(warm.rows), preloaded=warm.preloaded,
        host_s=f"{time.perf_counter() - t:.2f}",
        dar=f"{warm.accepts[warm.preloaded:].mean():.4f}")
    cs = meter.snapshot()
    setup_s = time.perf_counter() - t0
    log("setup", setup_s=f"{setup_s:.3f}", compile_s=f"{cs[0]:.2f}",
        compiles=cs[1], persistent_cache_hits=cs[2], cache_dir=CACHE_DIR)

    # -- the measured window -----------------------------------------------
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # host spans on, Python function tracing off: it would slow the
        # serving loop's host code several times over
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    probe.start_window()
    with spans.window_span(trace):
        win = drv.window(engine, stream, start, seconds, traffic, seed)
    if trace:
        jax.profiler.stop_trace()
    cw = meter.snapshot()
    span_names = probe.span_names
    mem = _hbm(device)
    peak = mem["peak_bytes_in_use"]
    acc = win.accepts
    log("window", requests=win.n, wall_s=f"{win.wall_s:.4f}",
        dar=f"{acc.mean():.4f}", exact_scans=len(win.exact_rows),
        spec_calls=win.spec_calls, scan_calls=win.scan_calls,
        compiles_in_window=cw[1] - cs[1],
        span_calls=probe.calls, **win.detail)
    log("lateness", load=drv.LOAD,
        host_gap_mean_ms=f"{1e3 * win.lateness_s.mean():.4f}",
        host_gap_max_ms=f"{1e3 * win.lateness_s.max():.4f}")
    log("hbm", resident_bytes=mem["bytes_in_use"], peak_bytes=peak)
    log("modeled", note="virtual clock / latency model, not measured",
        **win.modeled)

    # -- speculation on the cache the window left; then free the program ---
    rng = np.random.default_rng([seed, 3])
    sample = np.sort(rng.choice(win.n, min(SPEC_SAMPLE, win.n),
                                replace=False))
    sample_q = stream.emb[win.rows[sample]]
    prog = _speculate_sample(engine, sample_q, drv.spec_batch(engine),
                             drv.spec_backend(engine))
    state = drv.final_state(engine)
    state_np = {f: np.asarray(getattr(state, f)) for f in STATE_FIELDS}
    centroids = np.asarray(engine.index.centroids)
    bucket_ids = np.asarray(engine.index.bucket_ids)
    vecs_wrong = reference.bucket_vecs_wrong(
        w.doc_emb, engine.index.bucket_vecs, engine.index.bucket_ids)
    k, d = config["has"]["k"], config["d"]
    # the served table: set-up's rows, then the window's
    table_emb = stream.emb[np.concatenate([warm.rows, win.rows])]
    table_ids = np.concatenate([warm.ids, win.served])
    table_cloud = np.concatenate([warm.cloud, win.cloud])
    lives = drv.lifetimes(probe, warm, win, table_emb)
    win_rows = len(warm.rows) + np.arange(win.n)
    spec_rec = _window_spec(lives, win_rows, k)
    scans = probe.kept("cloud_scan", window=True)
    scan_q = drivers.stack_rows([q for q, _ in scans], d, np.float32)
    scan_s = drivers.stack_rows([o[0] for _, o in scans], k, np.float32)
    scan_ids = drivers.stack_rows([o[1] for _, o in scans], k, np.int32)
    probe.uninstall()
    del engine, svc, state, probe
    gc.collect()

    # -- the reference -----------------------------------------------------
    t = time.perf_counter()
    has = config["has"]
    corpus = w.doc_emb
    corpus_np = np.asarray(corpus)
    numbers, cache = check.ingest_numbers(lives, table_emb, table_ids,
                                          table_cloud, state_np, corpus_np,
                                          has)
    del lives
    rows = win.exact_rows
    if len(rows) > MAX_SCAN_ROWS:
        rows = np.sort(rng.choice(rows, MAX_SCAN_ROWS, replace=False))
    # the answers folded into the cache in bulk in set-up: a seeded sample
    p = warm.preloaded
    pre = (np.sort(rng.choice(p, min(WARM_SAMPLE, p), replace=False))
           if p else np.zeros(0, int))
    numbers.update(check.scan_numbers(
        corpus, corpus_np,
        np.concatenate([table_emb[pre], stream.emb[win.rows[rows]]]),
        np.concatenate([table_ids[pre], win.served[rows]]), has["k"],
        control))
    numbers.update(check.score_numbers(corpus_np, scan_q, scan_s, scan_ids))
    numbers.update(check.spec_numbers(sample_q, prog, cache, corpus_np,
                                      centroids, bucket_ids, has,
                                      control=control))
    if spec_rec is not None:
        served_bad = check.draft_served_bad(win.drafted, win.served, spec_rec)
        numbers.update(draft_served_bad=served_bad,
                       accept_bad=numbers["accept_bad"] + served_bad)
    if win.leader is not None:
        exact = np.zeros(win.n, bool)
        exact[win.exact_rows] = True
        numbers.update(check.follow_numbers(
            table_emb[win_rows], win.served, win.leader, exact, spec_rec,
            win.share_tau, corpus_np, control))
    numbers.update(check.ivf_numbers(corpus, corpus_np, centroids,
                                     bucket_ids, vecs_wrong))
    if control:
        numbers = check.control_in_place(numbers)
    correct, shown = check.verdict(numbers, check.load_limits(config))
    log("reference", host_s=f"{time.perf_counter() - t:.2f}",
        **{name: v for name, v in numbers.items() if name not in shown})

    # -- metrics -----------------------------------------------------------
    hits = world.doc_hits(w, ents[win.rows], attrs[win.rows], win.served)
    failed = int((win.served < 0).all(axis=1).sum())
    names_e2e = [m for m in spec.end_to_end(bench, cell_name)]
    values = dict(win.e2e, doc_hit=float(hits.mean()), setup_s=setup_s)
    result = {"correct": bool(correct), "attempted": int(win.n),
              "failed": failed, "metrics": {}, "device": {
                  "platform": device.platform, "kind": device.device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}}
    if not trace:
        for m in names_e2e:
            if m["name"] not in values:
                raise KeyError(f"path {traffic['path']!r} measures no "
                               f"{m['name']!r}")
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        red = tracing.reduce_file(tracing.latest_xplane(trace_dir),
                                  span_names)
        result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        ctx = types.SimpleNamespace(
            requests=win.n, accepted=int(acc.sum()),
            spec_calls=win.spec_calls, spec_rows=win.spec_rows,
            scan_calls=win.scan_calls, scan_rows=win.scan_rows,
            trace=red, config=config, peaks=peaks)
        for m in spec.per_layer(bench, cell_name):
            v = spec.reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in red.device_ops],
            "idle_gaps": [[n, s] for n, s in red.idle_gaps]}
        log("trace", device_lines=red.lines, spans=red.span_count,
            span_device_s=red.span_device_s, busy_s=red.busy_s,
            window_s=red.window_s, clock_offset_ms=red.offsets_ms)
    if control:
        result["control"] = {k: v for k, v in numbers.items()
                             if k.startswith("control_")}
    for name, v in shown.items():
        print(f"[check] {name}={v['value']} limit={v['limit']}",
              file=sys.stderr, flush=True)
    result["checks"] = shown
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--control", action="store_true",
                    help="compare the control (the reference at lower "
                         "precisions in the served answers' place) "
                         "instead of the program: correct must be false")
    opts = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    try:
        result = run(opts.workload, opts.seed, opts.seconds,
                     bool(opts.trace), opts.control)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
