"""The program's own spans on the device trace's clock
(``chipbench/program_spans.py``): nesting, idle time credited to the
innermost span, programs counted inside ``has.step``, the clock offset
taken from the Probe's spans, and the traced run's two metrics."""
from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest

import chipbench_tiny
from chipbench import program_spans, tracing
from chipbench_tiny import tiny  # noqa: F401  (the fixture)

MS = 1_000_000  # ns
PLANE = "/device:TPU:0"
LAG = 1.5 * MS   # the device clock runs this far behind the host's


def test_segments_name_the_innermost_span():
    spans = [(10, 50, "has.step"), (10, 20, "has.spec"),
             (30, 40, "has.scan"), (32, 35, "has.gather"),
             (60, 70, "has.step")]
    assert program_spans.segments(spans, 0, 80) == [
        (0, 10, "none"), (10, 20, "has.spec"), (20, 30, "has.step"),
        (30, 32, "has.scan"), (32, 35, "has.gather"), (35, 40, "has.scan"),
        (40, 50, "has.step"), (50, 60, "none"), (60, 70, "has.step"),
        (70, 80, "none")]
    # spans over the window's edges are cut at them
    assert program_spans.segments([(-5, 5, "has.step")], 0, 10) == [
        (0, 5, "has.step"), (5, 10, "none")]


def _window():
    """One accepted and one rejected request: Probe spans inside program
    spans, programs (host clock) inside the Probe spans with equal margins,
    eager programs in the program spans between them, one in the loop."""
    probe = [("window", 0, 100 * MS),
             ("spec", 8.5 * MS, 19.5 * MS), ("spec", 38.5 * MS, 49.5 * MS),
             ("cloud_scan", 54.5 * MS, 68.5 * MS),
             ("ingest", 74.5 * MS, 77.5 * MS)]
    program = [
        ("has.step", 5 * MS, 30 * MS, 0),
        ("has.upload", 6 * MS, 7 * MS, None),
        ("has.spec", 8 * MS, 20 * MS, None),
        ("has.readback", 21 * MS, 24 * MS, None),
        ("has.step", 35 * MS, 80 * MS, 1),
        ("has.upload", 36 * MS, 37 * MS, None),
        ("has.spec", 38 * MS, 50 * MS, None),
        ("has.readback", 51 * MS, 53 * MS, None),
        ("has.scan", 54 * MS, 70 * MS, None),
        ("has.gather", 71 * MS, 73 * MS, None),
        ("has.ingest", 74 * MS, 78 * MS, None),
        ("has.replicate", 78.5 * MS, 79 * MS, None)]
    on_host = [(9, 14), (14.5, 19),            # speculation, two programs
               (6.2, 6.4), (21.5, 21.6), (22, 22.1),   # upload, readback
               (39, 49),
               (36.5, 36.6), (51.5, 51.6),
               (55, 68),                        # the scan
               (71.5, 72),                      # gather
               (75, 77),                        # ingest
               (90, 91)]                        # the harness loop's own
    device = {PLANE: [("prog", s * MS - LAG, e * MS - LAG)
                      for s, e in on_host]}
    return probe, program, device


def test_reduce_program_on_the_probe_clock():
    probe, program, device = _window()
    red = tracing.reduce_events(device, probe)
    assert red.offsets_ms[PLANE] == pytest.approx(-1.5, abs=2e-3)
    pt = program_spans.reduce_program(device, program + [
        ("window", 0, 100 * MS, None)], red.offsets_ms)
    assert pt.requests == 2 and pt.req_missing == 0
    assert pt.programs == 11                     # all but the loop's own
    assert pt.phase_programs == {
        "has.spec": 3, "has.upload": 2, "has.readback": 3, "has.scan": 1,
        "has.gather": 1, "has.ingest": 1, "none": 1}
    busy = 9.5 + 0.2 + 0.1 + 0.1 + 10 + 0.1 + 0.1 + 13 + 0.5 + 2 + 1
    assert pt.inside_step == pytest.approx((busy - 1) / busy)
    # each device second of the Probe's spans is in the program's span
    assert pt.device_s["has.spec"] == pytest.approx(red.span_device_s["spec"])
    assert pt.device_s["has.scan"] == pytest.approx(
        red.span_device_s["cloud_scan"])
    assert pt.device_s["has.ingest"] == pytest.approx(
        red.span_device_s["ingest"])
    ms = {k: v * 1e3 for k, v in pt.host_phases.items()}
    want = {"has.upload": 1 - 0.2 + 1 - 0.1,
            "has.spec": 12 - 9.5 + 12 - 10,
            "has.readback": 3 - 0.2 + 2 - 0.1,
            "has.scan": 16 - 13, "has.gather": 2 - 0.5,
            "has.ingest": 4 - 2, "has.replicate": 0.5,
            # the step's own time between its children
            "has.step": (1 + 1 + 1 + 6) + (1 + 1 + 1 + 1 + 1 + 1 + 0.5 + 1),
            "none": 5 + 5 + 20 - 1}
    assert ms == pytest.approx(want)
    assert sum(ms.values()) == pytest.approx(100 - busy)
    assert pt.step_host_ms == pytest.approx((sum(want.values()) - want[
        "none"]) / 2)
    assert pt.phases["has.spec"] == (2, pytest.approx(24), pytest.approx(12))


def test_program_spans_leave_the_reduction_as_it_was():
    probe, program, device = _window()
    plain = tracing.reduce_events(device, probe)
    both = tracing.reduce_events(device, probe + [e[:3] for e in program])
    assert both == plain


def test_no_program_spans_no_reading():
    probe, program, device = _window()
    host = [e + (None,) for e in probe]
    assert program_spans.reduce_program(device, host, {PLANE: -1.5}) is None
    assert program_spans.reduce_program({}, program + [
        ("window", 0, 100 * MS, None)], {}) is None


@pytest.fixture(scope="module")
def engine():
    from repro.core.has import HasConfig
    from repro.data.synthetic import SyntheticWorld, WorldConfig
    from repro.serving.engine import HasEngine, RetrievalService
    from repro.serving.latency import LatencyModel
    world = SyntheticWorld(WorldConfig(n_entities=120, seed=0))
    svc = RetrievalService(world, LatencyModel(), k=10, chunk=256)
    return HasEngine(svc, HasConfig(k=10, tau=0.2, h_max=32, nprobe=4,
                                    n_buckets=16, d=world.cfg.d))


ACCEPTED = ["has.step", "has.upload", "has.spec", "has.readback"]
REJECTED = ACCEPTED + ["has.scan", "has.gather", "has.ingest",
                       "has.replicate"]


def test_step_spans_in_a_cpu_trace(engine, tmp_path):
    """A rejected request and the same query again, accepted: each shows
    its phases nested in one ``has.step`` with its own ``req``."""
    q = np.asarray(engine.s.world.doc_emb[7])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("window"):
            first = engine.step(q)
            second = engine.step(q)
    finally:
        jax.profiler.stop_trace()
    assert (first[1], second[1]) == (False, True)
    path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    from jax.profiler import ProfileData
    host = sorted(program_spans.host_events(ProfileData.from_file(path)),
                  key=lambda e: (e[1], -e[2]))
    steps = [e for e in host if e[0] == "has.step"]
    assert [r for *_, r in steps] == [engine.n_steps - 2, engine.n_steps - 1]
    for (_, s0, e0, _), want in zip(steps, (REJECTED, ACCEPTED)):
        inside = [n for n, s, e, _ in host if s0 <= s and e <= e0]
        assert inside == want
    # a CPU trace has no device plane: nothing to read, and no error
    assert program_spans.reduce_file(path, {}) is None


def test_traced_run_reads_program_span_metrics(tiny, tmp_path, monkeypatch):
    """A traced run reports ``step_host_ms`` and ``programs_per_request``
    from the program's spans in the recorded window.  The CPU has no device
    plane, so one is stood in: a program inside each ``has.spec``,
    ``has.scan`` and ``has.ingest`` of the real trace."""
    from chipbench import run
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))

    def fake_reduce(path, span_names):
        from jax.profiler import ProfileData
        w, = [e - s for n, s, e, _ in program_spans.host_events(
            ProfileData.from_file(path)) if n == "window"]
        return tracing.Reduced(
            window_s=w * 1e-9, busy_s=0.3,
            span_device_s={"spec": 0.1, "cloud_scan": 0.15, "ingest": 0.02},
            span_count={}, device_ops=[("fusion", 0.2)],
            idle_gaps=[("none", 0.2)], n_devices=1,
            offsets_ms={PLANE: 0.25})
    monkeypatch.setattr(tracing, "reduce_file", fake_reduce)
    seen = {}
    events_from_profile = tracing.events_from_profile

    def with_device(profile, span_names):
        device, host, ops, lines = events_from_profile(profile, span_names)
        launched = [(s, e) for n, s, e, _ in program_spans.host_events(
            profile) if n in ("has.spec", "has.scan", "has.ingest")]
        seen["launched"] = len(launched)
        device[PLANE] = [("prog", s + 0.25 * MS + 0.1 * (e - s),
                          e + 0.25 * MS - 0.1 * (e - s)) for s, e in launched]
        return device, host, ops, lines
    monkeypatch.setattr(tracing, "events_from_profile", with_device)
    bench = chipbench_tiny.tiny_bench("seq")
    bench["per_layer"] += [
        {"name": f"{m}.seq", "unit": u, "moves": "latency_mean_ms",
         "workloads": ["tiny.seq"]}
        for m, u in (("step_host_ms", "ms"),
                     ("programs_per_request", "programs"))]
    monkeypatch.setattr(chipbench_tiny, "tiny_bench", lambda path: bench)
    res = tiny("seq", trace=True)
    m = res["metrics"]
    assert set(m) == {"dar.seq", "spec_roofline.seq", "scan_roofline.seq",
                      "idle_share.seq", "step_host_ms.seq",
                      "programs_per_request.seq"}
    n, rejected = res["attempted"], res["attempted"] * (
        1 - m["dar.seq"]["value"] / 100)
    assert seen["launched"] == pytest.approx(n + 2 * rejected)
    assert m["programs_per_request.seq"]["value"] == pytest.approx(
        seen["launched"] / n)
    # the host time of the window, less the stood-in programs
    assert 0 < m["step_host_ms.seq"]["value"] * n < 1e3 * 0.6
    assert res["correct"]


def _ctx(window_s):
    import types
    return types.SimpleNamespace(trace=tracing.Reduced(
        window_s=window_s, busy_s=0.0, span_device_s={}, span_count={},
        device_ops=[], idle_gaps=[], n_devices=1, offsets_ms={PLANE: 0.0}))


@pytest.mark.parametrize("window_ms, own", [(100.0, True), (100.001, False)])
def test_read_takes_only_the_runs_own_window(tmp_path, monkeypatch,
                                             capsys, window_ms, own):
    """``read`` reduces the newest window under the trace directory once a
    run, and only if it is as long as the run's: another run's window left
    there gives nothing."""
    from chipbench import run
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(tracing, "latest_xplane", lambda d: d)
    probe, program, device = _window()
    calls = []

    def fake_file(path, offsets_ms):
        calls.append(path)
        return program_spans.reduce_program(
            device, program + [("window", 0, 100 * MS, None)], offsets_ms)
    monkeypatch.setattr(program_spans, "reduce_file", fake_file)
    ctx = _ctx(window_ms * 1e-3)
    got = [program_spans.read(ctx) for _ in range(2)]
    assert calls == [str(tmp_path)]
    assert got[0] is got[1]
    assert (got[0] is not None) == own
    logged = capsys.readouterr().err
    assert logged.count("[phases]") == own
    if own:
        assert got[0].requests == 2
