"""The whole harness on the ``sched`` path (``ContinuousBatchingScheduler``,
saturated segments) at a tiny size on the CPU: sound runs are correct; the
timed path broken underneath is not.  And a later path is one new module,
found by its traffic file's ``path`` alone."""
from __future__ import annotations

import os
import re

import numpy as np
import pytest

from chipbench import drivers
from chipbench_tiny import tiny  # noqa: F401  (the fixture)

SCHED_CHECKS = {"scan_gap", "score_err", "draft_gap", "accept_bad",
                "ingest_bad", "ivf_bad", "follow_bad", "follow_gap"}


@pytest.fixture
def sched_clock(monkeypatch):
    """The sched driver's clock advanced one second a reading, so that a
    window of ``seconds`` serves a fixed number of segments however fast
    the machine is: ``seconds=0`` one, ``seconds=5`` three (the stream's
    two, then the first again).  Every other caller keeps the real clock."""
    import sys
    import time

    real = time.perf_counter
    now = [0.0]

    def clock():
        if sys._getframe(1).f_globals.get("__name__") == \
                "chipbench_driver_sched":
            now[0] += 1.0
            return now[0]
        return real()
    monkeypatch.setattr(time, "perf_counter", clock)


@pytest.mark.parametrize("seed", [11, 2 ** 32 + 7])
def test_sound_sched_run_is_correct(tiny, sched_clock, seed):
    """Three segments in the window, the stream's two and the first again:
    four cache lifetimes with set-up's."""
    res = tiny("sched", seed=seed, seconds=5)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 3 * 320
    assert res["failed"] == 0
    m = res["metrics"]
    assert set(m) == {"qps", "doc_hit", "setup_s"}
    assert m["qps"]["value"] > 0 and 0 < m["doc_hit"]["value"] <= 1
    assert set(res["checks"]) == SCHED_CHECKS
    assert list(res)[-1] == "checks"


def test_sched_window_serves_every_class(tiny, sched_clock, capsys):
    """The tiny cell exercises all four channels, and the window compiles
    nothing."""
    tiny("sched", seconds=5)
    err = capsys.readouterr().err
    line = next(ln for ln in err.splitlines() if ln.startswith("[window]"))
    counts = dict(re.findall(r" (draft|reval|shared|full)=(\d+)", line))
    assert set(counts) == {"draft", "reval", "shared", "full"}
    assert all(int(v) > 0 for v in counts.values()), line
    assert "compiles_in_window=0" in line
    assert "other_channels=0" in line
    assert "segments=3" in line
    ref = next(ln for ln in err.splitlines() if ln.startswith("[reference]"))
    assert "lifetimes=4" in ref


def test_sched_traced_run_reads_per_layer_metrics(tiny, sched_clock,
                                                  monkeypatch):
    from chipbench import tracing

    seen = {}

    def fake_reduce(path, span_names):
        seen["spans"] = set(span_names)
        return tracing.Reduced(
            window_s=0.5, busy_s=0.2,
            span_device_s={"spec": 0.05, "cloud_scan": 0.1, "ingest": 0.01},
            span_count={}, device_ops=[("fusion", 0.1)],
            idle_gaps=[("none", 0.3)], n_devices=1)
    monkeypatch.setattr(tracing, "reduce_file", fake_reduce)
    res = tiny("sched", seconds=5, trace=True)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"dar.sched", "spec_roofline.sched",
                                   "scan_roofline.sched", "idle_share.sched"}
    assert res["metrics"]["idle_share.sched"]["value"] == pytest.approx(60.0)
    assert seen["spans"] == {"spec", "share", "reval", "cloud_scan", "ingest"}


@pytest.mark.parametrize("seed", [11, 2 ** 32 + 7])
def test_sched_control_reads_past_the_limits(tiny, sched_clock, seed):
    """The control (the reference one precision below the configuration's
    in the program's place, the followers re-ranked by one bfloat16 pass)
    comes out as not correct through the compared numbers."""
    res = tiny("sched", seed=seed, seconds=5, control=True)
    assert not res["correct"]
    checks = res["checks"]
    for name in ("score_err", "draft_gap", "follow_gap"):
        assert checks[name]["value"] > checks[name]["limit"], name


# -- the timed path broken underneath: correct must come out false --------

def _sched_serve(monkeypatch, wrap):
    from repro.serving import scheduler

    orig = scheduler.ContinuousBatchingScheduler.serve

    def serve(self, *a, **k):
        return wrap(self, orig, *a, **k)
    monkeypatch.setattr(scheduler.ContinuousBatchingScheduler, "serve",
                        serve)


def test_altered_follower_answer_is_caught(tiny, sched_clock, monkeypatch):
    """One id of each follower's served answer swapped for another
    document."""
    def wrap(self, orig, *a, **k):
        res = orig(self, *a, **k)
        f = res.channels == "shared"
        ids = res.served_ids
        ids[f, -1] = (ids[f, -1] + 1) % 2000
        return res
    _sched_serve(monkeypatch, wrap)
    res = tiny("sched", seconds=5)
    assert not res["correct"]
    assert res["checks"]["follow_bad"]["value"] > 0


def test_follower_shared_below_the_threshold_is_caught(tiny, sched_clock,
                                                       monkeypatch):
    """The sharing election run at a threshold below the stated one."""
    from repro.serving import scheduler

    orig = scheduler.intra_batch_share

    def low(val_ids, rejected, tau, *a, **k):
        return orig(val_ids, rejected, tau * 0.0, *a, **k)
    monkeypatch.setattr(scheduler, "intra_batch_share", low)
    res = tiny("sched", seconds=5)
    assert not res["correct"]
    assert res["checks"]["follow_bad"]["value"] > 0


@pytest.mark.parametrize("segment", ["first", "last"])
def test_leader_ingest_dropped_is_caught(tiny, sched_clock, monkeypatch,
                                         segment):
    """One leader's row left out of the cache ingest, underneath the
    recorded call: in the set-up segment's cache, or in the last one's."""
    from repro.serving import scheduler

    orig = scheduler.cache_update_chunked
    calls = {"n": 0}
    target = 0 if segment == "first" else None

    def drop(cfg, state, q_embs, full_ids, *a, **k):
        calls["n"] += 1
        if target is None or calls["n"] == 1 + target:
            q_embs, full_ids = q_embs[1:], full_ids[1:]
        return orig(cfg, state, q_embs, full_ids, *a, **k)
    monkeypatch.setattr(scheduler, "cache_update_chunked", drop)
    res = tiny("sched", seconds=5)
    assert not res["correct"]
    assert res["checks"]["ingest_bad"]["value"] > 0


def test_leader_ingest_left_out_of_the_call_is_caught(tiny, sched_clock,
                                                      monkeypatch):
    """One leader's row left out above the recorded call: the cache never
    sees the answer it served."""
    from repro.serving import scheduler

    orig = scheduler.ContinuousBatchingScheduler._ingest

    def ingest(self, batch, *a, **k):
        if batch and batch[0].followers == [] and len(batch) > 1:
            batch = batch[1:]
        return orig(self, batch, *a, **k)
    monkeypatch.setattr(scheduler.ContinuousBatchingScheduler, "_ingest",
                        ingest)
    res = tiny("sched", seconds=5)
    assert not res["correct"]
    assert res["checks"]["ingest_bad"]["value"] > 0


def test_cache_not_reset_between_segments_is_caught(tiny, sched_clock,
                                                    monkeypatch):
    """Each ``serve`` keeps the cache the last one left."""
    def wrap(self, orig, *a, **k):
        keep = self.state
        self._init_state = lambda: keep
        try:
            return orig(self, *a, **k)
        finally:
            del self._init_state
    _sched_serve(monkeypatch, wrap)
    res = tiny("sched", seconds=5)
    assert not res["correct"]
    assert res["checks"]["ingest_bad"]["value"] > 0


def test_flipped_accept_flags_are_caught(tiny, sched_clock, monkeypatch):
    from repro.core import has
    from repro.serving import scheduler

    orig = has.speculate_batch

    def flipped(*a, **k):
        out = dict(orig(*a, **k))
        out["accept"] = ~out["accept"]
        return out
    monkeypatch.setattr(has, "speculate_batch", flipped)
    monkeypatch.setattr(scheduler, "speculate_batch", flipped)
    res = tiny("sched", seconds=5)
    assert not res["correct"]
    assert res["checks"]["accept_bad"]["value"] > 0


def test_altered_draft_answer_is_caught(tiny, sched_clock, monkeypatch):
    """A draft served that is not the one speculation returned."""
    def wrap(self, orig, *a, **k):
        res = orig(self, *a, **k)
        d = res.channels == "draft"
        res.served_ids[d, 0] = (res.served_ids[d, 0] + 1) % 2000
        return res
    _sched_serve(monkeypatch, wrap)
    res = tiny("sched", seconds=5)
    assert not res["correct"]
    assert res["checks"]["accept_bad"]["value"] > 0


# -- a later path needs no edit to an existing file ------------------------

STUB = '''"""A stub path: the seq loop under a driver module of its own."""
import os

from chipbench.drivers.seq import *  # noqa: F401,F403
from chipbench.drivers import seq as _seq


def install(probe, engine):
    open(os.path.join(os.path.dirname(__file__), "installed"), "w").close()
    _seq.install(probe, engine)
'''


def test_stub_path_is_found_by_its_traffic_file(tiny, tmp_path):
    """A driver module in a directory of its own, named by the traffic
    file's ``path`` alone, is loaded and installed, and the run is
    correct."""
    here = tmp_path / "drivers"
    here.mkdir()
    (here / "stub.py").write_text(STUB)
    res = tiny("stub", traffic={"path": "stub"}, driver_dir=str(here))
    assert (here / "installed").exists()
    assert res["correct"], res["checks"]
    with pytest.raises(FileNotFoundError):
        drivers.load("nothing", str(here))


@pytest.mark.parametrize("module", ["run.py", "spans.py"])
def test_shared_harness_names_no_path(module):
    """The shared harness knows no serving path: each lives in its
    driver module."""
    paths = [f[:-3] for f in os.listdir(drivers.HERE)
             if f.endswith(".py") and not f.startswith("_")]
    assert {"seq", "sched"} <= set(paths)
    text = open(os.path.join(os.path.dirname(drivers.HERE), module)).read()
    for p in paths:
        assert not re.search(rf"\b{p}\b", text), (module, p)


# -- the pieces, alone -------------------------------------------------------

def test_follow_numbers_recompute_the_election():
    """A follower counts as bad when its leader paid no scan of its own,
    when its set is not the leader's, or when its validation draft shares
    no more than ``tau`` of its ids with the leader's; a re-rank that is
    not by its own query's scores shows in ``follow_gap``."""
    from chipbench import check

    rng = np.random.default_rng(0)
    corpus = rng.normal(size=(64, 8)).astype(np.float32)
    corpus /= np.linalg.norm(corpus, axis=1, keepdims=True)
    emb = rng.normal(size=(3, 8)).astype(np.float32)
    lead_ids = np.arange(10, dtype=np.int32)
    s = corpus[lead_ids] @ emb[1]
    own = lead_ids[np.argsort(-s, kind="stable")]
    served = np.stack([lead_ids, own, own[::-1]])
    leader = np.array([-1, 0, 0])
    exact = np.array([True, False, False])
    val = np.stack([np.arange(10), np.r_[0, 1, np.arange(30, 38)],
                    np.r_[0, np.arange(30, 39)]]).astype(np.int32)
    spec = {"val_ids": val, "accept": np.zeros(3, bool),
            "seen": np.ones(3, np.int32)}
    out = check.follow_numbers(emb, served, leader, exact, spec, 0.1, corpus)
    # row 2: one shared id of ten is not above 0.1, and its order is
    # reversed; row 1 holds
    assert out["follow_bad"] == 1 and out["follow_election_bad"] == 1
    assert out["follow_gap"] > 0
    out = check.follow_numbers(emb[:2], served[:2], leader[:2], exact[:2],
                               {k: v[:2] for k, v in spec.items()}, 0.1,
                               corpus)
    assert out["follow_bad"] == 0 and out["follow_gap"] == 0.0
    other = served[:2].copy()
    other[1, 3] = 40
    out = check.follow_numbers(emb[:2], other, leader[:2], exact[:2],
                               {k: v[:2] for k, v in spec.items()}, 0.1,
                               corpus)
    assert out["follow_bad"] == 1


def test_ingest_consistency_takes_any_order_once():
    from chipbench import check

    emb = np.arange(12, dtype=np.float32).reshape(6, 2)
    ids = np.arange(18, dtype=np.int32).reshape(6, 3)
    cloud = np.array([True, False, True, True, False, True])
    rows = np.flatnonzero(cloud)

    def life(order):
        return drivers.Lifetime(rows=np.arange(6), ingest_q=emb[order],
                                ingest_ids=ids[order])
    assert check.ingest_consistency(life(rows[::-1]), emb, ids, cloud) == 0
    assert check.ingest_consistency(life(rows[:-1]), emb, ids, cloud) == 1
    assert check.ingest_consistency(life(np.r_[rows, 0]), emb, ids,
                                    cloud) == 1
    assert check.ingest_consistency(life(np.r_[rows, 1]), emb, ids,
                                    cloud) == 1


def test_probe_uninstall_restores_what_it_wrapped():
    from chipbench import spans

    class Owner:
        def f(self, x):
            return x + 1

    o = Owner()
    probe = spans.Probe(False)
    probe.patch(o, "f", "f", record=lambda a, kw, out: out)
    probe.patch(o, "f", before=probe.new_lifetime)
    assert o.f(1) == 2 and o.f(2) == 3
    assert probe.calls == {"f": 2} and probe.lifetime == 2
    assert probe.kept("f") == [2, 3] and probe.kept("f", lifetime=2) == [3]
    probe.uninstall()
    assert "f" not in vars(o) and o.f(1) == 2
