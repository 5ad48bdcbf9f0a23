"""Spans and dispatch counts of the serving hot path (``core/dispatch.py``):
each span's calls, total and longest host time, nesting, ``reset``, the
counts the full-retrieval backends record, what ``HasEngine.step``
dispatches, and the serving CLI's ``[phases]`` line that reads them."""
from __future__ import annotations

import types

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch, has
from repro.data.synthetic import SyntheticWorld, WorldConfig
from repro.retrieval.service import (LocalFlatBackend, RetrievalService,
                                     ShardedMeshBackend)
from repro.serving.engine import HasEngine
from repro.serving.latency import LatencyModel


@pytest.fixture
def clock(monkeypatch):
    """A host clock that moves only when the test says, and an empty
    table."""
    now = types.SimpleNamespace(ns=0)
    monkeypatch.setattr(dispatch, "time", types.SimpleNamespace(
        perf_counter_ns=lambda: now.ns))
    dispatch.reset()
    return now


def test_span_counts_calls_total_and_max(clock):
    for dt in (30, 100, 20):
        with dispatch.span("has.x", req=dt):
            clock.ns += dt
    assert dispatch.spans() == {"has.x": dispatch.SpanStats(3, 150, 100)}


def test_nested_spans_hold_their_children(clock):
    with dispatch.span("has.step", req=0):
        clock.ns += 5
        for _ in range(2):
            with dispatch.span("has.readback"):
                clock.ns += 7
        clock.ns += 1
    got = dispatch.spans()
    assert got["has.step"] == dispatch.SpanStats(1, 20, 20)
    assert got["has.readback"] == dispatch.SpanStats(2, 14, 7)


def test_reset_starts_a_new_table(clock):
    with dispatch.span("has.x"):
        clock.ns += 1000                    # longest, but before the reset
    dispatch.reset()
    for dt in (3, 4):
        with dispatch.span("has.x"):
            clock.ns += dt
    assert dispatch.spans() == {"has.x": dispatch.SpanStats(2, 7, 4)}


def test_span_counts_a_phase_that_raises(clock):
    with pytest.raises(ValueError):
        with dispatch.span("has.x"):
            clock.ns += 9
            raise ValueError("boom")
    assert dispatch.spans() == {"has.x": dispatch.SpanStats(1, 9, 9)}


def test_reset_clears_spans_and_counts(clock):
    with dispatch.span("has.x"):
        clock.ns += 1
    dispatch.record("speculate_batch")
    dispatch.reset()
    assert dispatch.spans() == {} and dispatch.counts() == {}


@pytest.fixture(scope="module")
def world():
    return SyntheticWorld(WorldConfig(n_entities=120, seed=0))


@pytest.mark.parametrize("make, name", [
    (lambda c, lat: LocalFlatBackend(c, 10, lat, chunk=256),
     "flat_backend_search"),
    (lambda c, lat: ShardedMeshBackend(c, 10, lat, n_shards=2),
     "sharded_backend_search"),
])
def test_exact_backends_record_each_search(world, make, name):
    backend = make(jnp.asarray(world.doc_emb), LatencyModel())
    q = jnp.asarray(world.doc_emb[:3])
    with dispatch.capture() as c:
        backend.search(q)[1].block_until_ready()
        backend.search(q[:1])[1].block_until_ready()
    assert c.counts() == {name: 2}


def test_full_search_spans_scan_and_gather(world):
    svc = RetrievalService(world, LatencyModel(), k=10, chunk=256)
    dispatch.reset()
    with dispatch.capture() as c:
        ids, vecs, _ = svc.full_search(np.asarray(world.doc_emb[5]))
    assert ids[0] == 5
    np.testing.assert_array_equal(vecs, np.asarray(world.doc_emb)[ids])
    assert set(dispatch.spans()) == {"has.scan", "has.gather"}
    assert all(s.calls == 1 for s in dispatch.spans().values())
    assert c.counts() == {"flat_backend_search": 1}


def test_step_launches_one_program_a_phase(world):
    """An accepted ``HasEngine.step`` dispatches its speculation alone; a
    rejected one adds one scan and one ingest.  Once one of each has run,
    further steps compile nothing: host arrays and device rows reuse the
    cached programs of the speculation, the row gather and the ingest."""
    svc = RetrievalService(world, LatencyModel(), k=10, chunk=256)
    eng = HasEngine(svc, has.HasConfig(k=10, tau=0.2, h_max=64, nprobe=8,
                                       n_buckets=32, d=world.cfg.d))
    qs = world.sample_queries(21, seed=3)
    q = qs[0]["emb"]
    with dispatch.capture() as c:
        assert not eng.step(q)[1]           # an empty cache rejects
    assert c.counts() == {"speculate_batch": 1, "flat_backend_search": 1,
                          "cache_update": 1}
    with dispatch.capture() as c:
        assert eng.step(q)[1]               # its own answer is homologous
    assert c.counts() == {"speculate_batch": 1}
    progs = (has._gather_rows, has._speculate_batch_impl,
             has._cache_update_jit)
    sizes = [p._cache_size() for p in progs]
    accepts = [eng.step(x["emb"])[1] for x in qs[1:]]
    assert len(accepts) == 20 and not all(accepts)
    assert [p._cache_size() for p in progs] == sizes


def test_serve_cli_prints_phases(capsys):
    """``python -m repro.launch.serve`` reads the table: one ``[phases]``
    line with every ``has.*`` span of the run and the dispatches a
    request."""
    from repro.launch.serve import main
    main(["--queries", "16", "--entities", "60", "--dim", "32"])
    line, = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[phases]")]
    got = dict(kv.split("=") for kv in line.split()[1:] if "=" in kv)
    assert got["has.step"].startswith("16/")
    n_scan = int(got.get("has.scan", "0/").split("/")[0])
    assert got["has.spec"].startswith("16/") and got["has.upload"]
    assert float(got["speculate_batch"]) == 1.0
    assert float(got["flat_backend_search"]) == n_scan / 16


def test_sched_cli_counts_each_election(capsys, monkeypatch):
    """The scheduler records one ``intra_batch_share`` dispatch per
    sharing election it runs while serving (not its warm-up), and the
    serving CLI prints the count on its ``[phases]`` line."""
    from repro.launch.serve import main
    from repro.serving import scheduler

    calls = []
    elect = scheduler.intra_batch_share

    def counted(*a, **k):
        calls.append(dispatch.counts().get("intra_batch_share", 0))
        return elect(*a, **k)
    monkeypatch.setattr(scheduler, "intra_batch_share", counted)
    main(["--engine", "sched", "--queries", "64", "--entities", "60",
          "--dim", "32"])
    line, = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[phases]")]
    got = dict(kv.split("=") for kv in line.split()[1:] if "=" in kv)
    served = calls[1:]                     # calls[0] is the warm-up's
    assert served and served == list(range(1, len(served) + 1))
    assert float(got["intra_batch_share"]) == pytest.approx(
        len(served) / 64, abs=1e-4)
