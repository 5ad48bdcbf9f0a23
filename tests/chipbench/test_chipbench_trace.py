"""Trace reduction: busy union, idle share, attribution to host spans."""
from __future__ import annotations

import pytest

from chipbench import tracing

MS = 1_000_000  # ns


def test_union_and_clip():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [
        (0, 3), (5, 8)]
    assert tracing.clip([(0, 4), (6, 12)], 2, 10) == [(2, 4), (6, 10)]


def test_reduce_busy_idle_and_attribution():
    host = [("window", 0, 100 * MS),
            ("spec", 10 * MS, 20 * MS),
            ("cloud_scan", 30 * MS, 60 * MS),
            ("ingest", 70 * MS, 75 * MS),
            ("spec", 200 * MS, 210 * MS)]           # outside the window
    device = {"/device:TPU:0": [
        ("spec_prog", 11 * MS, 14 * MS),
        ("spec_prog", 16 * MS, 19 * MS),
        ("scan_prog", 31 * MS, 40 * MS),
        ("scan_part", 35 * MS, 39 * MS),            # overlaps the first
        ("scan_prog", 45 * MS, 58 * MS),
        ("ingest_prog", 71 * MS, 74 * MS),
        ("eager_gather", 62 * MS, 64 * MS),         # launched by the loop
        ("late", 95 * MS, 110 * MS)]}               # clipped at 100
    red = tracing.reduce_events(device, host, {"fusion": 3 * MS,
                                               "dot": 9 * MS})
    assert red.window_s == pytest.approx(0.1)
    busy = 6 + 22 + 3 + 2 + 5
    assert red.busy_s == pytest.approx(busy * 1e-3)
    assert red.idle_share == pytest.approx(1 - busy / 100)
    assert red.span_device_s["spec"] == pytest.approx(6e-3)
    assert red.span_device_s["cloud_scan"] == pytest.approx(22e-3)
    assert red.span_device_s["ingest"] == pytest.approx(3e-3)
    assert red.span_device_s["none"] == pytest.approx(7e-3)
    assert red.span_count == {"spec": 1, "cloud_scan": 1, "ingest": 1}
    assert red.device_ops[0] == ("dot", pytest.approx(9e-3))
    # an idle gap belongs to the span holding its midpoint: 14-16 in spec,
    # 40-45 in the scan, the rest (0-11, 19-31, 58-62, 64-71, 74-95) in
    # the loop's own host code
    gaps = dict(red.idle_gaps)
    assert gaps["spec"] == pytest.approx(2e-3)
    assert gaps["cloud_scan"] == pytest.approx(5e-3)
    assert gaps["none"] == pytest.approx(55e-3)
    assert sum(gaps.values()) == pytest.approx(0.1 - busy * 1e-3)


def test_device_clock_offset_is_found_and_removed():
    """A device plane whose clock runs 1.5 ms behind the host's: each
    program still belongs to the span that launched it."""
    host = [("window", 0, 100 * MS), ("spec", 10 * MS, 21 * MS),
            ("cloud_scan", 30 * MS, 45 * MS), ("ingest", 50 * MS, 51 * MS)]
    lag = 1.5 * MS
    device = {"/device:TPU:0": [
        ("spec_prog", 10.2 * MS - lag, 20.8 * MS - lag),
        ("scan_prog", 30.3 * MS - lag, 44.1 * MS - lag),
        ("ingest_prog", 50.1 * MS - lag, 50.6 * MS - lag),
        ("eager", 60 * MS - lag, 61 * MS - lag)]}
    red = tracing.reduce_events(device, host)
    assert red.offsets_ms["/device:TPU:0"] == pytest.approx(-1.5, abs=0.1)
    assert red.span_device_s["spec"] == pytest.approx(10.6e-3)
    assert red.span_device_s["cloud_scan"] == pytest.approx(13.8e-3)
    assert red.span_device_s["ingest"] == pytest.approx(0.5e-3)
    assert red.span_device_s["none"] == pytest.approx(1e-3)
    assert red.busy_s == pytest.approx(25.9e-3)


def test_only_accelerator_planes_are_devices():
    assert tracing.is_device_plane("/device:TPU:0")
    assert tracing.is_device_plane("/device:TPU:3")
    assert not tracing.is_device_plane("/device:CUSTOM:Megascale Trace")
    assert not tracing.is_device_plane("/host:CPU")


def test_busy_is_averaged_over_devices():
    host = [("window", 0, 10 * MS)]
    device = {"/device:TPU:0": [("a", 0, 10 * MS)],
              "/device:TPU:1": [("a", 0, 5 * MS)]}
    red = tracing.reduce_events(device, host)
    assert red.n_devices == 2
    assert red.busy_s == pytest.approx(7.5e-3)


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce_events({"/device:TPU:0": []}, [])
    with pytest.raises(ValueError):
        tracing.reduce_events({}, [("window", 0, 1)])


def test_events_from_a_recorded_xspace():
    """The profile reader on an XSpace shaped as a TPU trace: program
    executions on the device's module line, host spans on a host thread."""
    from jax.profiler import ProfileData
    text = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000000 }
    events { metadata_id: 2 offset_ps: 6000000000 duration_ps: 2000000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 3000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit__speculate" } }
  event_metadata { key: 2 value { id: 2 name: "jit_search" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.1" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 500000000 duration_ps: 5000000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000 }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "spec" } }
  event_metadata { key: 3 value { id: 3 name: "unrelated" } }
}
"""
    device, host, ops, lines = tracing.events_from_profile(
        ProfileData.from_text_proto(text), ("spec", "cloud_scan"))
    assert [e[0] for e in device["/device:TPU:0"]] == ["jit__speculate",
                                                        "jit_search"]
    assert {h[0] for h in host} == {"window", "spec"}
    assert ops == {"fusion.1": pytest.approx(3e6)}
    assert lines["/device:TPU:0|XLA Modules"] == 2
    red = tracing.reduce_events(device, host, ops, ("spec", "cloud_scan"))
    assert red.window_s == pytest.approx(0.01)
    assert red.busy_s == pytest.approx(0.006)
    assert red.span_device_s == {"spec": pytest.approx(0.004),
                                 "none": pytest.approx(0.002)}
