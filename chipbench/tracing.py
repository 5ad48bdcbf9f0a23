"""Profiler trace -> device busy time, idle share and per-span device time.

A traced run records the window with ``jax.profiler`` and reads the
``.xplane.pb`` back with ``jax.profiler.ProfileData``:

* device work: the events of each device plane (``/device:TPU:n``) on its
  ``XLA Modules`` line (one event per program execution), or on every line
  but the step and module markers where a plane has no such line;
* host spans: the events named ``window`` and those of the Probe's spans on
  any host thread.

A device plane keeps its own clock, which a TPU trace shows some hundreds
of microseconds to milliseconds off the host's.  The traced run waits for
a span's device work before the span ends, so every program a span
launched runs inside it; the reduction takes as a device's offset the
shift that puts the most of its device time inside the host spans (the
middle of the range of shifts that do so), and moves its events onto the
host clock by it.

Busy time is the union of a device's event intervals inside the window,
averaged over the devices used; idle share is ``1 - busy / window``.  A
device interval belongs to the host span that contains its start, else to
``none``: the serving loop's own host code.  Idle gaps are credited the
same way, by their midpoint.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

import numpy as np

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_SKIP_LINES = ("Steps", "XLA TraceMe", "Framework")
_DEVICE_PLANE = re.compile(r"/device:(TPU|GPU):\d+")
MAX_OFFSET_NS = 20e6          # shifts searched: +-20 ms ...
COARSE_NS, FINE_NS = 50e3, 2e3  # ... every 50 us, then every 2 us
OP_NAME_CHARS = 160           # of an HLO op's text, in the breakdown


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over devices
    span_device_s: dict[str, float]     # device time by launching span
    span_count: dict[str, int]          # host spans seen, by name
    device_ops: list[tuple[str, float]]  # heaviest ops (name, seconds)
    idle_gaps: list[tuple[str, float]]  # idle seconds by host span
    n_devices: int
    lines: dict[str, int] = dataclasses.field(default_factory=dict)
    offsets_ms: dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def union(intervals) -> list[tuple[float, float]]:
    """Merge [start, end) intervals (any order) into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _owner(t: float, spans: list[tuple[float, float, str]], starts) -> str:
    """Name of the span containing time ``t`` (spans sorted, disjoint)."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and spans[i][0] <= t < spans[i][1]:
        return spans[i][2]
    return "none"


def clock_offset(events, spans) -> float:
    """Device clock minus host clock in ns: the middle of the shifts that
    put the most device time inside the host ``spans`` ((start, end, name),
    sorted, disjoint); 0 without spans or events."""
    if not spans or not events:
        return 0.0
    ss = np.array([s for s, _, _ in spans])
    se = np.array([e for _, e, _ in spans])
    es = np.array([s for _, s, _ in events], np.float64)
    ee = np.array([e for _, _, e in events], np.float64)
    dur = ee - es

    def inside(shifts):
        out = np.empty(len(shifts))
        for j, d in enumerate(shifts):
            s, e = es - d, ee - d
            i = np.maximum(np.searchsorted(ss, s, side="right") - 1, 0)
            out[j] = dur[(s >= ss[i]) & (e <= se[i])].sum()
        return out

    coarse = np.arange(-MAX_OFFSET_NS, MAX_OFFSET_NS + 1, COARSE_NS)
    c = inside(coarse)
    top = coarse[c >= c.max()]
    fine = np.arange(top.min() - COARSE_NS, top.max() + COARSE_NS + 1,
                     FINE_NS)
    f = inside(fine)
    best = fine[f >= f.max()]
    return float(0.5 * (best.min() + best.max()))


def reduce_events(device: dict[str, list[tuple[str, float, float]]],
                  host: list[tuple[str, float, float]],
                  ops: dict[str, float] | None = None,
                  span_names=("spec", "cloud_scan", "ingest"),
                  lines: dict[str, int] | None = None) -> Reduced:
    """Reduce raw events (name, start_ns, end_ns) to the window's numbers.

    ``device`` maps a device plane to its program executions; ``host`` holds
    host events (only ``window`` and ``span_names`` are used); ``ops`` is the
    device time of each op name, for the breakdown.
    """
    windows = [(s, e) for n, s, e in host if n == "window"]
    if not windows:
        raise ValueError("trace holds no 'window' span")
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    spans = sorted((s, e, n) for n, s, e in host
                   if n in span_names and e > w0 and s < w1)
    starts = [s for s, _, _ in spans]
    count = collections.Counter(n for _, _, n in spans)
    span_dev: dict[str, float] = collections.defaultdict(float)
    idle: dict[str, float] = collections.defaultdict(float)
    busy_total = 0.0
    n_dev = 0
    offsets = {}
    for plane, events in sorted(device.items()):
        n_dev += 1
        d = clock_offset(events, spans)
        offsets[plane] = d * 1e-6
        inside = clip([(s - d, e - d) for _, s, e in events], w0, w1)
        ivs = union(inside)
        busy_total += sum(e - s for s, e in ivs)
        by_owner = collections.defaultdict(list)
        for s, e in inside:
            by_owner[_owner(s, spans, starts)].append((s, e))
        for owner, owned in by_owner.items():
            span_dev[owner] += sum(e - s for s, e in union(owned))
        prev = w0
        for s, e in ivs + [(w1, w1)]:
            if s > prev:
                idle[_owner(0.5 * (prev + s), spans, starts)] += s - prev
            prev = max(prev, e)
    if not n_dev:
        raise ValueError("trace holds no device plane")
    ns = 1e-9
    top_ops = sorted(((k, v * ns) for k, v in (ops or {}).items()),
                     key=lambda kv: -kv[1])[:10]
    gaps = sorted(((k, v * ns / n_dev) for k, v in idle.items()),
                  key=lambda kv: -kv[1])[:10]
    return Reduced(window_s=(w1 - w0) * ns,
                   busy_s=busy_total * ns / n_dev,
                   span_device_s={k: v * ns / n_dev
                                  for k, v in span_dev.items()},
                   span_count=dict(count), device_ops=top_ops,
                   idle_gaps=gaps, n_devices=n_dev, lines=dict(lines or {}),
                   offsets_ms=offsets)


def is_device_plane(name: str) -> bool:
    """An accelerator's own plane; not the host, nor a custom plane such
    as ``/device:CUSTOM:Megascale Trace``, which runs no program."""
    return _DEVICE_PLANE.fullmatch(name) is not None


def events_from_profile(profile, span_names):
    """(device events by plane, host span events, op seconds by name,
    events on each device line) from a ``jax.profiler.ProfileData``."""
    device: dict[str, list] = {}
    seen: dict[str, int] = {}
    ops: dict[str, float] = collections.defaultdict(float)
    host: list = []
    wanted = set(span_names) | {"window"}
    for plane in profile.planes:
        if is_device_plane(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            for name, ln in lines.items():
                seen[f"{plane.name}|{name}"] = sum(1 for _ in ln.events)
            if MODULE_LINE in lines:
                progs = [lines[MODULE_LINE]]
            else:
                progs = [ln for name, ln in lines.items()
                         if not any(t in name for t in _SKIP_LINES)]
            evs = device.setdefault(plane.name, [])
            for ln in progs:
                evs.extend((e.name, e.start_ns, e.end_ns) for e in ln.events)
            if OPS_LINE in lines:
                for e in lines[OPS_LINE].events:
                    ops[e.name[:OP_NAME_CHARS]] += e.duration_ns
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in ln.events if e.name in wanted)
    return device, host, dict(ops), seen


def latest_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def reduce_file(path: str, span_names) -> Reduced:
    from jax.profiler import ProfileData
    device, host, ops, seen = events_from_profile(
        ProfileData.from_file(path), span_names)
    return reduce_events(device, host, ops, span_names, seen)
