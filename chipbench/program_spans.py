"""The program's own spans on the device trace's clock.

The serving program marks the phases of its host loop itself
(``repro.core.dispatch.span``): ``has.step`` around each request, with the
engine's step count as ``req``, and inside it, on the same thread:

==============  ==========================================================
span            extent
==============  ==========================================================
has.upload      the query to the device
has.spec        ``speculate_batch`` and its wait (the Probe's ``spec``)
has.readback    accept flag, homology score and draft to the host
has.scan        ``backend.search`` and its ids to the host
has.gather      the served rows' vectors to the host
has.ingest      the uploads, ``cache_update`` and its wait
has.replicate   ``backend.on_ingest``
==============  ==========================================================

A traced run records them as host events.  This module reads them beside
each device plane's program executions, which it moves onto the host clock
by the offset ``tracing.reduce_events`` found from the Probe's spans
(``Reduced.offsets_ms``: no second estimate), and reduces the window to

* ``host_phases``: each idle interval of the device credited to the
  innermost program span over it, split at span edges; ``none`` is the time
  outside ``has.step``, the harness's own loop;
* ``device_s``: device time by the innermost program span that holds a
  program's start;
* ``programs``: device program executions that start inside ``has.step``,
  and ``phase_programs``, those by the innermost program span holding
  their start on the device, which can lag their launch: the query's
  ``[None]``, launched in ``has.upload``, waits for the upload and starts
  in ``has.spec``;
* ``inside_step``: the share of the window's device time that lies inside
  ``has.step``, which checks the clock alignment;
* ``phases``: calls, total and longest host ms of each program span.

Device numbers are means over the device planes.  A trace without the
program's spans, or without a device plane, gives ``None``.  Readers
``metrics/step_host_ms.py`` and ``metrics/programs_per_request.py``, through
``read``, which reduces a run's trace once and prints the ``[phases]`` and
``[host_phases]`` lines then.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import math
import sys

from chipbench import tracing

PREFIX = "has."
STEP = "has.step"
NONE = "none"


@dataclasses.dataclass
class ProgramTrace:
    window_s: float                     # the window span's length
    requests: int                       # has.step spans in the window
    host_phases: dict[str, float]       # idle s by innermost program span
    device_s: dict[str, float]          # device s by span at device start
    programs: float                     # programs started inside has.step
    phase_programs: dict[str, float]    # programs by span at device start
    inside_step: float                  # share of device time in has.step
    phases: dict[str, tuple[int, float, float]]  # calls, total ms, max ms
    req_missing: int                    # has.step req numbers not seen

    @property
    def step_host_ms(self) -> float:
        """Device-idle ms a request inside ``has.step``: the program's own
        host time."""
        inside = sum(v for k, v in self.host_phases.items() if k != NONE)
        return 1e3 * inside / self.requests


def host_events(profile) -> list[tuple[str, float, float, int | None]]:
    """(name, start_ns, end_ns, req) of the ``window`` span and every
    program span on any host thread."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX) or e.name == "window":
                    req = dict(e.stats).get("req") if e.name == STEP else None
                    out.append((e.name, e.start_ns, e.end_ns, req))
    return out


def segments(spans, w0: float, w1: float) -> list[tuple[float, float, str]]:
    """Cut [w0, w1) at the edges of nested ``spans`` ((start, end, name)):
    each piece named for the innermost span over it, ``none`` outside."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []          # (end, name), innermost last
    t = w0

    def close_until(limit: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    clipped = [(max(s, w0), min(e, w1), n) for s, e, n in spans]
    # a parent before the children that start with it
    for s, e, name in sorted(clipped, key=lambda x: (x[0], -x[1])):
        if e <= s:
            continue
        close_until(s)
        if s > t:
            out.append((t, s, stack[-1][1] if stack else NONE))
            t = s
        stack.append((e, name))
    close_until(w1)
    if w1 > t:
        out.append((t, w1, NONE))
    return out


def _overlap(ivs, segs, into: dict[str, float]) -> None:
    """Add to ``into[name]`` the length of sorted disjoint ``ivs`` inside
    each of the sorted disjoint ``segs``."""
    j = 0
    for s, e in ivs:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            lo, hi = max(s, segs[k][0]), min(e, segs[k][1])
            if hi > lo:
                into[segs[k][2]] += hi - lo
            k += 1


def reduce_program(device: dict[str, list[tuple[str, float, float]]],
                   host, offsets_ms: dict[str, float]) -> ProgramTrace | None:
    """Reduce device program executions (name, start_ns, end_ns) by plane
    and ``host_events`` to the window's ``ProgramTrace``; ``offsets_ms`` is
    each plane's device-minus-host clock offset."""
    windows = [(s, e) for n, s, e, _ in host if n == "window"]
    if not windows or not device:
        return None
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    spans = [(s, e, n) for n, s, e, _ in host
             if n.startswith(PREFIX) and e > w0 and s < w1]
    steps = [r for n, s, e, r in host if n == STEP and e > w0 and s < w1]
    if not steps:
        return None
    segs = segments(spans, w0, w1)
    starts = [s for s, _, _ in segs]
    step_segs = segments([x for x in spans if x[2] == STEP], w0, w1)
    step_starts = [s for s, _, _ in step_segs]
    idle: dict[str, float] = collections.defaultdict(float)
    dev: dict[str, float] = collections.defaultdict(float)
    started: dict[str, float] = collections.defaultdict(float)
    in_step: dict[str, float] = collections.defaultdict(float)
    busy = programs = 0.0
    for plane, events in device.items():
        d = offsets_ms.get(plane, 0.0) * 1e6
        inside = tracing.clip([(s - d, e - d) for _, s, e in events], w0, w1)
        ivs = tracing.union(inside)
        busy += sum(e - s for s, e in ivs)
        _overlap(ivs, step_segs, in_step)
        gaps, prev = [], w0
        for s, e in ivs + [(w1, w1)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        _overlap(gaps, segs, idle)
        owned = collections.defaultdict(list)
        for s, e in inside:
            owned[segs[bisect.bisect_right(starts, s) - 1][2]].append((s, e))
            i = bisect.bisect_right(step_starts, s) - 1
            programs += step_segs[i][2] == STEP
        for owner, ivs_o in owned.items():
            dev[owner] += sum(e - s for s, e in tracing.union(ivs_o))
            started[owner] += len(ivs_o)
    n_dev, ns = len(device), 1e-9
    durations = collections.defaultdict(list)
    for s, e, n in spans:
        durations[n].append(e - s)
    reqs = [r for r in steps if r is not None]
    return ProgramTrace(
        window_s=(w1 - w0) * ns,
        requests=len(steps),
        host_phases={k: v * ns / n_dev for k, v in sorted(idle.items())},
        device_s={k: v * ns / n_dev for k, v in sorted(dev.items())},
        programs=programs / n_dev,
        phase_programs={k: v / n_dev for k, v in sorted(started.items())},
        inside_step=in_step[STEP] / busy if busy else 0.0,
        phases={k: (len(v), sum(v) * 1e-6, max(v) * 1e-6)
                for k, v in sorted(durations.items())},
        req_missing=(max(reqs) - min(reqs) + 1 - len(reqs)) if reqs else 0)


def log(pt: ProgramTrace) -> None:
    """The ``[phases]`` and ``[host_phases]`` lines on stderr."""
    print("[phases] requests=%d req_missing=%d programs_per_request=%.4f "
          "inside_step=%.6f %s" % (
              pt.requests, pt.req_missing, pt.programs / pt.requests,
              pt.inside_step, " ".join(
                  f"{k}={c}/{t:.3f}/{m:.3f}ms"
                  for k, (c, t, m) in pt.phases.items())),
          file=sys.stderr, flush=True)
    print("[host_phases] idle_s " + " ".join(
        f"{k}={v:.6f}" for k, v in pt.host_phases.items())
        + " device_s " + " ".join(
        f"{k}={v:.6f}" for k, v in pt.device_s.items())
        + " programs " + " ".join(
        f"{k}={v:g}" for k, v in pt.phase_programs.items()),
        file=sys.stderr, flush=True)


def reduce_file(path: str, offsets_ms: dict[str, float]):
    """The ``ProgramTrace`` of a recorded window."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    device, _, _, _ = tracing.events_from_profile(profile, ())
    return reduce_program(device, host_events(profile), offsets_ms)


def read(ctx) -> ProgramTrace | None:
    """The run's ``ProgramTrace``, for a metric reader, on the clock its
    reduction (``ctx.trace``) found; reduced and logged once a run.

    ``ctx`` does not say where the run recorded, so this takes the newest
    window under ``run.py``'s default trace directory, and only if that
    window is as long as the run's own (``ctx.trace.window_s``, to the
    nanosecond): a window another run left there gives ``None``."""
    if ctx.trace is None:
        return None
    if not hasattr(ctx, "program_trace"):
        ctx.program_trace = _own_trace(ctx)
        if ctx.program_trace is not None:
            log(ctx.program_trace)
    return ctx.program_trace


def _own_trace(ctx) -> ProgramTrace | None:
    from chipbench import run
    try:
        path = tracing.latest_xplane(run.TRACE_DIR)
    except FileNotFoundError:
        return None
    pt = reduce_file(path, ctx.trace.offsets_ms)
    if pt is None or not math.isclose(pt.window_s, ctx.trace.window_s,
                                      rel_tol=0, abs_tol=1e-9):
        return None
    return pt
