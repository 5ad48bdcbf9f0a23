"""Spans and dispatch counts of the serving hot path, in one module.

*Dispatch counts.*  A dispatch is one host-side invocation of a jitted
program (one XLA executable launch): the unit the batch-native refactor
optimizes, since a speculation batch that costs O(B) dispatches is
dominated by host↔device round-trips long before it is bandwidth-bound.
Every public entry point in ``core/has.py`` and every full-retrieval
backend's ``search`` records itself here, so benchmarks can assert the
dispatch model (e.g. "one ``speculate_batch`` call == one dispatch
regardless of B") instead of inferring it from wall-clock.  Recording is a
dict increment (no device sync, no tracing interaction — wrappers record
*outside* the jitted callables, so nothing is counted at trace time).
Eager array ops (an index, a gather) launch programs too and are not
counted: a device trace shows them beside these counts.

*Spans.*  ``span(name, **meta)`` marks one phase of the host loop.  It
opens a ``jax.profiler.TraceAnnotation`` — recorded only while a profiler
session runs, on the same clock as the device trace — and always
accumulates the phase's calls, total and longest host time
(``time.perf_counter_ns``).  Spans nest on one thread; a parent's time
includes its children's.  Both together cost a few microseconds a span
on a host CPU core, so they stay on.  ``spans()`` returns the table;
``python -m repro.launch.serve`` prints it, beside the dispatch counts a
request, as its ``[phases]`` line.

Both are process-global, keyed by name; ``reset()`` clears both, and
``capture()`` scopes the dispatch counts to a block::

    from repro.core import dispatch
    with dispatch.capture() as probe:
        speculate_batch(cfg, state, index, q)     # [B, d]
    assert probe.total() == 1
    dispatch.spans()   # {name: SpanStats(calls, total_ns, max_ns)}
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Iterator, NamedTuple

import jax

_counts: collections.Counter = collections.Counter()
_spans: dict[str, list[int]] = {}        # name -> [calls, total_ns, max_ns]


class SpanStats(NamedTuple):
    calls: int
    total_ns: int
    max_ns: int


def record(name: str) -> None:
    """Count one device dispatch attributed to entry point ``name``."""
    _counts[name] += 1


def counts() -> dict[str, int]:
    return dict(_counts)


class span:
    """``with span(name, **meta):`` one phase of the host loop; ``meta``
    (e.g. ``req=7``) goes to the profiler event only."""

    __slots__ = ("_name", "_ann", "_t0")

    def __init__(self, name: str, **meta):
        self._name = name
        self._ann = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        ns = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        s = _spans.get(self._name)
        if s is None:
            _spans[self._name] = [1, ns, ns]
        else:
            s[0] += 1
            s[1] += ns
            if ns > s[2]:
                s[2] = ns
        return False


def spans() -> dict[str, SpanStats]:
    """Every span since the process started (or the last ``reset``)."""
    return {k: SpanStats(*v) for k, v in _spans.items()}


def reset() -> None:
    _counts.clear()
    _spans.clear()


class Capture:
    """Dispatch counts scoped to a ``with dispatch.capture()`` block."""

    def __init__(self, baseline: dict[str, int]):
        self._baseline = baseline

    def counts(self) -> dict[str, int]:
        return {k: v - self._baseline.get(k, 0)
                for k, v in _counts.items()
                if v - self._baseline.get(k, 0) > 0}

    def total(self) -> int:
        return sum(self.counts().values())


@contextlib.contextmanager
def capture() -> Iterator[Capture]:
    yield Capture(dict(_counts))
