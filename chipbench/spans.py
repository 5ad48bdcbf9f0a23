"""Host spans and records around the program's layer entries.

The benchmark wraps, from outside, the attributes through which a serving
loop calls each layer.  Which attributes, under which span names, is the
driver's to say (``drivers/<path>.py::install``, through ``Probe.patch``):
this module knows no serving path.

Every run counts each span's calls and keeps what the driver asks it to
record (references only: device arrays are read back after the window),
each record tagged with the cache lifetime it fell in and with whether it
fell in the measured window.  A driver starts a new cache lifetime through
``new_lifetime`` where its program starts from an empty cache.  Only a
traced run opens a ``jax.profiler.TraceAnnotation`` per call and waits for
the call's device work at span end, so that device events fall inside the
span that launched them.
"""
from __future__ import annotations

import collections
import contextlib

import jax


class Probe:
    """Call counts and records; spans when ``traced``."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.calls: dict[str, int] = {}
        # span -> [(lifetime, in_window, item)]
        self.records: dict[str, list] = collections.defaultdict(list)
        self.lifetime = 0
        self.in_window = False
        self._undo: list = []

    @property
    def span_names(self) -> tuple[str, ...]:
        return tuple(self.calls)

    def start_window(self) -> None:
        """Count calls from here on; records from here on are the
        window's."""
        self.calls = dict.fromkeys(self.calls, 0)
        self.in_window = True

    def new_lifetime(self, *_) -> None:
        """The program starts again from an empty cache."""
        self.lifetime += 1

    def keep(self, key: str, item) -> None:
        """Record ``item`` under ``key`` outside any call."""
        self.records[key].append((self.lifetime, self.in_window, item))

    def _wrap(self, span, fn, before, record):
        traced = self.traced

        def call(*args, **kwargs):
            if before is not None:
                before(args, kwargs)
            if span is None:
                return fn(*args, **kwargs)
            self.calls[span] += 1
            if not traced:
                out = fn(*args, **kwargs)
            else:
                with jax.profiler.TraceAnnotation(span):
                    out = fn(*args, **kwargs)
                    jax.block_until_ready(out)
            if record is not None:
                self.keep(span, record(args, kwargs, out))
            return out
        return call

    def patch(self, owner, name: str, span: str | None = None, *,
              before=None, record=None) -> None:
        """Wrap ``owner.name``: ``before(args, kwargs)`` first; then, with a
        ``span``, the call counted (a span in a traced run) and
        ``record(args, kwargs, out)`` kept under the span's name."""
        old = getattr(owner, name)
        own = name in getattr(owner, "__dict__", {})
        self._undo.append((owner, name, old, own))
        if span is not None:
            self.calls.setdefault(span, 0)
        setattr(owner, name, self._wrap(span, old, before, record))

    def uninstall(self) -> None:
        while self._undo:
            owner, name, old, own = self._undo.pop()
            if own:
                setattr(owner, name, old)
            else:
                delattr(owner, name)

    def kept(self, span: str, *, window: bool | None = None,
             lifetime: int | None = None) -> list:
        """The items recorded under ``span``, in call order; only the
        window's (``window=True``) or set-up's (``False``), only one
        lifetime's."""
        return [item for life, win, item in self.records.get(span, ())
                if (window is None or win == window)
                and (lifetime is None or life == lifetime)]


@contextlib.contextmanager
def window_span(traced: bool):
    """The measured window as one host span named ``window``."""
    if not traced:
        yield
        return
    with jax.profiler.TraceAnnotation("window"):
        yield
