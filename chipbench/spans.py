"""Host spans, ingest and scan records around the program's layer entries.

The benchmark wraps, from outside, the module attributes through which the
serving loops call each layer:

==========  ==================================================  ==========
span        entry point                                         layer
==========  ==================================================  ==========
spec        ``speculate_batch``                                 speculation
cloud_scan  ``RetrievalService.backend.search``                 cloud scan
ingest      ``cache_update``                                    cache ingest
==========  ==================================================  ==========

Every run counts the calls and keeps a reference to each ingest's rows
(the correctness check replays them) and to each cloud scan's queries and
answers (the check rescores them); only a traced run opens a
``jax.profiler.TraceAnnotation`` per call and waits for the call's device
work at span end, so that device events fall inside the span that launched
them.
"""
from __future__ import annotations

import contextlib

import jax
import numpy as np

SPANS = ("spec", "cloud_scan", "ingest")


class Probe:
    """Call counts, ingest rows and scan answers; spans when ``traced``."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.calls = dict.fromkeys(SPANS, 0)
        self.ingests: list[tuple[np.ndarray, np.ndarray]] = []
        self.scans: list[tuple] = []     # (queries, (scores, ids))
        self._undo: list = []

    def start_window(self) -> None:
        """Count and keep scans from here on."""
        self.calls = dict.fromkeys(SPANS, 0)
        self.scans = []

    def _wrap(self, span: str, fn, on_call=None, on_return=None):
        traced = self.traced

        def call(*args, **kwargs):
            self.calls[span] += 1
            if on_call is not None:
                on_call(args, kwargs)
            if not traced:
                out = fn(*args, **kwargs)
            else:
                with jax.profiler.TraceAnnotation(span):
                    out = fn(*args, **kwargs)
                    jax.block_until_ready(out)
            if on_return is not None:
                on_return(args, out)
            return out
        return call

    def patch(self, owner, name: str, span: str, on_call=None,
              on_return=None) -> None:
        old = getattr(owner, name)
        self._undo.append((owner, name, old))
        setattr(owner, name, self._wrap(span, old, on_call, on_return))

    def install(self, path: str, engine) -> None:
        """Wrap the entries the ``seq`` loop (``HasEngine.step``) calls."""
        if path != "seq":
            raise ValueError(f"unknown path {path!r}")
        from repro.serving import engine as loop
        self.patch(loop, "speculate_batch", "spec")
        self.patch(loop, "cache_update", "ingest", self._seq_row)
        self.patch(engine.s.backend, "search", "cloud_scan",
                   on_return=lambda args, out: self.scans.append(
                       (args[0], out)))

    def uninstall(self) -> None:
        while self._undo:
            owner, name, old = self._undo.pop()
            setattr(owner, name, old)

    # references only: the rows are read back after the window
    # cache_update(cfg, state, q_emb [d], full_ids [k], full_vecs, ...)
    def _seq_row(self, args, kwargs):
        self.ingests.append((args[2], args[3]))

    def scan_rows(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Every kept scan's rows: (queries [n, d], scores [n, k],
        ids [n, k]); a batch's padding rows included."""
        if not self.scans:
            return (np.zeros((0, 0), np.float32),) * 2 + (
                np.zeros((0, 1), np.int32),)
        return (np.concatenate([np.asarray(q, np.float32)
                                for q, _ in self.scans]),
                np.concatenate([np.asarray(o[0], np.float32)
                                for _, o in self.scans]),
                np.concatenate([np.asarray(o[1], np.int32)
                                for _, o in self.scans]))

    def ingest_rows(self) -> tuple[np.ndarray, np.ndarray]:
        """All recorded ingest rows, in order: (q [n, d], ids [n, k])."""
        qs = [np.asarray(q, np.float32).reshape(-1, np.shape(q)[-1])
              for q, _ in self.ingests]
        ids = [np.asarray(i, np.int32).reshape(-1, np.shape(i)[-1])
               for _, i in self.ingests]
        if not qs:
            return np.zeros((0, 0), np.float32), np.zeros((0, 0), np.int32)
        return np.concatenate(qs), np.concatenate(ids)


@contextlib.contextmanager
def window_span(traced: bool):
    """The measured window as one host span named ``window``."""
    if not traced:
        yield
        return
    with jax.profiler.TraceAnnotation("window"):
        yield
