"""Serving paths, one module each, found by the traffic file's ``path``.

A driver module ``<path>.py`` in this directory names the ``--engine`` it
builds (``ENGINE``) and the load it offers (``LOAD``), and gives

* ``install(probe, engine)``: wrap, through ``Probe.patch``, the attributes
  its loop calls: ``spec``, ``cloud_scan`` and ``ingest`` spans at least,
  with the records ``lifetimes`` reads;
* ``warm(engine, stream, traffic)`` -> ``Warm``: set-up's serving, which
  compiles every program the window runs;
* ``window(engine, stream, start, seconds, traffic, seed)`` -> ``Window``;
* ``spec_batch(engine)`` and ``spec_backend(engine)``: the speculation
  program's batch and backend as the window runs it;
* ``final_state(engine)``: the cache state the window left;
* ``lifetimes(probe, warm, win, emb)`` -> ``[Lifetime]``: what each cache
  lifetime took in, for the check to compare and replay (``emb``: the
  query vector of each served-table row, set-up's rows then the
  window's).

Adding a path is adding its module and a traffic file that names it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Warm:
    """What set-up served before the window, in admission order."""
    rows: np.ndarray            # [W] stream index of each request served
    ids: np.ndarray             # [W, k] ids served (or folded in)
    accepts: np.ndarray         # [W] speculation accepted
    cloud: np.ndarray           # [W] answered by the cloud: the answers
    #                             the cache has to take in
    preloaded: int = 0          # leading rows folded into the cache in
    #                             bulk, before any ingest the probe records


@dataclasses.dataclass
class Window:
    """What a measured window served, in admission order."""
    rows: np.ndarray            # [n] stream index of each request served
    n: int                      # requests completed in the window
    wall_s: float
    served: np.ndarray          # [n, k] ids served
    accepts: np.ndarray         # [n] accepted (any channel)
    exact_rows: np.ndarray      # window rows answered by their own scan
    cloud: np.ndarray           # [n] answered by the cloud (own scan or
    #                             one shared): the cache has to take these in
    spec_calls: int
    spec_rows: int
    scan_calls: int
    scan_rows: int
    e2e: dict                   # end-to-end metrics this path measures
    lateness_s: np.ndarray      # host time between one send and the last
    drafted: np.ndarray | None = None  # [n] served their speculation's
    #                             draft (None: not recorded)
    leader: np.ndarray | None = None   # [n] window row whose answer a row
    #                             shares (-1: none; None: no sharing)
    share_tau: float | None = None     # the sharing threshold stated
    detail: dict = dataclasses.field(default_factory=dict)  # printed only
    modeled: dict = dataclasses.field(default_factory=dict)  # virtual
    #                             clock / latency model: printed, never a metric


@dataclasses.dataclass
class Lifetime:
    """One cache lifetime, from an empty cache to the state it left.

    ``rows`` index the run's served table, set-up's rows then the
    window's.  The ingests are what the probe recorded, in program order.
    """
    rows: np.ndarray            # the served-table rows the lifetime holds
    ingest_q: np.ndarray        # [m, d]
    ingest_ids: np.ndarray      # [m, k]
    preloaded: int = 0          # leading ``rows`` folded in before any
    #                             recorded ingest
    spec: dict | None = None    # recorded speculation of each of ``rows``
    #                             (``val_ids``, ``draft_ids`` [len, k],
    #                             ``accept`` [len], ``seen`` [len])
    end: dict | None = None     # the cache the lifetime left, as host
    #                             arrays of some of HasState's fields; None
    #                             for the last, which the run compares
    #                             whole; {} where the program went on
    #                             writing it after the lifetime ended


def stack_rows(items, width: int, dtype) -> np.ndarray:
    """Recorded arrays (each [..., width]) as one [m, width] host array."""
    out = [np.asarray(x, dtype).reshape(-1, width) for x in items]
    return np.concatenate(out) if out else np.zeros((0, width), dtype)


def row_lookup(emb: np.ndarray, rows: np.ndarray) -> dict:
    """{bytes of a query vector: its served-table row} over ``rows``."""
    return {emb[r].tobytes(): int(r) for r in rows}


def load(path: str, here: str = HERE):
    """The driver module of a traffic ``path``: ``<here>/<path>.py``."""
    if not path.isidentifier():
        raise ValueError(f"bad path name {path!r}")
    file = os.path.join(here, f"{path}.py")
    if not os.path.exists(file):
        raise FileNotFoundError(f"no driver for path {path!r} at {file}")
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_driver_{path}", file)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod
