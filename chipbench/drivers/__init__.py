"""Serving paths, one module each, found by the traffic file's ``path``.

A driver module names the ``--engine`` it builds (``ENGINE``) and gives
``warm`` (-> ``Warm``), ``window`` (-> ``Window``), ``spec_batch`` and
``final_state``.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np


@dataclasses.dataclass
class Warm:
    """What set-up served before the window, in stream order."""
    ids: np.ndarray             # [W, k] ids served (or folded in)
    accepts: np.ndarray         # [W] speculation accepted
    preloaded: int = 0          # leading rows folded into the cache in
    #                             bulk, before any ingest the probe records


@dataclasses.dataclass
class Window:
    """What a measured window served, in stream order."""
    rows: np.ndarray            # [n] stream index of each request served
    n: int                      # requests completed in the window
    wall_s: float
    served: np.ndarray          # [n, k] ids served
    accepts: np.ndarray         # [n] speculation accepted (any channel)
    exact_rows: np.ndarray      # window rows answered by the exact scan
    spec_calls: int
    spec_rows: int
    scan_calls: int
    scan_rows: int
    e2e: dict                   # end-to-end metrics this path measures
    lateness_s: np.ndarray      # host time between one send and the last
    detail: dict = dataclasses.field(default_factory=dict)  # printed only
    modeled: dict = dataclasses.field(default_factory=dict)  # virtual
    #                             clock / latency model: printed, never a metric


def load(path: str):
    """The driver module of a traffic ``path`` (``seq``, ...)."""
    if not path.isidentifier():
        raise ValueError(f"bad path name {path!r}")
    return importlib.import_module(f"chipbench.drivers.{path}")
