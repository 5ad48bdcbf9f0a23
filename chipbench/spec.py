"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

* configuration: the ``file`` its ``configs`` entry names;
* traffic mix: ``traffic/<traffic>.json`` beside this file;
* per-layer metric ``<name>``: ``metrics/<name>.py``, else the reader of
  its quantity, ``metrics/<name up to the first dot>.py``; a reader is a
  module with ``read(ctx) -> float | None``.

Adding a configuration, a traffic mix or a metric is adding its file and
its entry; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def cell(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, cell_: dict, root: str = ROOT) -> dict:
    entry = _named(bench["configs"], cell_["config"], "config")
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def traffic(name: str, here: str = HERE) -> dict:
    with open(os.path.join(here, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(bench: dict, cell_name: str) -> list[dict]:
    """The end-to-end metrics this cell reports."""
    return [m for m in bench["end_to_end"] if _applies(m, cell_name)]


def per_layer(bench: dict, cell_name: str) -> list[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end(bench, cell_name)}
    out = []
    for m in bench["per_layer"]:
        if ("workloads" in m and cell_name in m["workloads"]
                or "workloads" not in m and m["moves"] in e2e):
            out.append(m)
    return out


def reader(name: str, here: str = HERE):
    """``read`` of the metric's own module."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(here, "metrics", f"{stem}.py")
        if os.path.exists(path):
            mod_spec = importlib.util.spec_from_file_location(
                f"chipbench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(mod_spec)
            mod_spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{os.path.join(here, 'metrics')}")
