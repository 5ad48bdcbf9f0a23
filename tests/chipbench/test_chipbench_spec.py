"""The benchmark finds its pieces by name, and BENCHMARK.json holds up."""
from __future__ import annotations

import json
import os
import re
import shutil

import pytest

from chipbench import drivers, spec

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_every_cell_resolves(bench):
    for cell in bench["workloads"]:
        cfg = spec.config(bench, cell)
        assert cfg["name"] == cell["config"]
        tr = spec.traffic(cell["traffic"])
        drv = drivers.load(tr["path"])
        assert drv.ENGINE in ("has", "sched")
        for fn in ("install", "warm", "window", "spec_batch", "spec_backend",
                   "final_state", "lifetimes"):
            assert callable(getattr(drv, fn)), (tr["path"], fn)
        e2e = {m["name"] for m in spec.end_to_end(bench, cell["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = spec.per_layer(bench, cell["name"])
        assert layer, cell["name"]
        for m in layer:
            assert m["moves"] in e2e, (cell["name"], m["name"])
            assert callable(spec.reader(m["name"]))


def test_names_units_and_files(bench):
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[key]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    used = {c["config"] for c in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_config_files_state_what_they_cut(bench):
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["n_docs"] == cfg["n_entities"] * cfg["docs_per_entity"]
        assert cfg["has"]["doc_capacity"] == cfg["has"]["h_max"] \
            * cfg["has"]["k"]


def test_cell_and_metric_added_as_files_only(tmp_path, bench):
    """A later PR adds a configuration, a traffic mix and a metric by
    adding files and entries; the harness finds them by name."""
    here = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, here,
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces",
                                                  "__pycache__"))
    (here / "configs" / "extra.json").write_text(json.dumps(
        {"name": "extra", "d": 8}))
    (here / "traffic" / "burst.seq.json").write_text(json.dumps(
        {"path": "seq", "zipf_a": 1.3}))
    (here / "metrics" / "hops.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    new = dict(bench)
    new["configs"] = bench["configs"] + [
        {"name": "extra", "file": "chipbench/configs/extra.json"}]
    new["workloads"] = bench["workloads"] + [
        {"name": "extra.burst.seq", "config": "extra",
         "traffic": "burst.seq", "chips": 1}]
    new["per_layer"] = bench["per_layer"] + [
        {"name": "hops.burst", "unit": "1", "moves": "latency_mean_ms",
         "workloads": ["extra.burst.seq"]}]
    new["end_to_end"] = [dict(m, workloads=m["workloads"]
                              + ["extra.burst.seq"])
                         if m["name"] == "latency_mean_ms" else m
                         for m in bench["end_to_end"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))

    loaded = spec.load_benchmark(str(tmp_path))
    cell = spec.cell(loaded, "extra.burst.seq")
    assert spec.config(loaded, cell, root=str(tmp_path))["d"] == 8
    assert spec.traffic("burst.seq", here=str(here))["zipf_a"] == 1.3
    names = [m["name"] for m in spec.per_layer(loaded, "extra.burst.seq")]
    assert names == ["hops.burst"]
    assert spec.reader("hops.burst", here=str(here))(None) == 42.0
    # the old cells see nothing new
    assert "hops.burst" not in [m["name"] for m in spec.per_layer(
        loaded, "flat768.granola.seq")]
    with pytest.raises(FileNotFoundError):
        spec.reader("nothing.here", here=str(here))


def test_metric_without_list_follows_its_end_to_end_metric():
    bench = {"end_to_end": [{"name": "qps", "workloads": ["a"]},
                            {"name": "setup_s"}],
             "per_layer": [{"name": "x", "moves": "qps"},
                           {"name": "y", "moves": "setup_s"}]}
    assert [m["name"] for m in spec.per_layer(bench, "a")] == ["x", "y"]
    assert [m["name"] for m in spec.per_layer(bench, "b")] == ["y"]
