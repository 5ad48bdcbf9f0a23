"""The cell ``flat1024.granola.sched`` at a tiny size on the CPU: a d=1024
world under Granola-EQ traffic through ``ContinuousBatchingScheduler``.
Popular traffic repeats head entities inside one speculation batch, so the
sharing election serves followers, and the checks see them.  Also the
files the cell is made of, and the ``share_ms`` reader."""
from __future__ import annotations

import copy
import json
import os
import re
import types

import pytest

from chipbench import spec
from chipbench_tiny import TINY_CONFIG, TINY_TRAFFIC, tiny  # noqa: F401
from test_chipbench_sched import sched_clock  # noqa: F401  (the fixture)

CELL = "flat1024.granola.sched"

TINY_1024 = copy.deepcopy(TINY_CONFIG)
TINY_1024.update(d=1024, encoder_profile={
    "entity_weight": 1.1, "attr_weight_doc": 0.60, "attr_weight_query": 0.70,
    "noise_doc": 0.95, "noise_query": 1.05})
TINY_1024["builder_args"] = ["--retrieval-backend", "flat", "--entities", "400",
                             "--dim", "1024", "--k", "10", "--tau", "0.2",
                             "--h-max", "64"]
GRANOLA = {"dataset": "granola", "zipf_a": 1.12, "p_uncovered": 0.42}


def _window_counts(err: str) -> dict:
    line = next(ln for ln in err.splitlines() if ln.startswith("[window]"))
    return {k: int(v) for k, v in
            re.findall(r" (draft|reval|shared|full)=(\d+)", line)}


@pytest.mark.parametrize("seed", [11, 2 ** 32 + 7])
def test_granola_sched_at_d1024_is_correct_with_followers(
        tiny, sched_clock, capsys, seed):
    res = tiny("sched", seed=seed, seconds=5, config=TINY_1024,
               traffic=GRANOLA)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 3 * TINY_TRAFFIC["sched"]["segment_requests"]
    assert res["failed"] == 0
    counts = _window_counts(capsys.readouterr().err)
    assert counts["shared"] > 0, counts
    for name in ("follow_bad", "follow_gap"):
        assert res["checks"][name]["value"] <= res["checks"][name]["limit"]


def test_granola_sched_at_d1024_control_is_not_correct(tiny, sched_clock):
    """The scan one precision below the configuration's (three bfloat16
    passes) reads past ``score_err``'s limit at d=1024 too."""
    res = tiny("sched", seconds=5, config=TINY_1024, traffic=GRANOLA,
               control=True)
    assert not res["correct"]
    score_err = res["checks"]["score_err"]
    assert score_err["value"] > score_err["limit"]


def test_traced_run_reads_share_ms(tiny, sched_clock, monkeypatch, capsys):
    """The election's device time over the window's speculation calls,
    read from the ``share`` spans of a traced run (the CPU trace has no
    device plane, so a recorded reduction stands in for it)."""
    import chipbench_tiny
    from chipbench import tracing

    def fake_reduce(path, span_names):
        assert "share" in span_names
        return tracing.Reduced(
            window_s=0.5, busy_s=0.2,
            span_device_s={"spec": 0.05, "share": 0.012, "cloud_scan": 0.1},
            span_count={}, device_ops=[("fusion", 0.1)],
            idle_gaps=[("none", 0.3)], n_devices=1)

    def bench_with_share_ms(path):
        bench = tiny_bench(path)
        bench["per_layer"].append({
            "name": f"share_ms.{path}", "unit": "ms", "moves": "qps",
            "workloads": [f"tiny.{path}"]})
        return bench
    tiny_bench = chipbench_tiny.tiny_bench
    monkeypatch.setattr(tracing, "reduce_file", fake_reduce)
    monkeypatch.setattr(chipbench_tiny, "tiny_bench", bench_with_share_ms)
    res = tiny("sched", seconds=5, trace=True, config=TINY_1024,
               traffic=GRANOLA)
    assert res["correct"], res["checks"]
    line = next(ln for ln in capsys.readouterr().err.splitlines()
                if ln.startswith("[window]"))
    spec_calls = int(re.search(r" spec_calls=(\d+)", line).group(1))
    assert spec_calls > 0
    assert res["metrics"]["share_ms.sched"]["value"] == pytest.approx(
        12.0 / spec_calls)


def test_share_ms_reads_nothing_without_its_spans():
    read = spec.reader("share_ms.flat1024")
    assert read(types.SimpleNamespace(trace=None, spec_calls=10)) is None
    no_share = types.SimpleNamespace(span_device_s={"spec": 0.1})
    assert read(types.SimpleNamespace(trace=no_share, spec_calls=10)) is None
    share = types.SimpleNamespace(span_device_s={"share": 0.02})
    assert read(types.SimpleNamespace(trace=share, spec_calls=10)) \
        == pytest.approx(2.0)
    assert read(types.SimpleNamespace(trace=share, spec_calls=0)) is None


# -- the files the cell is made of -------------------------------------------

def _load(rel: str) -> dict:
    with open(os.path.join(spec.ROOT, rel)) as f:
        return json.load(f)


def test_config_is_the_d768_deployment_with_the_bge_large_encoder():
    from repro.data.synthetic import ENCODERS

    bench = spec.load_benchmark()
    cfg = spec.config(bench, spec.cell(bench, CELL))
    base = _load("chipbench/configs/contriever-flat-1m-d768.json")
    assert set(cfg) == set(base)
    assert cfg["d"] == 1024 and cfg["encoder"] == "bge-large"
    assert cfg["encoder_profile"] == ENCODERS["bge-large"]
    assert cfg["builder_args"][cfg["builder_args"].index("--dim") + 1] \
        == "1024"
    for key in ("has", "precision", "reduced", "limits", "n_docs",
                "n_entities", "docs_per_entity", "attrs_per_entity",
                "attrs_per_doc", "dtype", "cloud"):
        assert cfg[key] == base[key], key


def test_traffic_is_granola_on_the_default_scheduler():
    bench = spec.load_benchmark()
    tr = spec.traffic(spec.cell(bench, CELL)["traffic"])
    seq = spec.traffic("granola.seq")
    sched = spec.traffic("triviaqa.sched")
    assert tr["path"] == "sched" and tr["scheduler"] == sched["scheduler"]
    for key in ("dataset", "zipf_a", "p_uncovered", "rank_seed"):
        assert tr[key] == seq[key], key
    for key in ("warm_requests", "segment_requests", "segments",
                "stream_requests"):
        assert tr[key] == sched[key], key


def test_cell_metrics_list_only_the_new_cell():
    bench = spec.load_benchmark()
    names = {m["name"] for m in spec.per_layer(bench, CELL)}
    assert names == {"dar.flat1024", "spec_roofline.flat1024",
                     "scan_roofline.flat1024", "idle_share.flat1024",
                     "share_ms.flat1024"}
    for m in bench["per_layer"]:
        if m["name"] in names:
            assert m["workloads"] == [CELL] and m["moves"] == "qps"
    e2e = {m["name"] for m in spec.end_to_end(bench, CELL)}
    assert e2e == {"qps", "doc_hit", "setup_s"}
