"""Tiny CPU stand-ins for the benchmark's cells: the whole harness at a
size a test run holds, with the look for a chip skipped.

Not a ``conftest.py``: the repository's tests import names from their own
``tests/conftest.py`` as the module ``conftest``, which a second file of
that name would shadow.  Test modules import the ``tiny`` fixture from
here."""
from __future__ import annotations

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY_CONFIG = {
    "name": "tiny", "n_entities": 400, "docs_per_entity": 5, "n_docs": 2000,
    "attrs_per_entity": 12, "attrs_per_doc": 4, "d": 64,
    "encoder_profile": {"entity_weight": 1.0, "attr_weight_doc": 0.55,
                        "attr_weight_query": 0.65, "noise_doc": 1.0,
                        "noise_query": 1.1},
    "has": {"k": 10, "tau": 0.2, "h_max": 64, "doc_capacity": 640,
            "n_buckets": 250, "bucket_capacity": 16, "nprobe": 16},
    "limits": {"scan_gap": 2.0 ** -22},
    "builder_args": ["--retrieval-backend", "flat", "--entities", "400",
                     "--dim", "64", "--k", "10", "--tau", "0.2",
                     "--h-max", "64"],
}

TINY_TRAFFIC = {
    "seq": {"path": "seq", "dataset": "granola", "zipf_a": 1.12,
            "p_uncovered": 0.42, "rank_seed": 7, "warm_requests": 48,
            "warm_batch": 16, "warm_steps": 8, "stream_requests": 4000},
    "sched": {"path": "sched", "dataset": "triviaqa", "zipf_a": 1.04,
              "p_uncovered": 0.05, "rank_seed": 7, "warm_requests": 96,
              "segment_requests": 320, "segments": 2,
              "stream_requests": 96 + 2 * 320,
              "scheduler": {"max_spec_batch": 32, "full_batch": 16,
                            "ingest_batch": 32, "share": True,
                            "share_tau": 0.1, "revalidate": True,
                            "ingest_followers": True,
                            "follower_score_weighted": True,
                            "cloud_workers": 1, "edge_replicas": 1,
                            "n_tenants": 1, "overload_policy": "none",
                            "fault_plan": None}},
}
E2E = {"seq": ["latency_mean_ms", "latency_p95_ms"], "sched": ["qps"]}


def tiny_bench(path: str) -> dict:
    """A benchmark of one tiny cell on the ``path`` traffic."""
    name = f"tiny.{path}"
    e2e = E2E.get(path, E2E["seq"])
    return {
        "configs": [{"name": "tiny", "file": "unused"}],
        "workloads": [{"name": name, "config": "tiny",
                       "traffic": f"tiny.{path}", "chips": 1}],
        "end_to_end": [{"name": m, "unit": "x", "workloads": [name]}
                       for m in e2e]
        + [{"name": "doc_hit", "unit": "fraction"},
           {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": f"{m}.{path}", "unit": "%", "moves": e2e[0],
             "workloads": [name]}
            for m in ("dar", "spec_roofline", "scan_roofline",
                      "idle_share")],
    }


@pytest.fixture
def tiny(tmp_path):
    """``tiny(path, seed=..., seconds=..., trace=..., traffic={...})`` ->
    result; ``traffic`` overrides keys of the path's tiny traffic mix."""
    from chipbench import run, work

    def go(path, seed=11, seconds=0.5, trace=False, config=None,
           traffic=None, **kw):
        base = TINY_TRAFFIC.get(path, TINY_TRAFFIC["seq"])
        return run.run(f"tiny.{path}", seed, seconds, trace,
                       bench=tiny_bench(path),
                       config=copy.deepcopy(config or TINY_CONFIG),
                       traffic=dict(base, **(traffic or {})),
                       peaks=work.peaks_for("TPU v5 lite"),
                       chips_required=False, cache=False,
                       trace_dir=str(tmp_path / "trace"), **kw)
    return go
