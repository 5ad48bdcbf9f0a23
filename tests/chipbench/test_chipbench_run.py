"""The whole harness at a tiny size on the CPU: the look for a chip is
skipped, everything else runs as on the chip.  Sound runs are correct;
the timed path broken underneath is not."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench import run as bench_run
from chipbench_tiny import tiny  # noqa: F401  (the fixture)


@pytest.mark.parametrize("seed", [11, 2 ** 32 + 7])
def test_sound_run_is_correct(tiny, seed):
    res = tiny("seq", seed=seed)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    assert set(m) == {"latency_mean_ms", "latency_p95_ms", "doc_hit",
                      "setup_s"}
    assert 0 < m["doc_hit"]["value"] <= 1
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"scan_gap", "score_err", "draft_gap",
                                  "accept_bad", "ingest_bad", "ivf_bad"}
    assert res["device"]["count"] == 1


def test_no_chip_no_result(capsys):
    """On a CPU the command exits non-zero and prints no result."""
    assert bench_run.main(["--workload", "flat768.granola.seq", "--seed",
                           "1", "--seconds", "1", "--trace", "0"]) != 0
    assert "{" not in capsys.readouterr().out


def test_traced_run_reads_per_layer_metrics(tiny, monkeypatch):
    """A traced run reports the per-layer metrics from the reduced trace
    (the CPU has no device plane, so the reduction is stood in for)."""
    from chipbench import tracing

    def fake_reduce(path, span_names):
        return tracing.Reduced(
            window_s=0.5, busy_s=0.3,
            span_device_s={"spec": 0.1, "cloud_scan": 0.15, "ingest": 0.02},
            span_count={}, device_ops=[("fusion", 0.2)],
            idle_gaps=[("none", 0.2)], n_devices=1)
    monkeypatch.setattr(tracing, "reduce_file", fake_reduce)
    res = tiny("seq", trace=True)
    m = res["metrics"]
    assert set(m) == {"dar.seq", "spec_roofline.seq", "scan_roofline.seq",
                      "idle_share.seq"}
    assert m["idle_share.seq"]["value"] == pytest.approx(40.0)
    assert 0 < m["dar.seq"]["value"] < 100
    assert res["device"]["busy_s"] == 0.3
    assert res["breakdown"]["device_ops"] == [["fusion", 0.2]]
    assert res["correct"]


@pytest.mark.parametrize("seed", [11, 2 ** 32 + 7])
def test_control_reads_past_the_limits(tiny, seed):
    """The control: the reference put in the program's place one precision
    below the configuration's (the scan at three bfloat16 passes, the
    draft ranked by int8 codes) comes out as not correct, through the
    same comparison that passes the program's own answers."""
    res = tiny("seq", seed=seed, control=True)
    assert not res["correct"]
    checks = res["checks"]
    for name in ("score_err", "draft_gap"):
        assert checks[name]["value"] > checks[name]["limit"], name
    assert res["control"]["control_bf16_scan_gap"] > \
        checks["scan_gap"]["limit"]


# -- the timed path broken underneath: correct must come out false --------

def _alter_ids(ids):
    """An answer altered where it is produced: slot 0 takes the id of the
    document just after the served k-th (a valid, worse document)."""
    ids = np.asarray(ids).copy()
    ids[:, 0] = (ids[:, -1] + 1) % 2000
    return ids


def _break_search(monkeypatch, how):
    from repro.retrieval import service

    orig = service.LocalFlatBackend.search

    def search(self, q_embs):
        s, ids = orig(self, q_embs)
        ids = np.asarray(ids)
        if how == "alter":
            ids = _alter_ids(ids)
        elif how == "half":
            # half of the batch left out: its rows get the answers of the
            # rows that were kept
            h = max(1, len(ids) // 2)
            ids = np.concatenate([ids[:h], ids[:len(ids) - h]])
        import jax.numpy as jnp
        return s, jnp.asarray(ids)
    monkeypatch.setattr(service.LocalFlatBackend, "search", search)


def test_altered_answer_is_caught(tiny, monkeypatch):
    _break_search(monkeypatch, "alter")
    res = tiny("seq")
    assert not res["correct"]
    assert res["checks"]["scan_gap"]["value"] > \
        res["checks"]["scan_gap"]["limit"]


def test_half_batch_left_out_is_caught(tiny, monkeypatch):
    """The window scans one row at a time; the batched scan whose answers
    set-up folds into the cache is what loses half."""
    _break_search(monkeypatch, "half")
    res = tiny("seq")
    assert not res["correct"]
    assert res["checks"]["scan_gap"]["value"] > \
        res["checks"]["scan_gap"]["limit"]


def _break_ivf(monkeypatch, how):
    """The IVF build broken: the fullest bucket's rows dropped, or one of
    them moved into the emptiest bucket (vectors moved with their ids)."""
    from repro.retrieval.ivf import IVFIndex
    from repro.serving import engine

    orig = engine.build_ivf

    def build(*a, **k):
        idx = orig(*a, **k)
        ids = np.asarray(idx.bucket_ids).copy()
        vecs = np.asarray(idx.bucket_vecs).copy()
        held = (ids >= 0).sum(axis=1)
        src, dst = int(held.argmax()), int(held.argmin())
        if how == "drop":
            ids[src], vecs[src] = -1, 0.0
        else:
            n = held[src] - 1
            ids[dst, held[dst]], vecs[dst, held[dst]] = ids[src, n], vecs[src, n]
            ids[src, n], vecs[src, n] = -1, 0.0
        import jax.numpy as jnp
        return IVFIndex(centroids=idx.centroids,
                        bucket_vecs=jnp.asarray(vecs),
                        bucket_ids=jnp.asarray(ids),
                        bucket_counts=jnp.asarray((ids >= 0).sum(axis=1),
                                                  dtype=jnp.int32))
    monkeypatch.setattr(engine, "build_ivf", build)


@pytest.mark.parametrize("how", ["drop", "move"])
def test_broken_ivf_build_is_caught(tiny, monkeypatch, how):
    """Rows of the speculation index dropped, or put in a far bucket: the
    reference checks the program's table, it does not take it as given."""
    _break_ivf(monkeypatch, how)
    res = tiny("seq")
    assert not res["correct"]
    assert res["checks"]["ivf_bad"]["value"] > 0


def test_ivf_vectors_checked_against_the_corpus():
    """A listed slot whose vector is not its row's counts."""
    import jax.numpy as jnp

    from chipbench import reference

    rng = np.random.default_rng(0)
    corpus = rng.normal(size=(32, 8)).astype(np.float32)
    ids = np.array([[0, 5, -1], [7, -1, -1]], np.int32)
    vecs = np.zeros((2, 3, 8), np.float32)
    vecs[ids >= 0] = corpus[ids[ids >= 0]]
    assert reference.bucket_vecs_wrong(jnp.asarray(corpus), vecs, ids) == 0
    vecs[0, 1, 3] += 1.0
    vecs[1, 2, 0] = 5.0          # a pad slot: not read
    assert reference.bucket_vecs_wrong(jnp.asarray(corpus), vecs, ids) == 1


@pytest.mark.parametrize("where", ["window", "bulk"])
def test_state_returned_unchanged_is_caught(tiny, monkeypatch, where):
    """A cache ingest that returns its state unchanged: the window's
    ``cache_update``, or the bulk fold of set-up's answers."""
    from repro.core import has
    from repro.serving import engine as loop

    if where == "window":
        monkeypatch.setattr(loop, "cache_update",
                            lambda cfg, state, *a, **k: state)
    else:
        monkeypatch.setattr(has, "cache_update_chunked",
                            lambda cfg, state, *a, **k: state)
    res = tiny("seq")
    assert not res["correct"]
    assert res["checks"]["ingest_bad"]["value"] > 0


def test_altered_speculation_is_caught(tiny, monkeypatch):
    """Speculation's answer altered where it is produced: accept flags
    flipped."""
    from repro.core import has
    from repro.serving import engine as loop

    orig = has.speculate_batch

    def flipped(*a, **k):
        out = dict(orig(*a, **k))
        out["accept"] = ~out["accept"]
        return out
    monkeypatch.setattr(has, "speculate_batch", flipped)
    monkeypatch.setattr(loop, "speculate_batch", flipped)
    res = tiny("seq")
    assert not res["correct"]
    assert res["checks"]["accept_bad"]["value"] > 0
