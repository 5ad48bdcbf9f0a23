"""Work counts against hand sums, and the peaks table."""
from __future__ import annotations

import json
import os

import pytest

from chipbench import spec, work


def _config(name):
    with open(os.path.join(spec.HERE, "configs", name)) as f:
        return json.load(f)


def _at_width(name, d, n):
    """A configuration file's settings at another width and corpus size
    (the bge-large width, 1024, has no configuration that fits a chip)."""
    cfg = _config(name)
    cfg.update(d=d, n_docs=n)
    cfg["has"]["bucket_capacity"] = work.bucket_capacity(n, 2048)
    return cfg


@pytest.mark.parametrize("name,d,n,cap", [
    ("contriever-flat-1m-d768.json", 768, 1_000_000, 977),
    ("contriever-flat-1m-d768.json", 1024, 600_000, 586)])
def test_work_counts_by_hand(name, d, n, cap):
    cfg = _at_width(name, d, n)
    assert cfg["d"] == d and cfg["n_docs"] == n
    has = cfg["has"]
    # one B=16 scan and one B=1 scan: the corpus twice, 17 query rows
    w = work.scan_work(cfg["n_docs"], d, calls=2, rows=17)
    assert w.bytes == 2 * n * d * 4
    assert w.flops == 2 * 17 * n * d
    # one B=32 speculation batch
    s = work.spec_work(has, d, calls=1, rows=32)
    by_hand = (2048 * d * 4 + 5000 * 10 * 4 + 50_000 * d * 4
               + 32 * 16 * cap * d * 4)
    assert s.bytes == by_hand
    assert s.flops == 32 * 2 * d * (2048 + 50_000 + 16 * cap)
    assert has["bucket_capacity"] == work.bucket_capacity(n, 2048) == cap
    if d == 768:
        assert abs(w.bytes / 2 - 3.072e9) < 1e6
        assert abs(s.bytes - 1.698e9) < 1e7


def test_roofline_and_bounds():
    peaks = work.peaks_for("TPU v5 lite")
    w = work.scan_work(1_000_000, 768, calls=1, rows=1)
    t, bound = work.min_time(w, peaks)
    assert bound == "hbm" and t == pytest.approx(3.072e9 / 819e9)
    assert work.roofline_pct(w, 2 * t, peaks) == pytest.approx(50.0)
    assert work.roofline_pct(w, 0.0, peaks) is None
    compute = work.Work(bytes=1.0, flops=197e12)
    assert work.min_time(compute, peaks) == (1.0, "compute")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks_for("TPU v9 imaginary")
    table = work.load_peaks()["devices"]
    assert all(v["source"] for v in table.values())
