"""Work each layer needs, from the configuration's shapes, and the peaks.

The counts are of what the algorithm has to read and compute, whatever
implements it:

* cloud scan (exact flat search), per call of ``b`` queries: the f32
  corpus once, ``N * d * 4`` bytes, and ``2 * b * N * d`` flops;
* speculation, per call: the centroids ``C * d * 4``, the validation table
  ``H * k * 4`` and the doc store ``Dc * d * 4`` once, and for each query
  its probed buckets ``nprobe * cap * d * 4`` bytes; flops ``2 * d`` per
  scored (query, vector) pair over the centroids, the doc store and the
  probed buckets.  This is the Pallas path's term of the program's
  ``speculation_bytes_moved`` with the matching matmul flops.

A roofline share is the least time the chip could take, the larger of
bytes over peak bandwidth and flops over peak compute, over the device
time measured for the same calls.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


@dataclasses.dataclass(frozen=True)
class Work:
    bytes: float
    flops: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.bytes + other.bytes, self.flops + other.flops)


def bucket_capacity(n_docs: int, n_buckets: int,
                    capacity_factor: float = 2.0) -> int:
    """Rows per IVF bucket: twice the mean bucket, as the index builds it."""
    return int(math.ceil(n_docs / n_buckets * capacity_factor))


def scan_work(n_docs: int, d: int, calls: int, rows: int) -> Work:
    """``calls`` exact flat scans serving ``rows`` queries in all."""
    return Work(bytes=calls * n_docs * d * 4.0,
                flops=2.0 * rows * n_docs * d)


def spec_work(has: dict, d: int, calls: int, rows: int) -> Work:
    """``calls`` speculation batches serving ``rows`` queries in all."""
    c, h, k = has["n_buckets"], has["h_max"], has["k"]
    dc, cap, nprobe = has["doc_capacity"], has["bucket_capacity"], \
        min(has["nprobe"], has["n_buckets"])
    per_call = c * d * 4.0 + h * k * 4.0 + dc * d * 4.0
    per_row = nprobe * cap * d * 4.0
    flops_row = 2.0 * d * (c + dc + nprobe * cap)
    return Work(bytes=calls * per_call + rows * per_row,
                flops=rows * flops_row)


def load_peaks(path: str = PEAKS_FILE) -> dict:
    with open(path) as f:
        return json.load(f)


def peaks_for(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    table = load_peaks(path)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def min_time(work: Work, peaks: dict) -> tuple[float, str]:
    """(least seconds the chip could take, which bound sets it)."""
    t_mem = work.bytes / peaks["hbm_bytes_per_s"]
    t_flop = work.flops / peaks["matmul_flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_flop else (t_flop, "compute")


def roofline_pct(work: Work, device_s: float, peaks: dict) -> float | None:
    """Share of the roofline in percent, or None with no device time."""
    if not device_s > 0:
        return None
    return 100.0 * min_time(work, peaks)[0] / device_s
