"""The comparison that decides ``correct``.

Five numbers, each against its limit in ``limits.json``:

``scan_gap``
    Over the window's requests answered by the exact cloud scan (all of
    them, or a seeded sample of at most ``max_scan_rows``): the widest gap
    by which the j-th served document's float64 score lies below the
    float64 j-th best of the whole corpus.
``score_err``
    Over every row of every cloud scan the window ran: the widest gap
    between a score the scan returned and the float64 score of the
    document it returned it for.  It reads the scan's precision whether or
    not a near-tie is there to be swapped.
``ingest_bad``
    Rows folded into the cache that are not exactly what was served (a
    served full-scan or shared result missing, extra, or altered), plus
    rows of the program's final cache state that differ, bit for bit, from
    a host replay of those rows.  Exact: limit 0.
``accept_bad``
    On the cache the window left, for a seeded sample of the window's
    queries sent through the timed speculation program: accept flags and
    homology scores that differ from the reference's homology of the same
    validation draft against the replayed cache, and validation drafts
    that differ from the served draft.  Exact: limit 0.
``draft_gap``
    For the same sample: the widest gap by which a draft's j-th document
    scores below the reference's lower bound on the j-th best draft.
``ivf_bad``
    Rows of the speculation program's IVF table that break nearest-centroid
    assignment (listed twice, listed in a bucket whose centroid trails the
    row's nearest by more than ``margin``, left out while no such bucket is
    full, an id out of range) plus listed slots whose vector is not the
    row's.  The draft bound takes the table as given once this reads 0.
    Exact: limit 0.

With ``control``, the control's readings stand in the compared numbers'
place (``control_in_place``): the reference one precision below the
configuration's, put where the program's answers were, has to come out as
not correct.
"""
from __future__ import annotations

import json
import os

import numpy as np

from chipbench import reference as ref

LIMITS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "limits.json")


def load_limits(config: dict, path: str = LIMITS_FILE) -> dict:
    with open(path) as f:
        limits = {k: v["limit"] for k, v in json.load(f)["limits"].items()}
    limits.update(config.get("limits", {}))
    return limits


def ingest_consistency(recorded_q, recorded_ids, emb: np.ndarray,
                       served: np.ndarray, accepts: np.ndarray) -> int:
    """The ingests must be the rejected requests' (query, served ids), in
    order."""
    rej = np.flatnonzero(~accepts)
    want_q, want_ids = emb[rej], served[rej]
    n = min(len(rej), len(recorded_q))
    bad = abs(len(rej) - len(recorded_q))
    if n:
        bad += int(((recorded_q[:n] != want_q[:n]).any(axis=1)
                    | (recorded_ids[:n] != want_ids[:n]).any(axis=1)).sum())
    return bad


def scan_numbers(corpus, corpus_np, queries, served, k: int,
                 control: bool) -> dict:
    """``scan_gap`` for the exact-scan answers (and, with ``control``, the
    same number for the reference at lower precisions in their place)."""
    if not len(queries):
        return {"scan_gap": 0.0, "scan_rows": 0, "host_scans": 0}
    ids, top, host_scans = ref.exact_topk(corpus, corpus_np, queries, k)
    got = ref.scores64(corpus_np, queries, served)
    gap = ref.shortfall(top, served, got)
    out = {"scan_gap": float(gap.max()), "scan_rows": len(queries),
           "scan_rows_over_1e-6": int((gap > 1e-6).sum()),
           "host_scans": host_scans,
           "served_equal_reference": int((served == ids).all(axis=1).sum())}
    if control:
        for mode in ("high", "bf16"):
            cs, cids = ref.device_topk_all(corpus, queries, k, mode)
            cgap = ref.shortfall(top, cids,
                                 ref.scores64(corpus_np, queries, cids))
            out[f"control_{mode}_scan_gap"] = float(cgap.max())
            out[f"control_{mode}_rows_wrong"] = int((cgap > 0).sum())
            out[f"control_{mode}_score_err"] = score_err(
                corpus_np, queries, cs, cids)
    return out


def score_err(corpus_np, queries, scores, ids) -> float:
    """Widest |returned score - float64 score of the returned id| over the
    valid ids."""
    exact = ref.scores64(corpus_np, queries, ids)
    ok = np.isfinite(exact)
    return float(np.max(np.abs(scores.astype(np.float64) - exact),
                        where=ok, initial=0.0))


def score_numbers(corpus_np, queries, scores, ids) -> dict:
    """``score_err`` over the cloud scans the window ran."""
    return {"score_err": score_err(corpus_np, queries, scores, ids),
            "score_rows": len(queries)}


def _gap(lb: np.ndarray, got_ids, got: np.ndarray) -> float:
    fin = np.isfinite(lb)
    ids = got_ids[fin]
    if (ids < 0).any() or len(set(ids.tolist())) < fin.sum():
        return 2.0
    return float(np.max(lb[fin] - got[fin], initial=0.0))


def spec_numbers(sample_q, prog: dict, cache: ref.CacheReplay, corpus_np,
                 centroids, bucket_ids, has: dict, margin: float = 1e-2,
                 control: bool = False) -> dict:
    """``accept_bad`` and ``draft_gap`` over the sampled speculations (and,
    with ``control``, ``draft_gap`` of the reference's own draft ranked by
    int8 codes)."""
    k, tau = has["k"], has["tau"]
    bound = ref.DraftBound(cache, corpus_np, centroids, bucket_ids, k,
                           has["nprobe"], margin, sample_q, control)
    accept_bad = 0
    gaps, cgaps = [], []
    for r, q in enumerate(sample_q):
        val, draft = prog["val_ids"][r], prog["draft_ids"][r]
        best = ref.homology_best(val, cache)
        accept_bad += int(bool(prog["accept"][r]) != (best / k > tau))
        accept_bad += int(round(float(prog["homology"][r]) * k) != best)
        accept_bad += int(not np.array_equal(val, draft))
        lb = bound(r, q)
        if control:
            lb, low = lb
            cgaps.append(float(np.max(lb - low, initial=0.0,
                                      where=np.isfinite(lb))))
        got = ref.scores64(corpus_np, q[None], draft[None])[0]
        gaps.append(_gap(lb, draft, got))
    out = {"accept_bad": accept_bad,
           "draft_gap": float(max(gaps, default=0.0)),
           "spec_rows": len(sample_q)}
    if control:
        out["control_int8_draft_gap"] = float(max(cgaps, default=0.0))
    return out


def ivf_numbers(corpus, corpus_np, centroids, bucket_ids, vecs_wrong: int,
                margin: float = 1e-2) -> dict:
    """``ivf_bad`` and its parts.  ``margin``: how far one bfloat16 pass,
    the TPU's default for the program's float32 assignment, may misorder
    two centroid scores of unit vectors.  Its rounding (2^-8 of each
    factor) moves a score of d=768 spread-out components by about 1e-4,
    so 1e-2 sits some 60 deviations out."""
    parts = ref.ivf_mismatch(corpus, corpus_np, centroids, bucket_ids,
                             margin)
    bad = (parts["ivf_bad_ids"] + parts["ivf_twice"] + parts["ivf_far"]
           + parts["ivf_dropped"] + vecs_wrong)
    return dict(parts, ivf_bad=bad, ivf_vecs_wrong=vecs_wrong)


# compared number -> the control reading that takes its place
CONTROL_FOR = {"scan_gap": "control_high_scan_gap",
               "score_err": "control_high_score_err",
               "draft_gap": "control_int8_draft_gap"}


def control_in_place(numbers: dict) -> dict:
    """The control's readings in the compared numbers' place; the
    program's own kept as ``program_<name>``."""
    out = dict(numbers)
    for name, ctl in CONTROL_FOR.items():
        out[f"program_{name}"] = numbers[name]
        out[name] = numbers[ctl]
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(every compared number within its limit, {name: {value, limit}})."""
    shown = {name: {"value": numbers[name], "limit": limits[name]}
             for name in ("scan_gap", "score_err", "draft_gap", "accept_bad",
                          "ingest_bad", "ivf_bad")
             if name in numbers}
    return all(v["value"] <= v["limit"] for v in shown.values()), shown
