"""The comparison that decides ``correct``.

Each number against its limit in ``limits.json``; a path that shares no
answers has no ``follow_*`` numbers:

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
    In every cache lifetime (one for a single long-running cache, one a
    ``serve`` for a path that starts each from empty): rows folded into
    the cache that are not exactly what the cloud answered and was served
    (a served full-scan or shared result missing, extra, repeated or
    altered); plus rows of the cache each lifetime left that differ, bit
    for bit, from a host replay of its rows from an empty cache (the last
    one's final state whole, an earlier one's ids, validity and
    pointers).  Exact: limit 0.
``accept_bad``
    On the cache the window left, for a seeded sample of the window's
    queries sent through the timed speculation program: accept flags and
    homology scores that differ from the reference's homology of the same
    validation draft against the replayed cache, and validation drafts
    that differ from the served draft.  Where the path records its
    speculation, also every window row served a draft that is not its
    speculation's, or accepted there and served something else
    (``draft_served_bad``).  Exact: limit 0.
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
``follow_bad``
    Rows served another request's answer whose leader paid no exact scan
    of its own, whose served set is not the leader's, or whose sharing
    election fails when recomputed on the host.  Exact: limit 0.
``follow_gap``
    The widest gap by which such a row's j-th served document scores, in
    float64 under its own query, below the j-th best of the leader's set.

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
from chipbench.drivers import row_lookup

LIMITS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "limits.json")


def load_limits(config: dict, path: str = LIMITS_FILE) -> dict:
    with open(path) as f:
        limits = {k: v["limit"] for k, v in json.load(f)["limits"].items()}
    limits.update(config.get("limits", {}))
    return limits


def ingest_consistency(life, emb: np.ndarray, ids: np.ndarray,
                       cloud: np.ndarray) -> int:
    """Recorded ingests of one cache lifetime that are not exactly its
    cloud-answered rows (query and served ids), each once, in any order:
    rows missing, extra, repeated or altered."""
    rows = life.rows[life.preloaded:]
    want = rows[cloud[rows]]
    at = row_lookup(emb, want)
    seen: set[int] = set()
    bad = 0
    for q, i in zip(life.ingest_q, life.ingest_ids):
        r = at.get(q.tobytes())
        if r is None or r in seen or not np.array_equal(i, ids[r]):
            bad += 1
        else:
            seen.add(r)
    return bad + len(want) - len(seen)


def ingest_numbers(lives, emb, ids, cloud, state_np: dict, corpus_np,
                   has: dict) -> tuple[dict, ref.CacheReplay]:
    """``ingest_bad`` over every cache lifetime: its recorded ingests
    against its cloud-answered rows, and the lifetime replayed on the host
    from an empty cache (its bulk-folded rows, then its recorded ingests in
    program order) against the cache it left, bit for bit: the fields its
    ``end`` holds, and the program's final state whole for the last.
    Returns the numbers and the last lifetime's replay."""
    bad_rows = sum(ingest_consistency(life, emb, ids, cloud)
                   for life in lives)
    diff: dict = {}
    for j, life in enumerate(lives):
        last = j == len(lives) - 1
        if not last and life.end is None:
            continue
        if not last and not life.end:
            diff["cache_reused"] = diff.get("cache_reused", 0) + 1
            continue
        cache = ref.CacheReplay(has["h_max"], has["k"], has["doc_capacity"],
                                emb.shape[1])
        pre = life.rows[:life.preloaded]
        for q, i in zip(np.concatenate([emb[pre], life.ingest_q]),
                        np.concatenate([ids[pre], life.ingest_ids])):
            cache.ingest(q, i)
        got = ref.state_mismatch(cache, state_np if last else life.end,
                                 corpus_np)
        for key, v in got.items():
            diff[key] = diff.get(key, 0) + v
    return dict(diff, ingest_bad=bad_rows + sum(diff.values()),
                ingest_consistency_bad=bad_rows,
                ingest_rows=len(lives[-1].ingest_q),
                lifetimes=len(lives)), cache


def draft_served_bad(drafted: np.ndarray, served: np.ndarray,
                     spec: dict) -> int:
    """Rows served a draft that is not their recorded speculation's, rows
    whose speculation accepted but that were served something else, rows
    speculated other than once, and speculated rows that are no
    request's."""
    bad = drafted & (served != spec["draft_ids"]).any(axis=1)
    bad |= spec["accept"] & ~drafted
    bad |= spec["seen"] != 1
    return int(bad.sum()) + spec["stray"]


def _low_order(queries, corpus_np, ids) -> np.ndarray:
    """``ids`` re-ranked by one bfloat16 pass: bfloat16 operands, products
    summed in float64 (no rounding there): the control of a float32
    re-rank."""
    import ml_dtypes

    def bf(x):
        return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
            np.float64)
    s = np.einsum("rkd,rd->rk", bf(corpus_np[np.maximum(ids, 0)]),
                  bf(queries))
    s = np.where(ids >= 0, s, -np.inf)
    return np.take_along_axis(ids, np.argsort(-s, axis=1, kind="stable"),
                              axis=1)


def follow_numbers(emb, served, leader, exact, spec: dict, tau: float,
                   corpus_np, control: bool = False) -> dict:
    """``follow_bad`` and ``follow_gap`` over every row served another
    row's answer (``leader`` >= 0).

    ``follow_bad`` counts followers whose leader did not pay its own exact
    scan, whose served set is not the leader's, whose speculation accepted,
    or whose election fails when recomputed: the share of the follower's
    validation draft ids that the leader's draft holds, in float32, not
    above ``tau``.  ``follow_gap``: the widest gap by which a follower's
    j-th served document scores, in float64 under its own query, below the
    j-th best of its leader's served set."""
    f = np.flatnonzero(leader >= 0)
    out = {"follow_rows": len(f)}
    if not len(f):
        out.update(follow_bad=0, follow_gap=0.0)
        if control:
            out["control_bf16_follow_gap"] = 0.0
        return out
    lead = leader[f]
    k = served.shape[1]
    bad = ~exact[lead]
    bad |= (np.sort(served[f], axis=1) != np.sort(served[lead], axis=1)).any(
        axis=1)
    vf, vl = spec["val_ids"][f], spec["val_ids"][lead]
    overlap = ((vf[:, :, None] == vl[:, None, :]).any(axis=2)
               & (vf >= 0)).sum(axis=1)
    bad |= ~(overlap.astype(np.float32) / np.float32(k) > np.float32(tau))
    bad |= spec["accept"][f] | (spec["seen"][f] != 1)
    q = emb[f]
    best = np.sort(ref.scores64(corpus_np, q, served[lead]), axis=1)[:, ::-1]
    gap = ref.shortfall(best, served[f], ref.scores64(corpus_np, q, served[f]))
    out.update(follow_bad=int(bad.sum()), follow_gap=float(gap.max()),
               follow_election_bad=int((overlap.astype(np.float32)
                                        / np.float32(k)
                                        <= np.float32(tau)).sum()))
    if control:
        low = _low_order(q, corpus_np, served[lead])
        cgap = ref.shortfall(best, low, ref.scores64(corpus_np, q, low))
        out["control_bf16_follow_gap"] = float(cgap.max())
    return out


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
               "draft_gap": "control_int8_draft_gap",
               "follow_gap": "control_bf16_follow_gap"}
COMPARED = ("scan_gap", "score_err", "draft_gap", "accept_bad", "ingest_bad",
            "ivf_bad", "follow_bad", "follow_gap")


def control_in_place(numbers: dict) -> dict:
    """The control's readings in the compared numbers' place; the
    program's own kept as ``program_<name>``."""
    out = dict(numbers)
    for name, ctl in CONTROL_FOR.items():
        if name in numbers:
            out[f"program_{name}"] = numbers[name]
            out[name] = numbers[ctl]
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(every compared number within its limit, {name: {value, limit}})."""
    shown = {name: {"value": numbers[name], "limit": limits[name]}
             for name in COMPARED if name in numbers}
    return all(v["value"] <= v["limit"] for v in shown.values()), shown
