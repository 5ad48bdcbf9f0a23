"""``sched``: saturated admission through ``ContinuousBatchingScheduler``.

One ``serve(queries, arrivals=None)`` admits a whole segment of the stream
at once.  Speculation runs ``max_spec_batch`` requests a call; each
rejected draft is elected a leader or a follower (``intra_batch_share``
against every pending leader); leaders are re-validated against the cache
at cloud dispatch (``_revalidate``) and scanned ``full_batch`` at a time
(``backend.search``); followers are served their leader's answer re-ranked
by their own query; the answers are folded into the cache
``ingest_batch`` rows a call, in completion order
(``cache_update_chunked``).  Which program runs when is decided by the
scheduler's virtual clock (``serving/latency.py``); the benchmark measures
only the host wall time around ``serve``, and prints the virtual clock's
latencies on the ``[modeled]`` line alone.

Every ``serve`` starts from an empty cache, so every segment is a cache
lifetime of its own.  Set-up serves one short segment (``warm_requests``),
which compiles every program the window runs.  The window serves the
stream's ``segments`` segments of ``segment_requests`` requests in turn,
from the first again when they run out (the same work: the cache starts
empty), until ``--seconds`` have passed; the segment under way finishes
and counts.  ``qps`` is the requests of those segments over their wall
time.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from chipbench.drivers import (Lifetime, Warm, Window, row_lookup,
                               stack_rows)

ENGINE = "sched"
LOAD = "saturated: a segment's requests all admitted at once; lateness is the host gap between one segment's return and the next one's start"
CHANNELS = ("draft", "reval", "shared", "full")
# the cache a segment left, kept by reference (no copy) for the check;
# the vectors are left out: the last segment's are compared whole
END_FIELDS = ("query_doc_ids", "query_valid", "q_ptr", "doc_ids", "d_ptr")


def install(probe, engine) -> None:
    """Wrap the entries ``ContinuousBatchingScheduler.serve`` calls; each
    ``serve`` starts a cache lifetime."""
    from repro.serving import scheduler as loop

    def end_of_lifetime(args, kwargs):
        probe.keep("cache_end", {f: getattr(engine.state, f)
                                 for f in END_FIELDS})
        probe.new_lifetime()
    probe.patch(engine, "serve", before=end_of_lifetime)
    # speculate_batch(cfg, state, index, q_embs [B, d], ...)
    probe.patch(loop, "speculate_batch", "spec",
                record=lambda args, kwargs, out: (args[3], out))
    probe.patch(loop, "intra_batch_share", "share")
    probe.patch(engine, "_revalidate", "reval")
    probe.patch(engine.s.backend, "search", "cloud_scan",
                record=lambda args, kwargs, out: (args[0], out))
    # cache_update_chunked(cfg, state, q_embs [n, d], full_ids [n, k], ...)
    probe.patch(loop, "cache_update_chunked", "ingest",
                record=lambda args, kwargs, out: (args[2], args[3]))


def spec_batch(engine) -> int:
    return engine.sched.max_spec_batch


def spec_backend(engine):
    return engine.spec_backend


def final_state(engine):
    return engine.state


def _check_scheduler(engine, stated: dict) -> None:
    """The scheduler must run as the traffic file states it."""
    sc = engine.sched
    got = {"max_spec_batch": sc.max_spec_batch, "full_batch": sc.full_batch,
           "ingest_batch": sc.ingest_batch, "share": sc.share,
           "share_tau": float(engine._share_tau),
           "revalidate": sc.revalidate,
           "ingest_followers": sc.ingest_followers,
           "follower_score_weighted": sc.follower_score_weighted,
           "cloud_workers": engine.n_full_workers,
           "edge_replicas": engine.n_edge_replicas,
           "n_tenants": engine.n_tenants,
           "overload_policy": sc.overload_policy,
           "fault_plan": None if sc.fault_plan is None else len(sc.fault_plan)}
    bad = {k: (v, stated[k]) for k, v in got.items() if v != stated[k]}
    if bad:
        raise ValueError(f"scheduler built {bad} (built, stated)")


def _queries(stream, rows: np.ndarray) -> list[dict]:
    return [{"emb": stream.emb[r], "entity": int(stream.ents[r]),
             "attr": int(stream.attrs[r])} for r in rows]


def _serve(engine, queries, traffic, seed: int):
    res = engine.serve(queries, arrivals=None, dataset=traffic["dataset"],
                       seed=seed)
    return {"served": np.asarray(res.served_ids, np.int32),
            "channels": np.asarray(res.channels),
            "leader": np.asarray(res.leader_idx, np.int64),
            "spec_batches": int(res.spec_batches),
            "full_batches": int(res.full_batches),
            "full_retrievals": int(res.full_retrievals),
            "modeled_s": np.asarray(res.latencies, np.float64)}


def warm(engine, stream, traffic) -> Warm:
    _check_scheduler(engine, traffic["scheduler"])
    rows = np.arange(traffic["warm_requests"])
    seg = _serve(engine, _queries(stream, rows), traffic, seed=0)
    ch = seg["channels"]
    return Warm(rows=rows, ids=seg["served"],
                accepts=np.isin(ch, ("draft", "reval", "shared")),
                cloud=np.isin(ch, ("full", "shared")))


def window(engine, stream, start: int, seconds: float, traffic,
           seed: int) -> Window:
    m, n_seg = traffic["segment_requests"], traffic["segments"]
    if start + n_seg * m > len(stream.emb):
        raise ValueError(f"stream of {len(stream.emb)} requests holds no "
                         f"{n_seg} segments of {m} after {start}")
    seg_rows = [start + s * m + np.arange(m) for s in range(n_seg)]
    queries = [_queries(stream, r) for r in seg_rows]
    segs, order, walls, gaps = [], [], [], []
    t0 = time.perf_counter()
    prev = t0
    while True:
        s = len(segs) % n_seg
        t = time.perf_counter()
        segs.append(_serve(engine, queries[s], traffic, seed=1 + s))
        t1 = time.perf_counter()
        order.append(s)
        walls.append(t1 - t)
        gaps.append(t - prev)
        prev = t1
        if t1 - t0 >= seconds:
            break
    wall = t1 - t0
    ch = np.concatenate([g["channels"] for g in segs])
    n = len(ch)
    offsets = np.arange(len(segs)) * m
    leader = np.concatenate([np.where(g["leader"] >= 0, g["leader"] + o, -1)
                             for g, o in zip(segs, offsets)])
    leader = np.where(ch == "shared", leader, -1)
    modeled = np.concatenate([g["modeled_s"] for g in segs])
    count = {c: int((ch == c).sum()) for c in CHANNELS}
    return Window(
        rows=np.concatenate([seg_rows[s] for s in order]), n=n, wall_s=wall,
        served=np.concatenate([g["served"] for g in segs]),
        accepts=np.isin(ch, ("draft", "reval", "shared")),
        exact_rows=np.flatnonzero(ch == "full"),
        cloud=np.isin(ch, ("full", "shared")),
        spec_calls=sum(g["spec_batches"] for g in segs), spec_rows=n,
        scan_calls=sum(g["full_batches"] for g in segs),
        scan_rows=sum(g["full_retrievals"] for g in segs),
        e2e={"qps": n / wall},
        lateness_s=np.array(gaps),
        drafted=np.isin(ch, ("draft", "reval")), leader=leader,
        share_tau=float(traffic["scheduler"]["share_tau"]),
        detail={"segments": len(segs),
                "segment_wall_s": ",".join(f"{x:.3f}" for x in walls),
                "other_channels": n - sum(count.values()), **count},
        modeled={"latency_mean_ms": 1e3 * float(modeled.mean()),
                 "latency_p95_ms": 1e3 * float(np.percentile(modeled, 95))})


def _spec_of(kept, rows: np.ndarray, emb: np.ndarray, k: int) -> dict:
    """The recorded speculation of each of ``rows``, matched by query
    vector; padding rows (all zero) are no request's."""
    at = row_lookup(emb, rows)
    pos = {r: j for j, r in enumerate(rows)}
    out = {"val_ids": np.full((len(rows), k), -1, np.int32),
           "draft_ids": np.full((len(rows), k), -1, np.int32),
           "accept": np.zeros(len(rows), bool),
           "seen": np.zeros(len(rows), np.int32), "stray": 0}
    host = jax.device_get([(q, {key: o[key] for key in
                                ("val_ids", "draft_ids", "accept")})
                           for q, o in kept])
    for q, o in host:
        q = np.asarray(q, np.float32)
        for b in range(len(q)):
            if not q[b].any():
                continue
            r = at.get(q[b].tobytes())
            if r is None:
                out["stray"] += 1
                continue
            j = pos[r]
            out["seen"][j] += 1
            out["val_ids"][j] = o["val_ids"][b]
            out["draft_ids"][j] = o["draft_ids"][b]
            out["accept"][j] = o["accept"][b]
    return out


def lifetimes(probe, warm: Warm, win: Window, emb) -> list[Lifetime]:
    """One lifetime a ``serve``: set-up's segment, then each of the
    window's; ingests in completion order."""
    d, k = emb.shape[1], warm.ids.shape[1]
    m = len(win.rows) // max(1, win.detail["segments"])
    bounds = [(0, len(warm.rows))] + [
        (len(warm.rows) + s * m, len(warm.rows) + (s + 1) * m)
        for s in range(win.detail["segments"])]
    out = []
    # lifetime 0 is before the first serve; set-up's serve is lifetime 1,
    # whose cache the next serve's start records
    for life, (lo, hi) in enumerate(bounds, start=1):
        rows = np.arange(lo, hi)
        kept = probe.kept("ingest", lifetime=life)
        end = probe.kept("cache_end", lifetime=life)
        end = end[0] if end else None
        if end is not None:
            # a buffer the program donated later: it went on writing this
            # lifetime's cache after the lifetime ended
            end = ({} if any(x.is_deleted() for x in end.values())
                   else jax.device_get(end))
        out.append(Lifetime(
            rows=rows,
            ingest_q=stack_rows([q for q, _ in kept], d, np.float32),
            ingest_ids=stack_rows([i for _, i in kept], k, np.int32),
            spec=_spec_of(probe.kept("spec", lifetime=life), rows, emb, k),
            end=end))
    return out
