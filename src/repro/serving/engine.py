"""RAG serving engines: Full / HaS / reuse-based / CRAG / ANNS (paper §IV).

All engines run on one serve-loop substrate (:class:`ServeLoop`): the loop
owns metrics recording, record-rng threading and micro-batch iteration, and
an engine only implements ``_step`` (one query -> ids/accept/latency) or
``_step_batch`` (one micro-batch -> a list of those).  Full-database
retrieval routes through the pluggable backend layer of the shared
:class:`~repro.retrieval.service.RetrievalService` (flat / sharded-mesh /
replica — see retrieval/service.py), so every engine's cloud stage swaps
without engine changes.  ``batch_size == 1``
gives Algorithm 1's sequential semantics (the cache mutates between
queries); serving/batched.py sets ``batch_size > 1`` for snapshot
micro-batching, and serving/scheduler.py reuses the same metrics substrate
for event-driven continuous batching.

Recorded metrics (paper §IV):

  AvgL   average end-to-end retrieval latency
  DAR    draft acceptance rate
  CAR    correct acceptance rate (accepted drafts containing a golden doc)
  DocHit golden document present in the returned set
  RA     simulated response accuracy per downstream LLM
  L@DA / L@DR   latency conditioned on acceptance / rejection
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.baselines import (CRAGEvaluator, ReuseState, init_reuse_state,
                                  mincache_match, minhash_signature,
                                  proximity_match, reuse_insert,
                                  saferadius_match)
from repro.core.dispatch import span
from repro.core.has import (HasConfig, _gather_rows, cache_update,
                            init_has_state, init_tenant_states,
                            speculate_batch)
from repro.data.synthetic import SyntheticWorld, simulate_response_accuracy
from repro.retrieval.ivf import (IVFIndex, build_ivf, ivf_search,
                                 subset_index)
# RetrievalService composes world + latency + a pluggable full-retrieval
# backend (retrieval/service.py); re-exported here for the serving layers
# and for backward compatibility of `repro.serving.engine.RetrievalService`.
from repro.retrieval.service import (FullRetrievalBackend, LocalFlatBackend,
                                     ReplicaBackend, RetrievalService,
                                     ShardedMeshBackend)
from repro.serving.latency import LatencyModel


@dataclasses.dataclass
class ServeResult:
    latencies: np.ndarray
    accepts: np.ndarray
    doc_hits: np.ndarray
    correct_accepts: np.ndarray
    ra: dict[str, np.ndarray]

    def summary(self) -> dict[str, float]:
        # NaN-safe on an empty stream (serve([])): rate/latency means
        # report NaN instead of numpy's mean-of-empty warning cascade
        def _mean(a) -> float:
            a = np.asarray(a)
            return float(a.mean()) if a.size else float("nan")

        acc = self.accepts.astype(bool)
        out = {
            "avg_latency_s": _mean(self.latencies),
            "dar": _mean(acc),
            "doc_hit_rate": _mean(self.doc_hits),
            "l_at_da": _mean(self.latencies[acc]) if acc.any() else 0.0,
            "l_at_dr": _mean(self.latencies[~acc]) if (~acc).any() else 0.0,
            "car": _mean(self.correct_accepts[acc]) if acc.any() else 0.0,
            "ra_at_da": _mean(self.ra["qwen3-8b"][acc]) if acc.any() else 0.0,
        }
        for llm, arr in self.ra.items():
            out[f"ra_{llm}"] = _mean(arr)
        return out


def _metrics_init(n, llms):
    return dict(latencies=np.zeros(n), accepts=np.zeros(n, bool),
                doc_hits=np.zeros(n, bool), correct=np.zeros(n, bool),
                ra={m: np.zeros(n, bool) for m in llms})


def _finish(m) -> ServeResult:
    return ServeResult(latencies=m["latencies"], accepts=m["accepts"],
                       doc_hits=m["doc_hits"], correct_accepts=m["correct"],
                       ra=m["ra"])


LLMS = ("qwen3-8b", "llama3-8b", "mixtral-7b")


def fuzzy_scope(cfg, index) -> float:
    """Fraction of the fuzzy IVF index streamed per probed query."""
    return min(cfg.nprobe, index.n_buckets) / index.n_buckets


def _record(m, i, world, query, ids, lat, accept, dataset, llms, rng):
    golden = world.golden_mask(query["entity"], query["attr"], ids)
    hit = bool(golden.any())
    m["latencies"][i] = lat
    m["accepts"][i] = accept
    m["doc_hits"][i] = hit
    m["correct"][i] = hit and accept
    n_docs = int(np.count_nonzero(np.asarray(ids) >= 0))
    for llm in llms:
        m["ra"][llm][i] = simulate_response_accuracy(
            rng, hit, dataset, llm, n_docs=n_docs)


# ---------------------------------------------------------------------------
# Serve-loop substrate
# ---------------------------------------------------------------------------

class ServeLoop:
    """One serve loop for every engine (sequential or micro-batched).

    ``serve`` owns the stream mechanics every engine previously hand-rolled:
    metrics array allocation, per-query recording (DocHit/CAR/RA draws from
    the record rng), and micro-batch iteration.  Engines implement either

      * ``_step(q, rng, dataset) -> (ids, accept, latency_s)`` — sequential
        Algorithm 1 semantics (``batch_size == 1``), or
      * ``_step_batch(group, rng, dataset) -> [(ids, accept, latency_s)]`` —
        snapshot micro-batch semantics (``batch_size > 1``).

    Latency accounting convention (serving/latency.py): engines compose each
    query's latency from sampled RTTs (the latency model's own rng stream),
    measured edge compute, and analytic bandwidth-bound scan times.
    """

    batch_size: int = 1

    def __init__(self, service: RetrievalService):
        self.s = service

    def _step(self, q, rng, dataset):
        raise NotImplementedError

    def _step_batch(self, group, rng, dataset):
        return [self._step(q, rng, dataset) for q in group]

    def serve(self, queries, dataset="granola", llms=LLMS,
              seed=0) -> ServeResult:
        rng = np.random.default_rng(seed)
        m = _metrics_init(len(queries), llms)
        bs = max(int(self.batch_size), 1)
        for start in range(0, len(queries), bs):
            group = queries[start:start + bs]
            for j, (ids, accept, lat) in enumerate(
                    self._step_batch(group, rng, dataset)):
                _record(m, start + j, self.s.world, group[j], ids, lat,
                        bool(accept), dataset, llms, rng)
        return _finish(m)


# ---------------------------------------------------------------------------
# Engines
# ---------------------------------------------------------------------------

class FullRetrievalEngine(ServeLoop):
    """Baseline: always full-database retrieval on the cloud."""

    def _step(self, q, rng, dataset):
        ids, _, t = self.s.full_search(q["emb"], q.get("terms"),
                                       q.get("term_weights"))
        return ids, False, self.s.latency.sample_cloud() + t


class ANNSEngine(ServeLoop):
    """IVF / ScaNN-substitute at a configurable scope (Table II ♠/♦).

    'scann' = IVF partitioning + int8 asymmetric scoring (the TPU-native
    stand-in for ScaNN's anisotropic quantization): the bucket store keeps
    int8-degraded values (accuracy cost) and is charged 1 byte/dim on the
    latency model (bandwidth win).
    """

    def __init__(self, service: RetrievalService, method: str = "ivf",
                 n_buckets: int = 4096, nprobe: int = 64,
                 on_edge: bool = True, seed: int = 0):
        super().__init__(service)
        self.on_edge = on_edge
        self.method = method
        self.index = build_ivf(service.corpus, n_buckets, seed=seed)
        self.nprobe = min(nprobe, self.index.n_buckets)
        self.scope = self.nprobe / self.index.n_buckets
        if method == "scann":
            # bake int8 rounding into the bucket store (score degradation)
            bv = self.index.bucket_vecs
            scale = jnp.max(jnp.abs(bv), axis=-1, keepdims=True) / 127.0
            q8 = jnp.clip(jnp.round(bv / jnp.maximum(scale, 1e-8)),
                          -127, 127)
            self.index = IVFIndex(
                centroids=self.index.centroids,
                bucket_vecs=(q8 * scale).astype(jnp.float32),
                bucket_ids=self.index.bucket_ids,
                bucket_counts=self.index.bucket_counts)
        self.search(np.zeros((service.world.cfg.d,), np.float32))  # warmup

    def search(self, q_emb):
        q = np.asarray(q_emb, np.float32)[None]
        lat = self.s.latency
        _, ids = ivf_search(self.index, q, nprobe=self.nprobe, k=self.s.k)
        # cost ~ probed fraction of the corpus (x2 bucket padding) at
        # 4 B/dim (ivf) or 1 B/dim (scann int8), + the centroid matmul
        bpd = 1 if self.method == "scann" else 4
        t = lat.scan_time(lat.target_corpus * self.scope * 2.0,
                          bytes_per_dim=bpd) + lat.scan_time(
                              self.index.n_buckets)
        return jax.device_get(ids)[0], t

    def _step(self, q, rng, dataset):
        ids, t = self.search(q["emb"])
        rtt = (self.s.latency.sample_edge() if self.on_edge
               else self.s.latency.sample_cloud())
        return ids, False, rtt + t


class HasEngine(ServeLoop):
    """The paper's system (Algorithm 1) with optional ANNS fallback (♦).

    ``n_tenants > 1`` partitions the cache (``init_tenant_states``): each
    query routes through its tenant's slice (``step(..., tenant=t)``, or a
    ``"tenant"`` key on the query dict), rejects ingest only into that
    partition, and replica backends receive the tenant tag on every
    ingest.  ``n_tenants == 1`` is the historical unpartitioned path.
    """

    def __init__(self, service: RetrievalService, cfg: HasConfig | None = None,
                 fallback: ANNSEngine | None = None,
                 fuzzy_fraction: float = 1.0, seed: int = 0,
                 backend: str | None = None, n_tenants: int = 1):
        super().__init__(service)
        self.cfg = cfg or HasConfig(k=service.k, d=service.world.cfg.d)
        self.n_tenants = max(1, int(n_tenants))
        self.state = (init_has_state(self.cfg) if self.n_tenants == 1
                      else init_tenant_states(self.cfg, self.n_tenants))
        index = build_ivf(service.corpus, self.cfg.n_buckets, seed=seed)
        self.index = subset_index(index, fuzzy_fraction)
        self.fallback = fallback
        self.backend = backend                  # None -> auto per platform
        self.fuzzy_scope = (self.cfg.nprobe / self.cfg.n_buckets) * fuzzy_fraction
        self.n_steps = 0                        # requests through step()
        # warmup the fused speculation program at the sequential shape B=1
        z = jnp.zeros((1, self.s.world.cfg.d))
        out = speculate_batch(self.cfg, self.state, self.index, z,
                              backend=backend,
                              tenant_ids=self._tids(0))
        jax.block_until_ready(out)

    def _tids(self, tenant: int):
        """tenant_ids for a B=1 speculation (None on the legacy path);
        rejects out-of-range tags up front — a silently-dropped scatter
        would otherwise leave the tenant's cache forever cold."""
        if not 0 <= tenant < self.n_tenants:
            raise ValueError(
                f"tenant {tenant} out of range for n_tenants="
                f"{self.n_tenants}")
        return (None if self.n_tenants == 1
                else jnp.full((1,), tenant, jnp.int32))

    def _fuzzy_time(self) -> float:
        """Analytic fuzzy-channel scan time at the target corpus scale."""
        lat = self.s.latency
        return lat.scan_time(lat.target_corpus * self.fuzzy_scope * 2.0
                             + self.cfg.n_buckets)

    def step(self, q_emb: np.ndarray, tenant: int = 0, q_terms=None,
             q_term_weights=None):
        """Returns (ids, accept, latency_s, homology).

        Each phase runs in a ``dispatch.span`` (``has.*``) inside the
        request's ``has.step``, whose ``req`` is this engine's step count;
        the step's own time outside them is the latency model's draws."""
        req, self.n_steps = self.n_steps, self.n_steps + 1
        with span("has.step", req=req):
            lat = self.s.latency.sample_edge()
            t0 = time.perf_counter()
            # host arrays go straight into the jitted programs, and each
            # phase's outputs come back in one ``device_get``: an eager
            # ``x[0]``, ``jnp.asarray`` or ``corpus[ids]`` is a program
            # launch and a sync of its own
            with span("has.upload"):
                q_emb = np.asarray(q_emb, np.float32)
                q = q_emb[None]
            with span("has.spec"):
                out = speculate_batch(self.cfg, self.state, self.index, q,
                                      backend=self.backend,
                                      tenant_ids=self._tids(tenant))
            with span("has.readback"):
                accept, homology, draft = jax.device_get(
                    (out["accept"], out["homology"], out["draft_ids"]))
            # measured edge compute (cache channel + validation at true
            # scale) + analytic fuzzy scan extrapolated to the target corpus
            lat += (time.perf_counter() - t0) + self._fuzzy_time()
            accept, homology = bool(accept[0]), float(homology[0])
            if accept:
                return draft[0], True, lat, homology
            # fallback: full database (cloud) or optimized ANNS (♦)
            if self.fallback is not None:
                ids, t = self.fallback.search(q_emb)
                vecs = _gather_rows(self.s.corpus, ids)
            else:
                ids, vecs, t = self.s.full_search(q_emb, q_terms,
                                                  q_term_weights)
            lat += self.s.latency.sample_cloud() + t
            t0 = time.perf_counter()
            with span("has.ingest"):
                self.state = cache_update(
                    self.cfg, self.state, q_emb, ids.astype(np.int32), vecs,
                    tenant_id=None if self.n_tenants == 1 else tenant)
                jax.block_until_ready(self.state.q_ptr)
            lat += time.perf_counter() - t0
            # replica-style backends mirror the ingest onto standby delta logs
            with span("has.replicate"):
                self.s.backend.on_ingest(
                    np.asarray(q_emb)[None], ids.astype(np.int32)[None],
                    self.state,
                    tenant_ids=(None if self.n_tenants == 1
                                else np.array([tenant], np.int32)))
            return ids, False, lat, homology

    def _step(self, q, rng, dataset):
        ids, accept, lat, _ = self.step(q["emb"],
                                        tenant=int(q.get("tenant", 0)),
                                        q_terms=q.get("terms"),
                                        q_term_weights=q.get("term_weights"))
        return ids, accept, lat


class ReuseEngine(ServeLoop):
    """Proximity / SafeRadius / MinCache reuse baselines (Table III)."""

    def __init__(self, service: RetrievalService, method: str,
                 h_max: int = 5000, theta: float = 0.9, alpha: float = 2.0,
                 t_lex: float = 0.6, t_sem: float = 0.9):
        super().__init__(service)
        self.method = method
        self.state = init_reuse_state(h_max, service.k, service.world.cfg.d)
        self.theta, self.alpha = theta, alpha
        self.t_lex, self.t_sem = t_lex, t_sem

    def _match(self, q):
        qe = jnp.asarray(q["emb"])
        if self.method == "proximity":
            return proximity_match(self.state, qe, jnp.float32(self.theta))
        if self.method == "saferadius":
            return saferadius_match(self.state, qe, jnp.float32(self.alpha))
        if self.method == "mincache":
            mh = jnp.asarray(minhash_signature(q["tokens"]))
            return mincache_match(self.state, qe, mh,
                                  jnp.float32(self.t_lex),
                                  jnp.float32(self.t_sem))
        raise ValueError(self.method)

    def _step(self, q, rng, dataset):
        lat = self.s.latency.sample_edge()
        t0 = time.perf_counter()
        ok, slot, _ = self._match(q)
        ok = bool(ok)
        lat += time.perf_counter() - t0
        if ok:
            ids = np.asarray(self.state.doc_ids[int(slot)])
        else:
            ids, vecs, t = self.s.full_search(q["emb"], q.get("terms"),
                                              q.get("term_weights"))
            lat += self.s.latency.sample_cloud() + t
            scores = np.asarray(self.s.corpus[ids] @ q["emb"])
            self.state = reuse_insert(
                self.state, jnp.asarray(q["emb"]),
                jnp.asarray(ids.astype(np.int32)), jnp.asarray(vecs),
                jnp.asarray(scores),
                jnp.asarray(minhash_signature(q["tokens"])))
        return ids, ok, lat


class CRAGEngine(HasEngine):
    """HaS pipeline with homology validation replaced by an LLM evaluator."""

    def __init__(self, service: RetrievalService, cfg: HasConfig | None = None,
                 evaluator: CRAGEvaluator | None = None, seed: int = 0,
                 n_tenants: int = 1):
        super().__init__(service, cfg, seed=seed, n_tenants=n_tenants)
        self.evaluator = evaluator or CRAGEvaluator()

    def _step(self, q, rng, dataset):
        tenant = int(q.get("tenant", 0))
        lat = self.s.latency.sample_edge()
        t0 = time.perf_counter()
        out = speculate_batch(self.cfg, self.state, self.index,
                              jnp.asarray(q["emb"])[None],
                              backend=self.backend,
                              tenant_ids=self._tids(tenant))
        jax.block_until_ready(out)
        lat += (time.perf_counter() - t0) + self._fuzzy_time()
        draft = np.asarray(out["draft_ids"][0])
        golden = self.s.world.golden_mask(q["entity"], q["attr"], draft)
        lat += self.evaluator.latency_s              # LLM inference cost
        accept = self.evaluator.evaluate(rng, golden, dataset == "popqa")
        if accept:
            return draft, True, lat
        ids, vecs, t = self.s.full_search(q["emb"], q.get("terms"),
                                          q.get("term_weights"))
        lat += self.s.latency.sample_cloud() + t
        self.state = cache_update(
            self.cfg, self.state, jnp.asarray(q["emb"]),
            jnp.asarray(ids.astype(np.int32)), jnp.asarray(vecs),
            tenant_id=(None if self.n_tenants == 1 else tenant))
        self.s.backend.on_ingest(
            np.asarray(q["emb"])[None], ids.astype(np.int32)[None],
            self.state,
            tenant_ids=(None if self.n_tenants == 1
                        else np.array([tenant], np.int32)))
        return ids, False, lat
