"""Pluggable full-retrieval backend layer + the shared RetrievalService.

The paper's speedup comes from *bypassing* slow full-database retrieval, but
every rejected draft still pays for it — so the cloud stage is the serving
system's scaling bottleneck.  This module makes that stage pluggable: the
:class:`FullRetrievalBackend` protocol is what every serving layer (the
``ServeLoop`` engines, ``BatchedHasEngine``, the continuous-batching
scheduler, ``AutoRagPipeline``) sees, and three implementations cover the
deployment spectrum:

``LocalFlatBackend``
    One in-process exact scan (``chunked_flat_search``) — the historical
    behavior of ``RetrievalService.full_search``.  One worker: full
    retrievals serialize behind each other.
``ShardedMeshBackend``
    The corpus row-sharded over a CPU/TPU mesh
    (``retrieval/distributed.py``): each shard streams N/shards rows and the
    O(shards·k) candidate sets merge with an all-gather.  Latency is scaled
    by ``LatencyModel.shard_scale(n_shards)`` and the backend exposes
    ``n_workers`` concurrent dispatch slots, so the scheduler's cloud stage
    becomes a worker *pool* whose throughput scales with corpus shards.
    Off-mesh (one local device) the identical merge math runs through
    :func:`~repro.retrieval.distributed.sharded_topk_reference`, keeping
    results bit-identical to the mesh path and to ``LocalFlatBackend``.
``IVFBackend``
    ANN cloud stage (``--retrieval-backend ann``): an IVF index
    (``retrieval/ivf.py``) scored through the Pallas ``ivf_scan`` kernel or
    its XLA oracle — the same ``backend="pallas"|"xla"`` switch the
    speculation path uses — in ONE dispatch per query batch (centroid
    matmul -> top-nprobe -> scalar-prefetched bucket scan -> residual
    merge).  Optional int8 compressed corpus residency
    (``compressed=True``) quantizes bucket storage per vector with the
    dequant fused into the scan.  ``latency`` is
    ``LatencyModel.ann_scale`` — centroid + nprobe·capacity bucket cost
    instead of the full corpus.  NOTE the result is *approximate*:
    recall@k is calibrated by ``benchmarks/ann_recall.py``, end-to-end,
    because approximate results feed the HaS cache.
``ReplicaBackend``
    Routes full retrievals through warm-standby replicas
    (``serving/replication.py``): ``n_workers`` = number of standbys, and
    every cache ingest is reconciled into each standby's delta log
    (``on_ingest``), so any replica can fail over with the cache it would
    have had — the scheduler no longer assumes one authoritative cache.

Latency protocol: ``latency(batch)`` returns the *modeled* service time of
one coalesced dispatch (bandwidth-bound: a batch streams the operand once,
so the time is batch-width independent); ``n_workers`` is how many such
dispatches the virtual clock may overlap.
"""
from __future__ import annotations

import functools
import time
from typing import Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch
from repro.core.has import _gather_rows
from repro.retrieval.distributed import (distributed_flat_search,
                                         sharded_topk_reference)
from repro.retrieval.flat import chunked_flat_search
from repro.retrieval.fusion import (hybrid_ann_search, hybrid_flat_search,
                                    hybrid_sharded_search, ivf_ann_body)
from repro.retrieval.ivf import (CompressedIVFIndex, IVFIndex, _assign_fn,
                                 _build_ivf_arrays, _quant_residual_halves,
                                 ivf_probe_scan)


@runtime_checkable
class FullRetrievalBackend(Protocol):
    """What a serving layer needs from the full-database retrieval stage."""

    #: concurrent dispatch slots the virtual clock may overlap
    n_workers: int

    def search(self, q_embs: jax.Array) -> tuple[jax.Array, jax.Array]:
        """Exact top-k for a query batch [B, d] -> (scores [B,k], ids [B,k])."""
        ...

    def latency(self, batch: int) -> float:
        """Modeled service time (s) of ONE coalesced dispatch of ``batch``."""
        ...

    def on_ingest(self, q_embs: np.ndarray, full_ids: np.ndarray,
                  state, tenant_ids: np.ndarray | None = None, *,
                  ingest_key=None) -> None:
        """Cache-ingest notification (rows just folded into the HaS cache).

        ``tenant_ids [N]`` (optional) tags each row with its tenant
        partition so replica-style backends keep per-tenant delta logs
        (None == the single-tenant path).  ``ingest_key`` (optional,
        keyword-only) is a stable batch identity for IDEMPOTENT ingest:
        a backend that replicates must drop a batch whose key it has
        already recorded — a retried cloud dispatch whose first attempt
        landed must not fold twice downstream.
        """
        ...


class _BackendBase:
    """Shared no-op ingest hook; concrete backends set search/latency."""

    n_workers: int = 1

    def on_ingest(self, q_embs, full_ids, state, tenant_ids=None, *,
                  ingest_key=None) -> None:
        return None


class LocalFlatBackend(_BackendBase):
    """Today's behavior: one in-process chunked exact scan, one worker."""

    def __init__(self, corpus: jax.Array, k: int, lat, chunk: int = 32768):
        self.corpus = corpus
        self.k = k
        self.lat = lat
        self.chunk = min(chunk, corpus.shape[0])
        self._search = jax.jit(functools.partial(
            chunked_flat_search, k=k, chunk=self.chunk))
        self.n_workers = 1

    def search(self, q_embs):
        dispatch.record("flat_backend_search")
        return self._search(self.corpus, q_embs)

    def latency(self, batch: int) -> float:
        # bandwidth-bound coalesced matmul: the batch streams the corpus once
        return self.lat.full_scan_time()


class ShardedMeshBackend(_BackendBase):
    """Row-sharded mesh scan with a concurrent-dispatch worker pool.

    ``mesh`` (multi-device) lowers through ``distributed_flat_search``
    (shard_map + all-gather merge over ``corpus_axes``); without a mesh —
    or on a 1-device mesh — the same candidate-merge math runs through
    ``sharded_topk_reference`` so the virtual clock can model an
    ``n_shards``-way deployment from a single-device container.  Either
    path returns scores/ids bit-identical to ``LocalFlatBackend``.
    """

    def __init__(self, corpus: jax.Array, k: int, lat, n_shards: int = 4,
                 n_workers: int = 1, mesh=None,
                 corpus_axes: tuple[str, ...] = ("data", "model")):
        self.corpus = corpus
        self.k = k
        self.lat = lat
        self.mesh = mesh
        mesh_shards = 1
        if mesh is not None:
            for a in corpus_axes:
                mesh_shards *= mesh.shape.get(a, 1)
        if mesh is not None and mesh_shards > 1:
            # the mesh decides the physical shard count
            self.n_shards = mesh_shards
            if corpus.shape[0] % mesh_shards:
                raise ValueError(
                    f"corpus rows {corpus.shape[0]} must divide evenly over "
                    f"{mesh_shards} mesh shards")
            dist = distributed_flat_search(mesh, corpus_axes)
            self._search = jax.jit(lambda c, q: dist(c, q, k))
        else:
            self.n_shards = max(1, int(n_shards))
            self._search = functools.partial(
                sharded_topk_reference, k=k, n_shards=self.n_shards)
        self.n_workers = max(1, int(n_workers))

    def search(self, q_embs):
        dispatch.record("sharded_backend_search")
        return self._search(self.corpus, q_embs)

    def latency(self, batch: int) -> float:
        # every shard streams N/n_shards rows concurrently + merge overhead
        return self.lat.full_scan_time() * self.lat.shard_scale(self.n_shards)


# the ANN program body lives in retrieval/fusion.py so the hybrid backend
# can inline the identical math as its dense channel inside ONE fused program
_ivf_ann_search = functools.partial(jax.jit, static_argnames=(
    "nprobe", "k", "scan_backend", "interpret"))(ivf_ann_body)


class IVFBackend(_BackendBase):
    """ANN cloud stage: IVF index + Pallas/XLA bucket scan + live-ingest
    reconciliation.

    The index is built by streaming the corpus through k-means assignment
    in ``build_chunk``-row slices (never materializing f32 buckets in
    compressed mode); host-side mirrors of the bucket arrays stay canonical
    so live ingest mutates numpy and re-uploads lazily on the next search.
    ``compressed=True`` stores int8 centroid-residual codes with two
    per-half dequant scales (``retrieval/ivf.py::_quant_residual_halves``,
    built on ``training/compression.py::quantize_int8``); the dequant fuses
    into scoring on both scan backends and the centroid term reuses the
    probe matmul — the bucket store shrinks ~3.6x (d bytes + two f32
    scales per vector vs 4d bytes), with a smaller recall drop than plain
    per-vector int8 because the int8 grid codes only the residual.

    Live ingest (``ingest_docs``) assigns each new doc to its nearest
    centroid; a full bucket spills into a small exact-scanned residual
    flat buffer (capacity ``residual_cap``), and residual overflow
    triggers a full re-bucketing flush (k-means + rebuild over the grown
    corpus).  Search correctness never depends on WHERE a doc landed —
    the residual is merged into every top-k.  ``on_ingest`` (cache-ingest
    notification) stays the no-op base hook, so ``ReplicaBackend`` can
    wrap an ``IVFBackend`` unchanged.

    Results are APPROXIMATE (recall < 1 at nprobe < n_buckets) and feed
    the HaS cache downstream; calibrate nprobe with
    ``benchmarks/ann_recall.py``, which measures end-to-end doc-hit, not
    just kernel recall@k.
    """

    def __init__(self, corpus: jax.Array, k: int, lat,
                 n_clusters: int = 1024, nprobe: int = 32,
                 capacity_factor: float = 2.0, compressed: bool = False,
                 backend: str | None = None, n_workers: int = 1,
                 seed: int = 0, residual_cap: int = 1024,
                 build_chunk: int = 65536, kmeans_iters: int = 10,
                 interpret: bool | None = None):
        from repro.core.has import default_backend
        from repro.kernels.ops import auto_interpret
        self.corpus = corpus
        self.k = k
        self.lat = lat
        self.n_clusters = int(n_clusters)
        self.nprobe = max(1, int(nprobe))
        self.capacity_factor = float(capacity_factor)
        self.compressed = bool(compressed)
        self.scan_backend = backend if backend is not None else default_backend()
        self.n_workers = max(1, int(n_workers))
        self.seed = int(seed)
        self.residual_cap = max(1, int(residual_cap))
        self.build_chunk = int(build_chunk)
        self.kmeans_iters = int(kmeans_iters)
        self._interpret = auto_interpret() if interpret is None else interpret
        self._corpus_np = np.asarray(corpus, np.float32)
        self._ids_np = np.arange(self._corpus_np.shape[0], dtype=np.int32)
        self._next_id = int(self._corpus_np.shape[0])
        self._ingest_seen: dict = {}
        self.rebuilds = 0
        self._res_vecs_np = np.zeros(
            (self.residual_cap, self._corpus_np.shape[1]), np.float32)
        self._res_ids_np = np.full(self.residual_cap, -1, np.int32)
        self._res_count = 0
        self._build()

    # -- index build / upload -------------------------------------------
    def _build(self) -> None:
        (self._cents_np, self._bvecs_np, self._bscales_np, self._bids_np,
         self._counts_np) = _build_ivf_arrays(
            self._corpus_np, self.n_clusters,
            capacity_factor=self.capacity_factor,
            kmeans_iters=self.kmeans_iters, seed=self.seed,
            chunk=self.build_chunk, compressed=self.compressed,
            ids=self._ids_np)
        self._dirty = True
        self._upload()

    def _upload(self) -> None:
        if self.compressed:
            self.index = CompressedIVFIndex(
                centroids=jnp.asarray(self._cents_np),
                bucket_vecs=jnp.asarray(self._bvecs_np),
                bucket_scales=jnp.asarray(self._bscales_np),
                bucket_ids=jnp.asarray(self._bids_np),
                bucket_counts=jnp.asarray(self._counts_np))
        else:
            self.index = IVFIndex(
                centroids=jnp.asarray(self._cents_np),
                bucket_vecs=jnp.asarray(self._bvecs_np),
                bucket_ids=jnp.asarray(self._bids_np),
                bucket_counts=jnp.asarray(self._counts_np))
        self._res_vecs = jnp.asarray(self._res_vecs_np)
        self._res_ids = jnp.asarray(self._res_ids_np)
        self._dirty = False

    # -- FullRetrievalBackend protocol ----------------------------------
    def search(self, q_embs):
        dispatch.record("ivf_backend_search")
        if self._dirty:
            self._upload()
        return _ivf_ann_search(self.index, self._res_vecs, self._res_ids,
                               q_embs, nprobe=self.nprobe, k=self.k,
                               scan_backend=self.scan_backend,
                               interpret=self._interpret)

    def latency(self, batch: int) -> float:
        return self.lat.full_scan_time() * self.lat.ann_scale(
            self.index.n_buckets, self.nprobe,
            capacity_factor=self.capacity_factor,
            bytes_per_dim=1 if self.compressed else 4,
            residual_rows=self._res_count)

    # -- live-ingest reconciliation -------------------------------------
    @property
    def residual_count(self) -> int:
        return self._res_count

    def _rebucket(self) -> None:
        """Flush: rebuild the whole index (incl. residual docs, which are
        already rows of the host corpus) and empty the residual buffer."""
        self._build()
        self._res_vecs_np[:] = 0.0
        self._res_ids_np[:] = -1
        self._res_count = 0
        self.rebuilds += 1
        self._dirty = True

    def ingest_docs(self, vecs, ids=None, *, ingest_key=None) -> np.ndarray:
        """Reconcile live-ingested docs: nearest-centroid assignment with
        bounded bucket spill into the residual buffer; residual overflow
        triggers a re-bucketing flush.  Idempotent on ``ingest_key``.
        Returns the global ids assigned to the new docs."""
        if ingest_key is not None and ingest_key in self._ingest_seen:
            return self._ingest_seen[ingest_key]
        vecs = np.asarray(vecs, np.float32)
        if vecs.ndim == 1:
            vecs = vecs[None]
        n_new = vecs.shape[0]
        if ids is None:
            ids = self._next_id + np.arange(n_new, dtype=np.int32)
        ids = np.asarray(ids, np.int32)
        self._next_id = max(self._next_id, int(ids.max(initial=-1)) + 1)
        # the host corpus grows FIRST: a re-bucketing flush rebuilds from
        # it, so every doc (placed or not) survives the flush
        self._corpus_np = np.concatenate([self._corpus_np, vecs])
        self._ids_np = np.concatenate([self._ids_np, ids])
        assign = np.asarray(_assign_fn(jnp.asarray(vecs),
                                       jnp.asarray(self._cents_np)))
        if self.compressed:
            q_all, s_all = _quant_residual_halves(
                jnp.asarray(vecs), jnp.asarray(self._cents_np[assign]))
            q_all = np.asarray(q_all)
            s_all = np.asarray(s_all)
        cap = self._bids_np.shape[1]
        for i in range(n_new):
            b = int(assign[i])
            c = int(self._counts_np[b])
            if c < cap:
                self._bids_np[b, c] = ids[i]
                if self.compressed:
                    self._bvecs_np[b, c] = q_all[i]
                    self._bscales_np[b, c] = s_all[i]
                else:
                    self._bvecs_np[b, c] = vecs[i]
                self._counts_np[b] = c + 1
            elif self._res_count < self.residual_cap:
                self._res_vecs_np[self._res_count] = vecs[i]
                self._res_ids_np[self._res_count] = ids[i]
                self._res_count += 1
            else:
                # overflow: the rebuild already covers every remaining doc
                self._rebucket()
                break
        self._dirty = True
        if ingest_key is not None:
            self._ingest_seen[ingest_key] = ids
        return ids


class HybridBackend(_BackendBase):
    """Hybrid lexical+dense cloud stage with single-dispatch fused reranking.

    Composes a dense channel (``dense="flat" | "sharded" | "ann"``) with the
    hashed-term lexical channel (``retrieval/lexical.py``) and fuses both
    into ONE jitted program per ``[B, d]`` batch (``retrieval/fusion.py``):
    channel scans -> rank-domain RRF (``1/(rrf_k + rank)``, cross-channel
    duplicate mass combined onto the first occurrence) -> greedy
    near-duplicate diversification (cosine >= ``diversify_sim`` against
    already-selected docs is dropped; ``None`` disables) -> dense rerank of
    the surviving pool.  ``search`` therefore costs exactly one host
    dispatch regardless of batch width (``dispatch.record``-probed).

    Queries without term arrays (warmup, engines that only carry
    embeddings) run the same program with an all-invalid term batch: the
    lexical channel contributes nothing and the result degrades gracefully
    to diversified+reranked dense retrieval.

    Id contract: postings row == global doc id, so ``ingest_docs`` REJECTS
    non-sequential ids — both channels grow in lockstep (dense vectors via
    the inner ``IVFBackend`` in ANN mode, plain corpus append otherwise;
    postings rows always appended here, ``-1``-padded when the new doc has
    no terms).  ``on_ingest`` stays the base no-op so ``ReplicaBackend``
    and the fault-plan retry/hedge paths compose unchanged.
    """

    uses_lexical = True

    def __init__(self, corpus: jax.Array, k: int, lat,
                 doc_terms, doc_term_weights, dense: str = "flat",
                 dense_k: int | None = None, lexical_k: int | None = None,
                 rrf_k: float = 60.0, diversify_sim: float | None = 0.98,
                 lexical_terms: int | None = None,
                 backend: str | None = None, interpret: bool | None = None,
                 chunk: int = 32768, n_shards: int = 4, n_workers: int = 1,
                 tile_n: int = 512, q_term_width: int = 2,
                 ann_kwargs: dict | None = None):
        from repro.core.has import default_backend
        from repro.kernels.ops import auto_interpret
        if dense not in ("flat", "sharded", "ann"):
            raise ValueError(f"unknown hybrid dense mode: {dense!r}")
        if rrf_k < 1:
            raise ValueError("rrf_k must be >= 1")
        if diversify_sim is not None and not 0.0 < diversify_sim <= 1.0:
            raise ValueError("diversify_sim must be in (0, 1]")
        self.k = k
        self.lat = lat
        self.dense = dense
        self.dense_k = int(dense_k) if dense_k else k
        self.lexical_k = int(lexical_k) if lexical_k else k
        self.rrf_k = float(rrf_k)
        self.diversify_sim = (None if diversify_sim is None
                              else float(diversify_sim))
        self.scan_backend = backend if backend is not None else default_backend()
        self._interpret = auto_interpret() if interpret is None else interpret
        self.tile_n = int(tile_n)
        self.q_term_width = max(1, int(q_term_width))
        self.n_workers = max(1, int(n_workers))
        self.n_shards = max(1, int(n_shards))
        self._corpus_np = np.asarray(corpus, np.float32)
        self.chunk = min(chunk, max(1, self._corpus_np.shape[0]))
        terms = np.asarray(doc_terms, np.int32)
        tw = np.asarray(doc_term_weights, np.float32)
        if terms.shape != tw.shape or terms.shape[0] != self._corpus_np.shape[0]:
            raise ValueError("postings arrays must be [n_docs, L] and match "
                             "the corpus row count")
        if lexical_terms is not None:
            lw = max(1, int(lexical_terms))
            terms, tw = terms[:, :lw], tw[:, :lw]
        self.lexical_terms = terms.shape[1]
        self._terms_np, self._tw_np = terms, tw
        self._ivf = None
        if dense == "ann":
            kw = dict(backend=self.scan_backend, interpret=self._interpret)
            kw.update(ann_kwargs or {})
            self._ivf = IVFBackend(jnp.asarray(self._corpus_np),
                                   self.dense_k, lat, **kw)
        self._ingest_seen: dict = {}
        self._dirty = True
        self._upload()

    def _upload(self) -> None:
        if self._ivf is not None and self._ivf._dirty:
            self._ivf._upload()
        self.corpus = jnp.asarray(self._corpus_np)
        self._terms = jnp.asarray(self._terms_np)
        self._tw = jnp.asarray(self._tw_np)
        self._dirty = False

    # -- FullRetrievalBackend protocol ----------------------------------
    def search(self, q_embs, q_terms=None, q_term_weights=None):
        dispatch.record("hybrid_backend_search")
        b = q_embs.shape[0]
        if q_terms is None:
            # term-less callers: inert terms, lexical channel matches nothing
            q_terms = jnp.full((b, self.q_term_width), -1, jnp.int32)
            q_term_weights = jnp.zeros((b, self.q_term_width), jnp.float32)
        else:
            q_terms = jnp.asarray(q_terms).astype(jnp.int32)
            if q_term_weights is None:
                q_term_weights = jnp.where(q_terms >= 0, 1.0, 0.0)
            q_term_weights = jnp.asarray(q_term_weights).astype(jnp.float32)
        if self._dirty or (self._ivf is not None and self._ivf._dirty):
            self._upload()
        common = dict(k=self.k, kd=self.dense_k, kl=self.lexical_k,
                      rrf_k=self.rrf_k, diversify_sim=self.diversify_sim,
                      scan_backend=self.scan_backend,
                      interpret=self._interpret, tile_n=self.tile_n)
        if self.dense == "flat":
            return hybrid_flat_search(self.corpus, self._terms, self._tw,
                                      q_embs, q_terms, q_term_weights,
                                      chunk=self.chunk, **common)
        if self.dense == "sharded":
            return hybrid_sharded_search(self.corpus, self._terms, self._tw,
                                         q_embs, q_terms, q_term_weights,
                                         n_shards=self.n_shards,
                                         chunk=self.chunk, **common)
        return hybrid_ann_search(self._ivf.index, self._ivf._res_vecs,
                                 self._ivf._res_ids, self.corpus,
                                 self._terms, self._tw, q_embs, q_terms,
                                 q_term_weights, nprobe=self._ivf.nprobe,
                                 **common)

    def _dense_scale(self) -> float:
        if self.dense == "flat":
            return 1.0
        if self.dense == "sharded":
            return self.lat.shard_scale(self.n_shards)
        return self.lat.ann_scale(
            self._ivf.index.n_buckets, self._ivf.nprobe,
            capacity_factor=self._ivf.capacity_factor,
            bytes_per_dim=1 if self._ivf.compressed else 4,
            residual_rows=self._ivf._res_count)

    def latency(self, batch: int) -> float:
        return self.lat.full_scan_time() * self.lat.hybrid_scale(
            self._dense_scale(), self.lexical_terms,
            self.dense_k + self.lexical_k)

    # -- live-corpus ingest (both channels in lockstep) ------------------
    def ingest_docs(self, vecs, ids=None, *, terms=None, term_weights=None,
                    ingest_key=None) -> np.ndarray:
        if ingest_key is not None and ingest_key in self._ingest_seen:
            return self._ingest_seen[ingest_key]
        vecs = np.asarray(vecs, np.float32)
        if vecs.ndim == 1:
            vecs = vecs[None]
        n_new = vecs.shape[0]
        start = self._corpus_np.shape[0]
        want = (start + np.arange(n_new)).astype(np.int32)
        if ids is not None and not np.array_equal(
                np.asarray(ids, np.int32), want):
            raise ValueError(
                "HybridBackend requires sequential doc ids (postings row == "
                f"global id): expected {start}..{start + n_new - 1}")
        t_rows = np.full((n_new, self.lexical_terms), -1, np.int32)
        w_rows = np.zeros((n_new, self.lexical_terms), np.float32)
        if terms is not None:
            terms = np.asarray(terms, np.int32)
            if terms.ndim == 1:
                terms = terms[None]
            if term_weights is None:
                tw = np.where(terms >= 0, 1.0, 0.0).astype(np.float32)
            else:
                tw = np.asarray(term_weights, np.float32)
                if tw.ndim == 1:
                    tw = tw[None]
            m = min(self.lexical_terms, terms.shape[1])
            t_rows[:, :m] = terms[:, :m]
            w_rows[:, :m] = np.where(terms[:, :m] >= 0, tw[:, :m], 0.0)
        if self._ivf is not None:
            got = np.asarray(
                self._ivf.ingest_docs(vecs, want, ingest_key=ingest_key),
                np.int32)
            self._corpus_np = self._ivf._corpus_np
        else:
            got = want
            self._corpus_np = np.concatenate([self._corpus_np, vecs])
        self._terms_np = np.concatenate([self._terms_np, t_rows])
        self._tw_np = np.concatenate([self._tw_np, w_rows])
        self._dirty = True
        if ingest_key is not None:
            self._ingest_seen[ingest_key] = got
        return got


class ReplicaBackend(_BackendBase):
    """Warm-standby replica routing + cache-ingest reconciliation.

    Wraps an inner backend for the actual scan and models one concurrent
    dispatch slot per standby replica.  ``on_ingest`` mirrors every row the
    serving loop folds into the authoritative cache onto each member's
    delta log via the shared ``record_batch`` sink protocol
    (serving/replication.py) — members are cloud ``WarmStandby`` replicas
    and/or an edge ``EdgeReplicaPool`` (serving/edge_pool.py), so both
    replication tiers reconcile off ONE ingest notification.  A standby
    failover then resumes with exactly the cache the primary had — the
    serving loop no longer owns the only authoritative copy.

    Padded (``-1``) doc ids — emitted by the sharded search paths when the
    corpus holds fewer than k rows — gather ZERO vectors into the delta
    logs (:func:`~repro.serving.replication.gather_doc_vecs`); a raw
    ``corpus[full_ids]`` would wrap them to the LAST corpus row and
    silently corrupt every member's log.
    """

    def __init__(self, inner: FullRetrievalBackend, standbys: Sequence,
                 corpus: jax.Array):
        self.inner = inner
        self.standbys = list(standbys)
        self.corpus = corpus
        self._corpus_np = np.asarray(corpus)    # one host copy, reused
        self.n_workers = max(1, len(self.standbys))

    def search(self, q_embs, **kw):
        # kwargs pass through untouched (e.g. a HybridBackend inner's
        # q_terms/q_term_weights)
        return self.inner.search(q_embs, **kw)

    @property
    def uses_lexical(self) -> bool:
        return bool(getattr(self.inner, "uses_lexical", False))

    @property
    def q_term_width(self) -> int:
        return int(getattr(self.inner, "q_term_width", 0))

    def latency(self, batch: int) -> float:
        return self.inner.latency(batch)

    def on_ingest(self, q_embs, full_ids, state, tenant_ids=None, *,
                  ingest_key=None) -> None:
        from repro.serving.replication import gather_doc_vecs
        q_embs = np.asarray(q_embs, np.float32)
        full_ids = np.asarray(full_ids, np.int32)
        vecs = gather_doc_vecs(self._corpus_np, full_ids)  # [N, k, d]
        for sb in self.standbys:
            sb.record_batch(q_embs, full_ids, vecs, state,
                            tenant_ids=tenant_ids, ingest_key=ingest_key)

    def ingest_docs(self, vecs, ids=None, *, ingest_key=None, **kw):
        """Live-corpus ingest passthrough (an ``IVFBackend`` or
        ``HybridBackend`` inner): the inner index reconciles, and this
        wrapper refreshes its host corpus mirror so later ``on_ingest``
        gathers see the new rows.  Extra kwargs (e.g. the hybrid backend's
        ``terms``/``term_weights``) pass through untouched."""
        inner_ingest = getattr(self.inner, "ingest_docs", None)
        if inner_ingest is None:
            raise AttributeError(
                f"{type(self.inner).__name__} has no ingest_docs")
        out = inner_ingest(vecs, ids, ingest_key=ingest_key, **kw)
        inner_np = getattr(self.inner, "_corpus_np", None)
        if inner_np is not None:
            self._corpus_np = inner_np
        return out


class RetrievalService:
    """Shared substrate: corpus + latency calibration + retrieval backend.

    Composition only — the world supplies the corpus, the
    :class:`LatencyModel` supplies analytic scan times, and the
    :class:`FullRetrievalBackend` supplies the actual full-database search
    (``backend=None`` -> :class:`LocalFlatBackend`, the historical
    behavior).

    Latency accounting (see serving/latency.py): edge-local compute (cache
    channel, homology validation, cache updates) is charged at *measured*
    wall-clock — those structures run at their true paper-scale sizes here.
    Corpus-proportional compute (full ENNS scan, fuzzy IVF scan) is charged
    analytically as bytes/bandwidth at the paper's 49.2M-passage target
    scale, with the bandwidth calibrated from a measured reference scan.
    """

    def __init__(self, world, latency, k: int = 10, chunk: int = 32768,
                 calibrate: bool = False,
                 backend: FullRetrievalBackend | None = None):
        self.world = world
        self.latency = latency
        self.latency.d = world.cfg.d
        self.latency.actual_corpus = world.cfg.n_docs
        self.k = k
        self.chunk = min(chunk, world.cfg.n_docs)
        # one device-resident corpus: reuse the backend's copy when one was
        # injected (every backend holds the same world.doc_emb by contract)
        # and it sits on one device — the edge side (IVF build, cache-ingest
        # gathers) runs single-device programs, while a mesh backend keeps
        # its own row shards
        bc = getattr(backend, "corpus", None) if backend is not None else None
        sharding = getattr(bc, "sharding", None)
        if sharding is not None and len(sharding.device_set) > 1:
            bc = None
        self.corpus = bc if bc is not None else jnp.asarray(world.doc_emb)
        self.backend = backend if backend is not None else LocalFlatBackend(
            self.corpus, k, latency, chunk=self.chunk)
        # warmup (+ optional bandwidth calibration from a measured scan)
        z = jnp.zeros((1, world.cfg.d))
        self.backend.search(z)[0].block_until_ready()
        if calibrate:
            # bandwidth is defined against the UNSHARDED reference scan
            # (shard_scale etc. apply on top of it) — always time the flat
            # chunked scan, not backend.search, or a sharded backend would
            # count its speedup twice
            ref = (self.backend._search
                   if isinstance(self.backend, LocalFlatBackend)
                   else jax.jit(functools.partial(
                       chunked_flat_search, k=k, chunk=self.chunk)))
            ref(self.corpus, z)[0].block_until_ready()
            t0 = time.perf_counter()
            for _ in range(3):
                ref(self.corpus, z)[0].block_until_ready()
            self.latency.calibrate((time.perf_counter() - t0) / 3,
                                   world.cfg.n_docs)

    def _term_kw(self, q_terms, q_term_weights) -> dict:
        """Forward query terms only to backends that score them."""
        if q_terms is None or not getattr(self.backend, "uses_lexical", False):
            return {}
        return dict(q_terms=jnp.asarray(q_terms),
                    q_term_weights=(None if q_term_weights is None
                                    else jnp.asarray(q_term_weights)))

    def full_search(self, q_emb: np.ndarray, q_terms=None,
                    q_term_weights=None):
        """Exact full-database search; returns (ids [k] on the host, vecs
        [k,d] on the device, t_comp): the scan's ids come back in one read,
        and the rows are gathered by one compiled program."""
        kw = self._term_kw(None if q_terms is None else
                           np.asarray(q_terms)[None],
                           None if q_term_weights is None else
                           np.asarray(q_term_weights)[None])
        with dispatch.span("has.scan"):
            _, ids = self.backend.search(np.asarray(q_emb, np.float32)[None],
                                         **kw)
            ids = jax.device_get(ids)[0]
        with dispatch.span("has.gather"):
            vecs = _gather_rows(self.corpus, ids)
        return ids, vecs, self.backend.latency(1)

    def full_search_batch(self, q_embs, q_terms=None,
                          q_term_weights=None) -> tuple[np.ndarray, float]:
        """Coalesced exact search for [B, d]; returns (ids [B,k], t_comp)."""
        kw = self._term_kw(q_terms, q_term_weights)
        _, ids = self.backend.search(jnp.asarray(q_embs), **kw)
        return np.asarray(ids), self.backend.latency(len(q_embs))
