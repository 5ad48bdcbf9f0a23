"""Compile the Pallas kernels for a TPU v5e chip that is described, not
attached, at the widths the serving path runs them.

Interpret mode (every other kernel test) never sees Mosaic's tiling rules,
its VMEM budget or the primitives it cannot lower; these compiles do.  The
widths are one chip's share of the d=768 deployment: a 32-query speculation
batch, k=10, a 50,000-slot doc store, a 5,000-row query cache and 2,048 IVF
buckets of capacity 977 (a 1M-passage corpus at capacity factor 2).

The topology is described inside a module fixture, never at import, so the
suite collects the same tests on every worker and only the worker that runs
this file loads the TPU compiler.  The persistent compilation cache is off
around them: an entry compiled for a described chip cannot be read back.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.has import HasConfig, HasState, _speculate_batch_impl
from repro.kernels.fused_rerank import fused_rerank
from repro.kernels.homology_score import homology_score
from repro.kernels.ivf_scan import bucket_format, ivf_scan
from repro.kernels.lexical_score import lexical_score
from repro.kernels.topk_search import topk_search
from repro.retrieval.flat import chunked_flat_search
from repro.retrieval.ivf import IVFIndex, _bucket_program, _gather_buckets

B, D, K = 32, 768, 10                 # speculation batch, width, draft size
DOC_CAP, H_MAX = 50_000, 5_000        # HasConfig(h_max=5000).doc_cap
N_DOCS, N_BUCKETS, CAP, NPROBE = 1_000_000, 2048, 977, 16
POSTINGS, TERMS, Q_TERMS = 1_000_000, 5, 2
CFG = HasConfig(k=K, tau=0.2, h_max=H_MAX, nprobe=NPROBE,
                n_buckets=N_BUCKETS, d=D)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(one_chip):
    """Argument shapes on the described chip, each in the row-major layout.

    Row-major is not the chip's default for every shape: it stores
    ``f32[2048, 977, 768]`` with the 2048 buckets second-minor, since 977
    rows are no whole number of 8-row tiles.  The program compiled here
    therefore reads an index the build has already written row-major;
    ``test_speculation_reads_built_index_in_place`` takes that layout from
    the build program itself."""
    from jax.experimental.layout import Format, Layout

    def make(dims, dtype):
        fmt = Format(Layout(tuple(range(len(dims)))), one_chip)
        return jax.ShapeDtypeStruct(dims, dtype, sharding=fmt)
    return make


def _compile(fn, *args):
    """Compile for the described chip; return the optimized HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernels(hlo: str) -> list[str]:
    """Names of the Mosaic kernels the compiled program calls."""
    return re.findall(
        r"%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        hlo)


@pytest.mark.parametrize("grouped", [False, True], ids=["plain", "grouped"])
def test_topk_search_compiles(shape, grouped):
    n = DOC_CAP * (2 if grouped else 1)
    args = [shape((B, D), jnp.float32), shape((n, D), jnp.float32),
            shape((n,), bool)]
    if grouped:
        args += [shape((n,), jnp.int32), shape((B,), jnp.int32)]

    def fn(q, c, v, *groups):
        return topk_search(q, c, K, valid=v, row_group=groups[0] if groups
                           else None, q_group=groups[1] if groups else None)
    assert _kernels(_compile(fn, *args)) == ["topk_search"]


@pytest.mark.parametrize("compressed", [False, True], ids=["f32", "int8"])
def test_ivf_scan_compiles(shape, compressed):
    args = [shape((B, D), jnp.float32), shape((B, NPROBE), jnp.int32),
            shape((N_BUCKETS, CAP, D),
                  jnp.int8 if compressed else jnp.float32),
            shape((N_BUCKETS, CAP), jnp.int32)]
    if compressed:
        args += [shape((N_BUCKETS, CAP, 2), jnp.float32),
                 shape((B, NPROBE), jnp.float32)]

    def fn(q, probe, vecs, ids, *scaled):
        return ivf_scan(q, probe, vecs, ids, K,
                        bucket_scales=scaled[0] if scaled else None,
                        probe_bias=scaled[1] if scaled else None)
    assert _kernels(_compile(fn, *args)) == ["ivf_scan"]


@pytest.mark.parametrize("mode", ["plain", "grouped", "weighted"])
def test_homology_score_compiles(shape, mode):
    h = H_MAX * (2 if mode == "grouped" else 1)
    args = [shape((B, K), jnp.int32), shape((h, K), jnp.int32),
            shape((h,), bool)]
    extra = {"plain": [],
             "grouped": [shape((h,), jnp.int32), shape((B,), jnp.int32)],
             "weighted": [shape((B, K), jnp.float32)]}[mode]

    def fn(draft, cache, valid, *more):
        if mode == "grouped":
            return homology_score(draft, cache, valid, row_group=more[0],
                                  q_group=more[1])
        if mode == "weighted":
            return homology_score(draft, cache, valid, draft_weights=more[0])
        return homology_score(draft, cache, valid)
    assert _kernels(_compile(fn, *args, *extra)) == ["homology_score"]


@pytest.mark.parametrize("b,q_terms", [(B, Q_TERMS), (B, 8), (128, Q_TERMS)])
def test_lexical_score_compiles(shape, b, q_terms):
    """Postings of a 1M-passage corpus scored within the 16 MB scoped VMEM,
    also for wider query batches and longer queries than the serving
    default."""
    hlo = _compile(lambda qt, qw, dt, dw: lexical_score(qt, qw, dt, dw, K),
                   shape((b, q_terms), jnp.int32),
                   shape((b, q_terms), jnp.float32),
                   shape((POSTINGS, TERMS), jnp.int32),
                   shape((POSTINGS, TERMS), jnp.float32))
    assert _kernels(hlo) == ["lexical_score"]


@pytest.mark.parametrize("dsim", [None, 0.98], ids=["plain", "diversify"])
def test_fused_rerank_compiles(shape, dsim):
    """RRF fusion of a dense + lexical pool of k slots each."""
    hlo = _compile(
        lambda q, ids, vecs: fused_rerank(q, ids, vecs, K, K,
                                          diversify_sim=dsim),
        shape((B, D), jnp.float32), shape((B, 2 * K), jnp.int32),
        shape((B, 2 * K, D), jnp.float32))
    assert _kernels(hlo) == ["fused_rerank"]


@pytest.fixture(scope="module")
def stored(one_chip):
    """Argument shapes on the described chip in the layout the runtime
    gives a device array of that shape by default."""
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _state(shape, tenants=None):
    lead = () if tenants is None else (tenants,)
    return HasState(
        query_emb=shape(lead + (H_MAX, D), jnp.float32),
        query_doc_ids=shape(lead + (H_MAX, K), jnp.int32),
        query_valid=shape(lead + (H_MAX,), bool),
        q_ptr=shape(lead, jnp.int32),
        doc_emb=shape(lead + (DOC_CAP, D), jnp.float32),
        doc_ids=shape(lead + (DOC_CAP,), jnp.int32),
        d_ptr=shape(lead, jnp.int32))


def _speculation(shape, index, tenants):
    """The Pallas speculation program over ``index``, compiled: the
    single-tenant program, or with ``tenants`` the one over a stacked
    store."""
    args = [_state(shape, tenants), index, shape((B, D), jnp.float32)]
    if tenants is not None:
        args.append(shape((B,), jnp.int32))
    return _speculate_batch_impl.lower(
        CFG, *args, backend="pallas", interpret=False,
        tile_c=1024).compile()


@pytest.mark.parametrize("tenants", [None, 2], ids=["single", "tenants"])
def test_speculation_program_compiles(shape, tenants):
    """The whole Pallas speculation program the scheduler warms up: all
    three kernels are Mosaic custom calls, and its temporaries stay small
    beside the ~9.4 GB the chip holds resident at this size."""
    index = IVFIndex(centroids=shape((N_BUCKETS, D), jnp.float32),
                     bucket_vecs=shape((N_BUCKETS, CAP, D), jnp.float32),
                     bucket_ids=shape((N_BUCKETS, CAP), jnp.int32),
                     bucket_counts=shape((N_BUCKETS,), jnp.int32))
    compiled = _speculation(shape, index, tenants)
    assert sorted(_kernels(compiled.as_text())) == [
        "homology_score", "ivf_scan", "topk_search"]
    assert compiled.memory_analysis().temp_size_in_bytes < 512 * 2**20


@pytest.mark.parametrize("cap,dtype,asked", [
    (CAP, jnp.float32, True), (CAP, jnp.int8, True),
    (984, jnp.float32, False)], ids=["f32", "int8", "whole_tiles"])
def test_bucket_format_on_v5e(one_chip, cap, dtype, asked):
    """Row-major is asked for where the chip's default stores the buckets
    second-minor (977 rows are no whole number of tiles), and nothing
    where the default is row-major already."""
    fmt = bucket_format((N_BUCKETS, cap, D), dtype, one_chip)
    if asked:
        assert fmt.layout.major_to_minor == (0, 1, 2)
        assert fmt.sharding == one_chip
    else:
        assert fmt is None


@pytest.fixture(scope="module")
def built_vecs(one_chip, stored):
    """The bucket array as ``build_ivf``'s gather writes it at 1M x 768:
    its format read from the compiled build program, not assumed."""
    fmt = bucket_format((N_BUCKETS, CAP, D), jnp.float32, one_chip)
    assert fmt is not None and fmt.layout.major_to_minor == (0, 1, 2)
    gather = _bucket_program(_gather_buckets, "block", fmt).lower(
        stored((N_DOCS, D), jnp.float32),
        stored((N_BUCKETS, CAP), jnp.int32), block=64).compile()
    assert gather.memory_analysis().temp_size_in_bytes < 512 * 2**20
    return jax.ShapeDtypeStruct((N_BUCKETS, CAP, D), jnp.float32,
                                sharding=gather.output_formats)


@pytest.mark.parametrize("tenants", [None, 2], ids=["single", "tenants"])
def test_speculation_reads_built_index_in_place(stored, built_vecs, tenants):
    """Handed the bucket array in the format the build wrote, and every
    other argument in the runtime's default layout, the speculation
    program holds no relayout copy of the 6.1 GB array, and its
    temporaries stay small."""
    assert built_vecs.format.layout.major_to_minor == (0, 1, 2)
    index = IVFIndex(centroids=stored((N_BUCKETS, D), jnp.float32),
                     bucket_vecs=built_vecs,
                     bucket_ids=stored((N_BUCKETS, CAP), jnp.int32),
                     bucket_counts=stored((N_BUCKETS,), jnp.int32))
    compiled = _speculation(stored, index, tenants)
    hlo = compiled.as_text()
    assert sorted(_kernels(hlo)) == [
        "homology_score", "ivf_scan", "topk_search"]
    assert not re.search(
        rf"= f32\[{N_BUCKETS},{CAP},{D}\]\{{[^}}]*\}} copy\(", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 512 * 2**20


@pytest.mark.parametrize("b", [1, 16])
@pytest.mark.parametrize("d", [768, 1024])
def test_exact_scan_reads_corpus_in_place(shape, d, b):
    """The cloud scan over a 1M-row corpus (no whole number of 32,768-row
    chunks) reads the stored array: no padded copy of the corpus in its
    program, and temporaries far below one corpus (3.1 GB at d=768, 4.1 GB
    at d=1024)."""
    scan = functools.partial(chunked_flat_search, k=K, chunk=32_768)
    compiled = jax.jit(scan).lower(shape((N_DOCS, d), jnp.float32),
                                   shape((b, d), jnp.float32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2**20
    assert not re.search(r"f32\[1015808,", compiled.as_text())
