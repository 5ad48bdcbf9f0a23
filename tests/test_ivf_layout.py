"""The IVF bucket array is stored in the layout ``ivf_scan`` reads.

``bucket_format`` names that layout (row-major) wherever the backend would
store the array otherwise, and ``build_ivf`` writes it there directly.  On
the CPU the backend's default is row-major already, so the helper is a
no-op; these tests also force it on, in the layout it names and in the
TPU's default one, and check that the index's contents and every answer of
the speculation program stay bit-identical, that the program compiles once
for a stored index, that ``subset_index`` keeps the stored layout, and that
the layout survives JAX's persistent compilation cache.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

import repro.retrieval.ivf as ivf
from repro.core.has import (HasConfig, _speculate_batch_impl, cache_update,
                            init_has_state, init_tenant_states,
                            speculate_batch)
from repro.kernels.ivf_scan import bucket_format

N_DOCS, N_BUCKETS = 300, 8            # capacity 75: no whole 8-row tile
CFG = HasConfig(k=4, tau=0.2, h_max=24, doc_capacity=64, nprobe=3,
                n_buckets=N_BUCKETS, d=16)
# the layout the helper names, and the TPU's default for such a shape
LAYOUTS = {"row_major": (0, 1, 2), "buckets_second_minor": (1, 0, 2)}


def _corpus(seed=0):
    rng = np.random.default_rng(seed)
    corpus = rng.normal(size=(N_DOCS, CFG.d)).astype(np.float32)
    return corpus / np.linalg.norm(corpus, axis=1, keepdims=True)


def _store_in(monkeypatch, major_to_minor):
    """Make the build ask for ``major_to_minor``, as it does on a TPU."""
    monkeypatch.setattr(
        ivf, "bucket_format",
        lambda shape, dtype, sharding: Format(Layout(major_to_minor),
                                              sharding))


def _warm(state, corpus, tenants=None, n=8, seed=1):
    rng = np.random.default_rng(seed)
    for i in range(n):
        q = rng.normal(size=(CFG.d,)).astype(np.float32)
        ids = np.argsort(-(corpus @ q))[:CFG.k].astype(np.int32)
        state = cache_update(CFG, state, jnp.asarray(q), jnp.asarray(ids),
                             jnp.asarray(corpus[ids]),
                             tenant_id=None if tenants is None
                             else i % tenants)
    return state


def test_bucket_format_is_a_noop_on_cpu():
    sharding = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])
    for shape in [(2048, 977, 768), (N_BUCKETS, 75, CFG.d)]:
        for dtype in (jnp.float32, jnp.int8):
            assert bucket_format(shape, dtype, sharding) is None


@pytest.mark.parametrize("layout", [None, *LAYOUTS],
                         ids=["default", *LAYOUTS])
def test_build_writes_corpus_rows_in_the_stored_layout(monkeypatch, layout):
    if layout is not None:
        _store_in(monkeypatch, LAYOUTS[layout])
    corpus = _corpus()
    index = ivf.build_ivf(jnp.asarray(corpus), N_BUCKETS, seed=0)
    cap = int(np.ceil(N_DOCS / N_BUCKETS * 2.0))
    assert index.bucket_vecs.shape == (N_BUCKETS, cap, CFG.d)
    assert index.bucket_vecs.dtype == jnp.float32
    assert index.bucket_ids.shape == (N_BUCKETS, cap)
    ids = np.asarray(index.bucket_ids)
    want = np.where((ids >= 0)[..., None], corpus[np.maximum(ids, 0)], 0.0)
    assert np.array_equal(np.asarray(index.bucket_vecs), want)
    m2m = index.bucket_vecs.format.layout.major_to_minor
    assert m2m == (LAYOUTS[layout] if layout else (0, 1, 2))


@pytest.mark.parametrize("tenants", [None, 2], ids=["single", "tenants"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_speculation_bit_identical_with_stored_layout(monkeypatch, layout,
                                                      backend, tenants):
    corpus = _corpus()
    plain = ivf.build_ivf(jnp.asarray(corpus), N_BUCKETS, seed=0)
    _store_in(monkeypatch, LAYOUTS[layout])
    stored = ivf.build_ivf(jnp.asarray(corpus), N_BUCKETS, seed=0)
    assert stored.bucket_vecs.format.layout.major_to_minor == LAYOUTS[layout]
    state = _warm(init_has_state(CFG) if tenants is None
                  else init_tenant_states(CFG, tenants), corpus, tenants)
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(5, CFG.d)), jnp.float32)
    tids = None if tenants is None else jnp.arange(5, dtype=jnp.int32) % 2
    kw = dict(backend=backend, tile_c=64, tenant_ids=tids,
              **({"interpret": True} if backend == "pallas" else {}))
    a = speculate_batch(CFG, state, plain, q, **kw)
    b = speculate_batch(CFG, state, stored, q, **kw)
    for key in ("accept", "homology", "val_ids", "draft_ids",
                "draft_scores", "matched_slot"):
        assert np.array_equal(np.asarray(a[key]), np.asarray(b[key])), key


def test_stored_index_compiles_once(monkeypatch):
    """Repeated calls, a warmer cache and a rebuilt index in the same
    layout all reuse one compiled program."""
    _store_in(monkeypatch, LAYOUTS["row_major"])
    corpus = _corpus(seed=5)
    cfg = HasConfig(k=3, tau=0.3, h_max=20, doc_capacity=48, nprobe=2,
                    n_buckets=N_BUCKETS, d=CFG.d)
    index = ivf.build_ivf(jnp.asarray(corpus), N_BUCKETS, seed=0)
    state = init_has_state(cfg)
    q = jnp.asarray(np.random.default_rng(3).normal(size=(1, cfg.d)),
                    jnp.float32)
    before = _speculate_batch_impl._cache_size()
    for i in range(3):
        out = speculate_batch(cfg, state, index, q, backend="xla")
        ids = np.asarray(out["draft_ids"])[0]
        state = cache_update(cfg, state, q[0], jnp.asarray(ids),
                             jnp.asarray(corpus[ids]))
    index = ivf.build_ivf(jnp.asarray(corpus), N_BUCKETS, seed=0)
    speculate_batch(cfg, state, index, q, backend="xla")
    assert _speculate_batch_impl._cache_size() == before + 1


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_subset_index_keeps_the_stored_layout(monkeypatch, layout):
    _store_in(monkeypatch, LAYOUTS[layout])
    corpus = _corpus()
    index = ivf.build_ivf(jnp.asarray(corpus), N_BUCKETS, seed=0)
    half = ivf.subset_index(index, 0.5)
    cap = max(1, int(index.capacity * 0.5))
    assert half.bucket_vecs.shape == (N_BUCKETS, cap, CFG.d)
    assert half.bucket_vecs.format.layout.major_to_minor == LAYOUTS[layout]
    assert np.array_equal(np.asarray(half.bucket_vecs),
                          np.asarray(index.bucket_vecs)[:, :cap])
    assert np.array_equal(np.asarray(half.bucket_ids),
                          np.asarray(index.bucket_ids)[:, :cap])


def test_stored_layout_survives_the_persistent_cache(monkeypatch, tmp_path):
    """A second build in a process whose programs are all in the persistent
    cache still hands out the array under its own layout, and a program
    read back from the cache reads it right."""
    from jax.experimental.compilation_cache import compilation_cache
    _store_in(monkeypatch, LAYOUTS["buckets_second_minor"])
    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    corpus = _corpus(seed=7)
    state = _warm(init_has_state(CFG), corpus)
    q = jnp.asarray(np.random.default_rng(4).normal(size=(3, CFG.d)),
                    jnp.float32)
    want = speculate_batch(CFG, state, ivf.build_ivf(jnp.asarray(corpus),
                                                     N_BUCKETS, seed=0),
                           q, backend="xla")
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        for _ in range(2):              # the second pass reads the cache
            jax.clear_caches()
            index = ivf.build_ivf(jnp.asarray(corpus), N_BUCKETS, seed=0)
            assert (index.bucket_vecs.format.layout.major_to_minor
                    == LAYOUTS["buckets_second_minor"])
            got = speculate_batch(CFG, state, index, q, backend="xla")
            for key in ("accept", "homology", "val_ids", "draft_ids"):
                assert np.array_equal(np.asarray(got[key]),
                                      np.asarray(want[key])), key
        assert any(tmp_path.iterdir())
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        jax.clear_caches()
