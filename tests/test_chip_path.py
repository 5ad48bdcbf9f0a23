"""Host-side pieces of the chip path, checked on the CPU.

``chip_smoke.py`` refuses to run without a TPU; the compile cache helper
keeps an externally chosen directory and otherwise a fixed in-repo one; the
world generator stays bit-identical however it is chunked; the IVF bucket
gather zero-fills pad slots; and the staged ``launch/serve.py`` build
carries ``--dim`` through to the scheduler.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.data.synthetic as synthetic
from repro.retrieval.ivf import _bucket_gather
from repro.utils import use_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_cpu(capsys):
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert "'cpu'" in out.err
    assert '"ok"' not in out.out


def test_compile_cache_keeps_env_dir(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert use_compile_cache() == path          # stable across calls
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("chunk", [1000, 4999])
def test_world_generation_chunk_invariant(monkeypatch, chunk):
    """Row-chunked generation draws the same stream as one whole-corpus
    pass: every array of the world is bit-identical."""
    cfg = synthetic.WorldConfig(n_entities=1000, d=48, seed=4)
    whole = synthetic.SyntheticWorld(cfg)
    monkeypatch.setattr(synthetic, "_EMB_CHUNK", chunk)
    parts = synthetic.SyntheticWorld(cfg)
    for name in ("doc_emb", "doc_attr_mask", "entity_attrs", "doc_terms"):
        assert np.array_equal(getattr(whole, name), getattr(parts, name))
    assert parts.doc_emb.dtype == np.float32


@pytest.mark.parametrize("block", [1, 2, 4])
def test_bucket_gather_zero_fills_pads(block):
    rng = np.random.default_rng(0)
    corpus = rng.normal(size=(30, 8)).astype(np.float32)
    ids = rng.integers(-1, 30, size=(4, 5)).astype(np.int32)
    got = np.asarray(_bucket_gather(jnp.asarray(corpus), jnp.asarray(ids),
                                    block=block))
    want = np.where((ids >= 0)[..., None], corpus[np.maximum(ids, 0)], 0.0)
    assert np.array_equal(got, want)


def test_serve_build_carries_dim():
    from repro.launch import serve
    args = serve.parse_args(["--engine", "sched", "--entities", "60",
                             "--dim", "32", "--queries", "12", "--qps",
                             "50"])
    stack = serve.build(args)
    assert stack.world.doc_emb.shape == (300, 32)
    assert stack.engine.cfg.d == 32
    assert stack.engine.spec_backend == "xla"       # the CPU default
    assert len(stack.arrivals) == len(stack.queries) == 12
    result = stack.engine.serve(stack.queries, stack.arrivals)
    assert result.served_ids.shape == (12, 10)


@pytest.mark.parametrize("dim", ["0", "7"])
def test_serve_rejects_bad_dim(dim):
    from repro.launch import serve
    with pytest.raises(SystemExit):
        serve.parse_args(["--dim", dim])
