"""End-to-end fault-tolerance scenario: crash mid-training -> resume ->
elastic downscale plan, plus hypothesis property tests on HaS invariants."""
import numpy as np
import pytest
# real hypothesis when installed, skip-stubs otherwise (see conftest.py)
from conftest import given, settings, st

import jax
import jax.numpy as jnp


def test_crash_resume_identical_state(tmp_path):
    """Training resumed from a checkpoint continues from the same state."""
    from repro.checkpoint import CheckpointManager
    from repro.launch.train import train_lm
    from repro.models.transformer import TransformerConfig
    cfg = TransformerConfig(name="t", n_layers=1, d_model=32, n_heads=2,
                            n_kv_heads=1, d_ff=64, vocab_size=64, d_head=16,
                            remat=False)
    # run 1: 60 steps, checkpoints at 50
    train_lm(cfg, steps=60, batch=2, seq=16, ckpt_dir=str(tmp_path),
             log_every=1000)
    mgr = CheckpointManager(str(tmp_path))
    assert 50 in mgr.all_steps() or 60 in mgr.all_steps()
    # 'crash' and resume: restores from the latest checkpoint without error
    losses = train_lm(cfg, steps=70, batch=2, seq=16,
                      ckpt_dir=str(tmp_path), log_every=1000)
    assert len(losses) <= 20          # resumed, did not restart from 0


def test_elastic_downscale_then_upscale():
    from repro.training.fault import ElasticPlan
    down = ElasticPlan.plan(old_data=16, surviving_hosts=12)
    assert down.new_data == 12 and down.accum_steps * down.new_data >= 16
    up = ElasticPlan.plan(old_data=down.new_data, surviving_hosts=16)
    assert up.new_data == 16 and up.accum_steps == 1


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10_000))
def test_homology_accept_monotone_in_tau(seed):
    """Property: raising tau can only flip accept -> reject."""
    from repro.core.homology import reidentify
    rng = np.random.default_rng(seed)
    draft = jnp.asarray(rng.integers(0, 30, 6), jnp.int32)
    cache = jnp.asarray(rng.integers(0, 30, (12, 6)), jnp.int32)
    valid = jnp.asarray(rng.random(12) > 0.3)
    acc_lo, s, _ = reidentify(draft, cache, valid, jnp.float32(0.1))
    acc_hi, _, _ = reidentify(draft, cache, valid, jnp.float32(0.5))
    assert bool(acc_lo) or not bool(acc_hi)      # hi accept => lo accept


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 12))
def test_cache_ring_never_exceeds_capacity(seed, n_inserts):
    from repro.core.has import HasConfig, cache_update, init_has_state
    rng = np.random.default_rng(seed)
    cfg = HasConfig(k=3, h_max=4, doc_capacity=16, d=4)
    state = init_has_state(cfg)
    for i in range(n_inserts):
        ids = jnp.asarray(rng.integers(0, 100, 3), jnp.int32)
        state = cache_update(cfg, state, jnp.ones((4,)), ids,
                             jnp.ones((3, 4)))
    assert int(jnp.sum(state.query_valid)) <= cfg.h_max
    assert int(jnp.sum(state.doc_ids >= 0)) <= cfg.doc_cap
    assert int(state.q_ptr) == n_inserts


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_speculate_draft_ids_come_from_channels(seed):
    """Property: every returned draft id is a live cached doc or an IVF-
    indexed corpus id (never fabricated)."""
    from repro.core.has import (HasConfig, cache_update, init_has_state,
                                speculate_batch)
    from repro.retrieval.ivf import build_ivf
    rng = np.random.default_rng(seed)
    cfg = HasConfig(k=4, tau=0.3, h_max=8, doc_capacity=32, nprobe=2,
                    n_buckets=4, d=8)
    corpus = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    index = build_ivf(corpus, 4, seed=0)
    state = init_has_state(cfg)
    ids0 = jnp.asarray(rng.integers(0, 64, 4), jnp.int32)
    state = cache_update(cfg, state, jnp.ones((8,)), ids0, corpus[ids0])
    q = jnp.asarray(rng.normal(size=(1, 8)), jnp.float32)
    out = speculate_batch(cfg, state, index, q)
    live = set(np.asarray(state.doc_ids)[np.asarray(state.doc_ids) >= 0])
    indexed = set(np.asarray(index.bucket_ids).reshape(-1))
    for d in np.asarray(out["draft_ids"])[0]:
        assert d == -1 or int(d) in (live | indexed)
