"""HaS core invariants: FIFO cache, dedup, homology math, Algorithm 1
equivalence between the jitted fixed-shape engine and the faithful
hash-map reference (core/reference.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
# real hypothesis when installed, skip-stubs otherwise (see conftest.py)
from conftest import given, settings, st

from repro.core.has import (HasConfig, cache_update, init_has_state,
                            speculate_batch)
from repro.core.homology import (homology_scores, pairwise_homology,
                                 reidentify)
from repro.core.reference import RefHas


def test_cache_fifo_eviction():
    cfg = HasConfig(k=4, h_max=3, doc_capacity=64, d=8)
    state = init_has_state(cfg)
    rng = np.random.default_rng(0)
    for i in range(7):
        ids = jnp.asarray(np.arange(i * 4, i * 4 + 4), jnp.int32)
        vecs = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
        state = cache_update(cfg, state, jnp.ones((8,)), ids, vecs)
    assert int(state.q_ptr) == 7
    # only the last h_max=3 queries survive, in ring order
    live = set(np.asarray(state.query_doc_ids).reshape(-1).tolist())
    expected = set(range(16, 28))   # queries 4,5,6 -> ids 16..27
    assert expected <= live


def test_doc_dedup_on_insert():
    cfg = HasConfig(k=4, h_max=8, doc_capacity=32, d=4)
    state = init_has_state(cfg)
    ids = jnp.asarray([1, 2, 3, 4], jnp.int32)
    vecs = jnp.ones((4, 4))
    state = cache_update(cfg, state, jnp.ones((4,)), ids, vecs)
    state = cache_update(cfg, state, jnp.ones((4,)), ids, vecs)  # same docs
    live = np.asarray(state.doc_ids)
    assert sorted(live[live >= 0].tolist()) == [1, 2, 3, 4]
    assert int(state.d_ptr) == 4     # no duplicate slots consumed


def test_homology_score_definition():
    # s(q1,q2) = |D1 ∩ D2| / k  (Definition 5)
    a = jnp.asarray([1, 2, 3, 4], jnp.int32)
    b = jnp.asarray([3, 4, 5, 6], jnp.int32)
    assert float(pairwise_homology(a, b)) == 0.5
    assert float(pairwise_homology(a, a)) == 1.0
    assert float(pairwise_homology(a, jnp.asarray([7, 8, 9, 10], jnp.int32))) == 0.0


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 2**31 - 2), st.integers(1, 10))
def test_homology_symmetric_and_bounded(seed, k):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(0, 20, k), jnp.int32)
    b = jnp.asarray(rng.integers(0, 20, k), jnp.int32)
    # NOTE: result sets contain distinct docs in practice; with duplicates
    # the overlap count is still bounded by k
    sab = float(pairwise_homology(a, b))
    assert 0.0 <= sab <= 1.0
    # identical sets always score 1
    assert float(pairwise_homology(a, a)) == 1.0


def test_reidentify_threshold_strict():
    cache = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    valid = jnp.asarray([True, True])
    draft = jnp.asarray([1, 2, 9, 10], jnp.int32)   # overlap 2/4 = 0.5
    acc, best, slot = reidentify(draft, cache, valid, jnp.float32(0.5))
    assert not bool(acc)            # strict >
    acc, _, slot = reidentify(draft, cache, valid, jnp.float32(0.49))
    assert bool(acc) and int(slot) == 0


def test_invalid_slots_score_zero():
    cache = jnp.asarray([[1, 2], [1, 2]], jnp.int32)
    valid = jnp.asarray([False, True])
    s = homology_scores(jnp.asarray([1, 2], jnp.int32), cache, valid)
    assert float(s[0]) == 0.0 and float(s[1]) == 1.0


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_algorithm1_equivalence_with_reference(backend):
    """Jitted fixed-shape HaS == faithful hash-map reference, per query,
    through the B=1 speculation program the sequential engine runs."""
    k, h_max, doc_cap, d = 5, 16, 128, 16
    cfg = HasConfig(k=k, tau=0.3, h_max=h_max, doc_capacity=doc_cap,
                    nprobe=2, n_buckets=4, d=d,
                    use_fuzzy_validation=False, use_fuzzy_enhancement=False)
    refi = RefHas(k=k, tau=0.3, h_max=h_max, doc_cap=doc_cap)
    state = init_has_state(cfg)

    rng = np.random.default_rng(3)
    corpus = rng.normal(size=(256, d)).astype(np.float32)
    corpus /= np.linalg.norm(corpus, axis=1, keepdims=True)

    from repro.retrieval.ivf import build_ivf
    index = build_ivf(jnp.asarray(corpus), cfg.n_buckets, seed=0)

    for step in range(60):
        q = rng.normal(size=(d,)).astype(np.float32)
        q /= np.linalg.norm(q)
        out = speculate_batch(cfg, state, index, q[None], backend=backend,
                              interpret=True)
        out = {key: np.asarray(v)[0] for key, v in out.items()}
        # reference: cache-channel draft + inverted-index validation
        ref_ids, _ = refi.cache_channel(q)
        accept_ref, _ = refi.validate(ref_ids)
        got_ids = out["val_ids"]
        live_got = sorted(int(i) for i in got_ids if i >= 0)
        live_ref = sorted(int(i) for i in ref_ids if i >= 0)
        assert live_got == live_ref, (step, live_got, live_ref)
        assert bool(out["accept"]) == accept_ref, step
        if not accept_ref:
            full = np.argsort(-(corpus @ q))[:k].astype(np.int32)
            state = cache_update(cfg, state, jnp.asarray(q),
                                 jnp.asarray(full), jnp.asarray(corpus[full]))
            refi.update(q, full, corpus[full])


def test_fuzzy_ablation_flags():
    """Table VI flags: V/E control which channels feed validation/output."""
    cfg_full = HasConfig(k=4, tau=0.1, h_max=8, doc_capacity=32,
                         nprobe=2, n_buckets=4, d=8)
    cfg_noE = HasConfig(k=4, tau=0.1, h_max=8, doc_capacity=32,
                        nprobe=2, n_buckets=4, d=8,
                        use_fuzzy_enhancement=False)
    rng = np.random.default_rng(0)
    corpus = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    from repro.retrieval.ivf import build_ivf
    index = build_ivf(corpus, 4, seed=0)
    state = init_has_state(cfg_full)
    # insert one query so the cache channel is non-empty
    state = cache_update(cfg_full, state, jnp.ones((8,)),
                         jnp.asarray([0, 1, 2, 3], jnp.int32), corpus[:4])
    q = jnp.asarray(rng.normal(size=(1, 8)), jnp.float32)
    out_full = speculate_batch(cfg_full, state, index, q)
    out_noE = speculate_batch(cfg_noE, state, index, q)
    # without enhancement the returned draft only contains cached docs
    cached = {0, 1, 2, 3, -1}
    assert set(np.asarray(out_noE["draft_ids"])[0].tolist()) <= cached
    # validation drafts identical (V on in both)
    assert np.array_equal(np.asarray(out_full["val_ids"]),
                          np.asarray(out_noE["val_ids"]))
