"""Retrieval substrate: chunked==flat, IVF recall, int8 store, distributed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
# real hypothesis when installed, skip-stubs otherwise (see conftest.py)
from conftest import given, settings, st

from repro.retrieval.flat import (chunked_flat_search, flat_search,
                                  quantize_store, quantized_search)
from repro.retrieval.ivf import build_ivf, ivf_search, subset_index


def _unit(rng, n, d):
    x = rng.normal(size=(n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def test_chunked_equals_flat(rng):
    corpus = jnp.asarray(_unit(rng, 1000, 32))
    q = jnp.asarray(_unit(rng, 5, 32))
    s1, i1 = flat_search(corpus, q, 10)
    s2, i2 = chunked_flat_search(corpus, q, 10, chunk=128)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))


SCAN_CHUNK = 128


@pytest.mark.parametrize("b", [1, 16])
@pytest.mark.parametrize("d", [64, 1024])
@pytest.mark.parametrize("n", [1, SCAN_CHUNK - 1, SCAN_CHUNK, SCAN_CHUNK + 1,
                               3 * SCAN_CHUNK + 17])
def test_chunked_scan_matches_float64_topk(n, d, b):
    """Whole chunks and a ragged tail, read in place, give the exact top-k:
    the ids of a float64 numpy scan wherever its gap to the next score is
    above 1e-6, and its scores within 2^-22, the float32 tolerance of a
    unit-vector inner product.  Past the corpus, ids are -1."""
    k = 10
    rng = np.random.default_rng([n, d, b])
    corpus, q = _unit(rng, n, d), _unit(rng, b, d)
    s, i = (np.asarray(x) for x in chunked_flat_search(
        jnp.asarray(corpus), jnp.asarray(q), k, chunk=SCAN_CHUNK))
    full = q.astype(np.float64) @ corpus.astype(np.float64).T
    order = np.argsort(-full, axis=1, kind="stable")
    m = min(k, n)
    want_i = order[:, :m]
    want_s = np.take_along_axis(full, want_i, axis=1)
    nxt = np.take_along_axis(full, order[:, 1:m + 1], axis=1) if n > m \
        else np.full((b, m), -np.inf)
    nxt[:, :m - 1] = want_s[:, 1:]
    clear = want_s - nxt > 1e-6
    assert np.array_equal(i[:, :m][clear], want_i[clear])
    np.testing.assert_allclose(s[:, :m], want_s, rtol=0, atol=2.0 ** -22)
    assert (i[:, m:] == -1).all() and np.isneginf(s[:, m:]).all()


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 1000), st.integers(1, 7), st.sampled_from([64, 100, 257]))
def test_chunked_property(seed, k, n):
    rng = np.random.default_rng(seed)
    corpus = jnp.asarray(_unit(rng, n, 16))
    q = jnp.asarray(_unit(rng, 2, 16))
    s1, i1 = flat_search(corpus, q, k)
    s2, i2 = chunked_flat_search(corpus, q, k, chunk=50)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-6)


def test_ivf_recall(rng):
    corpus = jnp.asarray(_unit(rng, 2000, 32))
    index = build_ivf(corpus, 16, seed=0)
    q = jnp.asarray(_unit(rng, 20, 32))
    _, exact = flat_search(corpus, q, 10)
    _, approx = ivf_search(index, q, nprobe=8, k=10)
    recall = np.mean([len(set(a) & set(e)) / 10
                      for a, e in zip(np.asarray(approx), np.asarray(exact))])
    assert recall > 0.6   # half the buckets probed -> decent recall
    # more probes -> recall must not decrease (on average)
    _, approx_all = ivf_search(index, q, nprobe=16, k=10)
    recall_all = np.mean([len(set(a) & set(e)) / 10 for a, e in
                          zip(np.asarray(approx_all), np.asarray(exact))])
    assert recall_all >= recall - 1e-9


def test_ivf_all_vectors_indexed_once(rng):
    corpus = jnp.asarray(_unit(rng, 512, 16))
    index = build_ivf(corpus, 8, capacity_factor=8.0, seed=0)
    ids = np.asarray(index.bucket_ids)
    live = ids[ids >= 0]
    assert len(live) == 512 and len(set(live.tolist())) == 512


def test_subset_index_compression(rng):
    corpus = jnp.asarray(_unit(rng, 512, 16))
    index = build_ivf(corpus, 8, seed=0)
    sub = subset_index(index, 0.25)
    assert sub.capacity == max(1, index.capacity // 4)


def test_quantized_store_error_bound(rng):
    corpus = jnp.asarray(_unit(rng, 300, 32))
    store = quantize_store(corpus)
    deq = store["q"].astype(jnp.float32) * store["scale"][:, None]
    err = float(jnp.max(jnp.abs(deq - corpus)))
    assert err <= float(jnp.max(store["scale"])) * 0.5 + 1e-6


def test_quantized_search_with_rescore(rng):
    corpus = jnp.asarray(_unit(rng, 500, 32))
    q = jnp.asarray(_unit(rng, 4, 32))
    store = quantize_store(corpus)
    _, exact = flat_search(corpus, q, 5)
    _, approx = quantized_search(store, q, 5, rescore=corpus)
    recall = np.mean([len(set(a) & set(e)) / 5
                      for a, e in zip(np.asarray(approx), np.asarray(exact))])
    assert recall > 0.9


def test_distributed_topk_single_device():
    """shard_map distributed top-k on a 1x1 mesh == flat search."""
    from repro.launch.mesh import make_local_mesh
    from repro.retrieval.distributed import distributed_flat_search
    rng = np.random.default_rng(0)
    corpus = jnp.asarray(_unit(rng, 256, 16))
    q = jnp.asarray(_unit(rng, 3, 16))
    mesh = make_local_mesh()
    search = distributed_flat_search(mesh, ("data", "model"))
    s, i = jax.jit(lambda c, qq: search(c, qq, 7))(corpus, q)
    se, ie = flat_search(corpus, q, 7)
    np.testing.assert_allclose(np.asarray(s), np.asarray(se), rtol=1e-5)
    assert np.array_equal(np.asarray(i), np.asarray(ie))
