"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.topk_search import topk_search

RNG = np.random.default_rng(7)


def _topk_case(b, n, d, k, tile, plant=None):
    """A ``test_topk_search`` case; a ``plant`` case appends its name."""
    name = "-".join(map(str, (b, n, d, k, tile)))
    return pytest.param(b, n, d, k, tile, plant,
                        id=name if plant is None else f"{name}-{plant}")


@pytest.mark.parametrize("b,n,d,k,tile,plant", [
    _topk_case(1, 257, 32, 5, 64),
    _topk_case(4, 1024, 64, 10, 256),
    _topk_case(8, 5000, 128, 16, 512),
    _topk_case(2, 100, 16, 10, 128),     # corpus smaller than tile
    _topk_case(3, 4096, 64, 64, 1024),   # large k
    # ragged corpora (N = m*tile + r, r = 1 or tile - 1) whose rows all
    # score below 0, the r rows of the partial last block highest: a row
    # past N that escaped the mask (zero or garbage) would win a top-k
    # position, or push one of those r rows out
    _topk_case(2, 3 * 64 + 1, 32, 10, 64, "negative"),
    _topk_case(3, 2 * 64 + 63, 32, 10, 64, "negative"),
    # ... with ``valid`` hiding the rows that would win
    _topk_case(2, 3 * 64 + 1, 32, 10, 64, "valid"),
    _topk_case(3, 2 * 128 + 127, 32, 10, 128, "valid"),
    # ... and partitioned by row_group / q_group
    _topk_case(4, 2 * 128 + 1, 32, 5, 128, "grouped"),
    _topk_case(4, 128 + 127, 32, 5, 128, "grouped"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_search(b, n, d, k, tile, plant, dtype):
    if plant is None:
        q = jnp.asarray(RNG.normal(size=(b, d)), dtype)
        c = jnp.asarray(RNG.normal(size=(n, d)), dtype)
    else:
        q = jnp.asarray(np.abs(RNG.normal(size=(b, d))) + 0.1, dtype)
        c = -np.abs(RNG.normal(size=(n, d))) - 0.1
        c[n // tile * tile:] *= 0.1
        c = jnp.asarray(c, dtype)
    ok = np.ones((b, n), bool)          # row i may win for query j
    kw, search = {}, ops.topk_search
    if plant == "valid":
        _, top = ref.topk_search_ref(q, c, k // 2)
        valid = np.ones(n, bool)
        valid[np.asarray(top).ravel()] = False
        ok &= valid[None, :]
        kw["valid"] = jnp.asarray(valid)
    elif plant == "grouped":
        row_group = RNG.integers(0, 3, n).astype(np.int32)
        q_group = RNG.integers(0, 3, b).astype(np.int32)
        ok &= row_group[None, :] == q_group[:, None]
        kw.update(row_group=jnp.asarray(row_group),
                  q_group=jnp.asarray(q_group))
        search = topk_search            # the ops wrapper passes no groups
    v, i = search(q, c, k, tile_c=tile, interpret=True, **kw)
    # the oracle, query by query over the rows that query may see
    vr, ir = [], []
    for j in range(b):
        rows = np.flatnonzero(ok[j])
        vj, ij = ref.topk_search_ref(q[j:j + 1], c[rows], k)
        vr.append(np.asarray(vj[0]))
        ir.append(rows[np.asarray(ij[0])])
    vr, ir = np.stack(vr), np.stack(ir)
    i = np.asarray(i)
    assert ((i >= 0) & (i < n)).all()
    assert np.take_along_axis(ok, i, axis=1).all()
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.sort(i, 1), np.sort(ir, 1))
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(v), vr, rtol=tol, atol=tol)
    # ids can differ on exact ties: check score-equivalence instead
    got = np.asarray(jnp.sum(c[i] * q[:, None, :], -1), np.float32)
    np.testing.assert_allclose(got, vr.astype(np.float32),
                               rtol=max(tol, 1e-4), atol=max(tol, 1e-4))


@pytest.mark.parametrize("b,h,k,tile", [
    (1, 100, 10, 64), (4, 1000, 10, 256), (8, 5000, 4, 512),
    (2, 513, 16, 512),
])
def test_homology_score(b, h, k, tile):
    draft = jnp.asarray(RNG.integers(-1, 60, (b, k)), jnp.int32)
    cache = jnp.asarray(RNG.integers(0, 60, (h, k)), jnp.int32)
    valid = jnp.asarray(RNG.random(h) > 0.3)
    s = ops.homology_score(draft, cache, valid, tile_h=tile, interpret=True)
    sr = ref.homology_score_ref(draft, cache, valid)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=1e-6)


@pytest.mark.parametrize("b,c,cap,d,p,k", [
    (2, 8, 16, 32, 3, 5), (4, 32, 64, 64, 8, 10), (1, 4, 8, 16, 2, 4),
])
def test_ivf_scan(b, c, cap, d, p, k):
    q = jnp.asarray(RNG.normal(size=(b, d)), jnp.float32)
    bv = jnp.asarray(RNG.normal(size=(c, cap, d)), jnp.float32)
    bi = jnp.asarray(RNG.integers(0, 10000, (c, cap)), jnp.int32)
    bi = jnp.where(jnp.asarray(RNG.random((c, cap)) > 0.85), -1, bi)
    probe = jnp.asarray(
        np.stack([RNG.choice(c, p, replace=False) for _ in range(b)]),
        jnp.int32)
    v, i = ops.ivf_scan(q, probe, bv, bi, k, interpret=True)
    vr, ir = ref.ivf_scan_ref(q, probe, bv, bi, k)
    np.testing.assert_allclose(np.asarray(v), np.asarray(vr),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("v,d,b,n", [(100, 32, 8, 4), (33, 8, 2, 9),
                                     (500, 64, 16, 2)])
@pytest.mark.parametrize("mode", ["sum", "mean"])
@pytest.mark.parametrize("weighted", [False, True])
def test_embedding_bag(v, d, b, n, mode, weighted):
    t = jnp.asarray(RNG.normal(size=(v, d)), jnp.float32)
    ids = jnp.asarray(RNG.integers(0, v, (b, n)), jnp.int32)
    w = jnp.asarray(RNG.normal(size=(b, n)), jnp.float32) if weighted else None
    o = ops.embedding_bag(t, ids, w, mode, interpret=True)
    orf = ref.embedding_bag_ref(t, ids, w, mode)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=1e-4, atol=1e-5)


def test_embedding_bag_matches_segment_sum_substrate():
    """Kernel == the take+segment_sum substrate used by the models."""
    from repro.models.recsys import embedding_bag as substrate_bag
    t = jnp.asarray(RNG.normal(size=(64, 16)), jnp.float32)
    ids = jnp.asarray(RNG.integers(0, 64, (6, 5)), jnp.int32)
    seg = jnp.repeat(jnp.arange(6), 5)
    o1 = ops.embedding_bag(t, ids, mode="sum", interpret=True)
    o2 = substrate_bag(t, ids.reshape(-1), seg, 6, mode="sum")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5)


@pytest.mark.parametrize("b,h,d,s,blk,clen", [
    (2, 4, 16, 128, 32, 100), (1, 8, 32, 300, 64, 299), (3, 2, 8, 64, 64, 0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(b, h, d, s, blk, clen, dtype):
    from repro.kernels.decode_attention import decode_attention_ref
    q = jnp.asarray(RNG.normal(size=(b, h, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, s, h, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, s, h, d)), dtype)
    o = ops.decode_attention(q, k, v, jnp.int32(clen), block_s=blk,
                             interpret=True)
    orf = decode_attention_ref(q, k, v, jnp.int32(clen))
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=tol, atol=tol)
