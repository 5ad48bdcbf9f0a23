"""The benchmark's own world and streams, at a tiny size on the CPU."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench import world

CONFIG = {"n_entities": 500, "docs_per_entity": 5, "n_docs": 2500,
          "attrs_per_entity": 12, "attrs_per_doc": 4, "d": 64,
          "encoder_profile": {"entity_weight": 1.0, "attr_weight_doc": 0.55,
                              "attr_weight_query": 0.65, "noise_doc": 1.0,
                              "noise_query": 1.1}}
TRAFFIC = {"zipf_a": 1.12, "p_uncovered": 0.42, "rank_seed": 5}
SHAPE = world.WorldShape(500, 5, 12, 4, 64)


def _world(seed, n=2000, traffic=TRAFFIC):
    tables = world.host_tables(SHAPE, seed)
    ents, attrs = world.sample_stream(n, traffic, tables[2], seed)
    return world.World(CONFIG, seed, ents, attrs, tables), ents, attrs


@pytest.fixture(scope="module")
def made():
    return _world(2 ** 31 + 17)       # seeds run past 32 signed bits


def test_shapes_units_and_seed(made):
    w, ents, attrs = made
    emb = np.asarray(w.doc_emb)
    assert emb.shape == (2500, 64) and emb.dtype == np.float32
    np.testing.assert_allclose(np.linalg.norm(emb, axis=1), 1, atol=1e-5)
    np.testing.assert_allclose(np.linalg.norm(w.query_emb, axis=1), 1,
                               atol=1e-5)
    again, e2, a2 = _world(2 ** 31 + 17)
    np.testing.assert_array_equal(np.asarray(again.doc_emb), emb)
    np.testing.assert_array_equal(again.query_emb, w.query_emb)
    np.testing.assert_array_equal(e2, ents)
    other, e3, _ = _world(3)
    assert not np.array_equal(np.asarray(other.doc_emb), emb)
    assert not np.array_equal(e3, ents)


def test_query_prefix_does_not_depend_on_stream_length(made):
    w, ents, _ = made
    short, e_short, _ = _world(2 ** 31 + 17, n=300)
    np.testing.assert_array_equal(e_short, ents[:300])
    np.testing.assert_array_equal(short.query_emb, w.query_emb[:300])


def test_attribute_coverage(made):
    w, ents, attrs = made
    assert (w.doc_attr_mask.sum(axis=1) == 4).all()
    cov = w.doc_attr_mask.reshape(500, 5, 12).any(axis=1)
    np.testing.assert_array_equal(cov, w.entity_attrs)
    # asked attributes: covered ones mostly, uncovered ones sometimes
    asked_cov = w.entity_attrs[ents, attrs]
    assert 0.6 < asked_cov.mean() < 1.0


def test_entity_alignment_and_golden_oracle(made):
    """Retrieval is entity-aligned (the encoder's entity bias), and the
    oracle marks a document golden iff entity and attribute match."""
    w, ents, attrs = made
    emb = np.asarray(w.doc_emb)
    q = w.query_emb[:400]
    top5 = np.argsort(-(q @ emb.T), axis=1)[:, :5]
    aligned = (w.doc_entity[top5] == ents[:400, None]).sum(axis=1).mean()
    assert 1.8 < aligned <= 5.0
    g = world.golden(w.doc_entity, w.doc_attr_mask, ents[:400], attrs[:400],
                     top5)
    expect = ((w.doc_entity[top5] == ents[:400, None])
              & w.doc_attr_mask[top5, attrs[:400, None]])
    np.testing.assert_array_equal(g, expect)
    assert not world.golden(w.doc_entity, w.doc_attr_mask, 0, 0,
                            np.array([-1])).any()
    hits = world.doc_hits(w, ents[:400], attrs[:400], top5)
    assert 0.3 < hits.mean() < 1.0


def test_zipf_head_share_and_shared_profile():
    tables = world.host_tables(SHAPE, 1)
    n = 20000
    e1, _ = world.sample_stream(n, TRAFFIC, tables[2], 1)
    e2, _ = world.sample_stream(n, TRAFFIC, tables[2], 2)
    # the same popularity profile for every seed, other entities in it
    c1 = np.sort(np.bincount(e1, minlength=500))[::-1]
    c2 = np.sort(np.bincount(e2, minlength=500))[::-1]
    np.testing.assert_array_equal(c1, c2)
    assert not np.array_equal(e1, e2)
    ranks = world.zipf_ranks(n, 1.12, 500, np.random.default_rng(5))
    p = 1.0 / np.arange(1, 501) ** 1.12
    p /= p.sum()
    assert abs((ranks < 10).mean() - p[:10].sum()) < 0.02
    scattered = world.zipf_ranks(n, 1.04, 500, np.random.default_rng(5))
    assert (scattered < 10).mean() < (ranks < 10).mean()


def test_row_chunk():
    assert world.row_chunk(1_000_000) == 50_000      # a multiple of 8
    assert world.row_chunk(2000) == 2000
    assert world.row_chunk(2500) == 2500              # no multiple of 8
    assert world.row_chunk(7) == 7
