"""End-to-end system tests: dry-run lowering (subprocess), graph sampler,
LM training convergence, the has-rag dry-run step."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.slow
def test_dryrun_cell_subprocess():
    """One real production-mesh cell: 512 virtual devices, lower+compile."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "dlrm-rm2",
         "--shape", "serve_p99", "--multi-pod"],
        capture_output=True, text=True, env=env, timeout=420)
    assert "OK" in out.stdout, out.stdout + out.stderr


def test_mesh_axes():
    # no XLA flag in-process: just validate shapes/axis names via subprocess
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=512")
    code = ("from repro.launch.mesh import make_production_mesh;"
            "m = make_production_mesh(multi_pod=True);"
            "assert m.shape == {'pod': 2, 'data': 16, 'model': 16}, m.shape;"
            "m2 = make_production_mesh();"
            "assert m2.shape == {'data': 16, 'model': 16};"
            "print('MESH_OK')")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=120)
    assert "MESH_OK" in out.stdout, out.stdout + out.stderr


def test_neighbor_sampler_block_shapes():
    from repro.data.graph import NeighborSampler, random_graph
    g = random_graph(500, 4000, 8, 3, seed=0)
    samp = NeighborSampler(g["edge_src"].astype(np.int64),
                           g["edge_dst"].astype(np.int64), 500, seed=0)
    seeds = np.arange(32)
    nodes, src, dst, mask = samp.sample_block(seeds, (5, 3), e_max=1024)
    assert src.shape == (1024,) and mask.dtype == bool
    assert mask.sum() > 0
    # all local ids within the node set
    assert src[mask].max() < len(nodes) and dst[mask].max() < len(nodes)


def test_lm_training_loss_decreases():
    """(b) deliverable sanity at test scale: loss goes down on Markov data."""
    from repro.launch.train import train_lm
    from repro.models.transformer import TransformerConfig
    cfg = TransformerConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                            n_kv_heads=2, d_ff=128, vocab_size=64, d_head=16,
                            remat=False)
    losses = train_lm(cfg, steps=30, batch=8, seq=32, ckpt_dir=None,
                      log_every=1000)
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2


def test_has_dryrun_step_semantics():
    """has-rag smoke: accepted queries return drafts, rejected the full ids."""
    from repro.configs import get_arch
    spec = get_arch("has-rag")
    cfg, fn, args = spec.make_smoke()
    ids, accept, best = jax.jit(fn)(*args)
    corpus = np.asarray(args[0])
    queries = np.asarray(args[-1])
    k = ids.shape[1]
    exact = np.argsort(-(queries @ corpus.T), axis=1)[:, :k]
    for i in range(queries.shape[0]):
        if not bool(accept[i]):
            assert set(np.asarray(ids[i]).tolist()) == set(exact[i].tolist())
