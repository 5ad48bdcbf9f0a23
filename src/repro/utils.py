"""Shared utilities: logical-axis sharding rules, tree helpers, dtype policy.

The framework uses *logical axis names* on every parameter / activation dim
(MaxText-style).  A ``ShardingRules`` table maps logical names to physical mesh
axes; :func:`logical_to_spec` resolves a tuple of logical names into a
``PartitionSpec``.  This keeps model code mesh-agnostic: the same model lowers
on a single CPU device (all rules -> None), the 16x16 single-pod mesh, and the
2x16x16 multi-pod mesh.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from pathlib import Path
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Persistent compilation cache
# ---------------------------------------------------------------------------

_REPO_ROOT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Keep compiled programs across processes; returns the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the directory is part of what a later run
    must find, so it never depends on a temp dir, a pid or the time.
    Entry points call this before their first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# Logical axis rules
# ---------------------------------------------------------------------------

# Default production rules for the (pod, data, model) mesh.  ``fsdp`` is the
# weight-sharding axis (ZeRO-3 style); ``tensor`` is the tensor-parallel axis.
PRODUCTION_RULES: dict[str, tuple[str, ...] | str | None] = {
    "batch": ("pod", "data"),          # data-parallel batch
    "seq": "model",                    # residual-stream sequence parallelism
    "kv_seq": "model",                 # decode-time KV cache sharding
    "kv_seq_long": ("data", "model"),  # 500k-context decode KV sharding
    "d_model": None,                   # activations stay replicated on d_model
    "heads": "model",                  # attention-head tensor parallel
    "kv_heads": None,                  # GQA KV heads are few -> replicate
    "d_ff": "model",                   # FFN tensor parallel
    "vocab": "model",                  # vocab-parallel embedding / logits
    "experts": "model",                # MoE expert parallel
    "fsdp": "data",                    # ZeRO-3 weight shard axis
    "corpus": ("data", "model"),       # retrieval corpus shards
    "emb_vocab": "model",              # recsys embedding-table vocab shards
    "nodes": ("data", "model"),        # GNN node partition
    "edges": ("data", "model"),        # GNN edge partition
}

# Single-device rules (tests / smoke): everything replicated.
LOCAL_RULES: dict[str, tuple[str, ...] | str | None] = {k: None for k in PRODUCTION_RULES}


def logical_to_spec(logical: Sequence[str | None],
                    rules: Mapping[str, Any]) -> P:
    """Resolve a tuple of logical axis names into a PartitionSpec."""
    out = []
    for name in logical:
        if name is None:
            out.append(None)
        else:
            out.append(rules.get(name))
    return P(*out)


def tree_specs(logical_tree: Any, rules: Mapping[str, Any]) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda lg: logical_to_spec(lg, rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, str) or e is None for e in x),
    )


def tree_shardings(logical_tree: Any, rules: Mapping[str, Any], mesh: Mesh) -> Any:
    specs = tree_specs(logical_tree, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def constrain(x: jax.Array, logical: Sequence[str | None],
              rules: Mapping[str, Any] | None) -> jax.Array:
    """with_sharding_constraint by logical names; no-op when rules is None."""
    if rules is None:
        return x
    return jax.lax.with_sharding_constraint(x, logical_to_spec(logical, rules))


# ---------------------------------------------------------------------------
# Tree / param helpers
# ---------------------------------------------------------------------------

def tree_size(tree: Any) -> int:
    """Total number of parameters in a pytree (works on ShapeDtypeStructs)."""
    return sum(int(jnp.prod(jnp.asarray(x.shape))) if x.shape else 1
               for x in jax.tree.leaves(tree))


def tree_bytes(tree: Any) -> int:
    return sum(
        int(jnp.prod(jnp.asarray(x.shape))) * jnp.dtype(x.dtype).itemsize
        if x.shape else jnp.dtype(x.dtype).itemsize
        for x in jax.tree.leaves(tree))


def cast_tree(tree: Any, dtype) -> Any:
    return jax.tree.map(lambda x: x.astype(dtype), tree)


@dataclasses.dataclass(frozen=True)
class DTypePolicy:
    """Mixed-precision policy: params / compute / output dtypes."""
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    output_dtype: Any = jnp.float32

    def cast_compute(self, x):
        return jax.tree.map(lambda a: a.astype(self.compute_dtype), x)


FP32 = DTypePolicy(jnp.float32, jnp.float32, jnp.float32)
BF16 = DTypePolicy(jnp.bfloat16, jnp.bfloat16, jnp.float32)
MIXED = DTypePolicy(jnp.float32, jnp.bfloat16, jnp.float32)


def pretty_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024:
            return f"{n:.2f} {unit}"
        n /= 1024
    return f"{n:.2f} PiB"


def fold_rng(key: jax.Array, *names: str) -> jax.Array:
    """Deterministically derive a sub-key from string names."""
    for name in names:
        key = jax.random.fold_in(key, abs(hash(name)) % (2**31))
    return key
