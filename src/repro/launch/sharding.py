"""Logical-axis sharding rules (MaxText-style) + ZeRO-1 optimizer sharding.

Every parameter carries logical axis names (from its ParamSpec); a rule table
maps logical axes to mesh axes with automatic divisibility fallback to
replication. Activations/batches shard their batch dim over (pod, data).

Param strategy:
  * ``model`` axis carries tensor parallelism: vocab, heads, mlp, experts...
  * ``fsdp=True`` configs additionally shard the ``embed`` axis over
    (pod, data) — weight-gathered on use by GSPMD (FSDP).
  * optimizer state is ZeRO-1: each state leaf additionally shards its
    largest still-unsharded dim over the data axes.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def logical_rules(cfg, mesh: Mesh) -> Dict[str, Optional[Tuple[str, ...]]]:
    data_axes = tuple(n for n in mesh.axis_names if n != "model")
    if getattr(cfg, "pure_dp", False):
        # small-model strategy: no tensor parallelism; every param replicated,
        # batch over the data axes. Kills the resharding collective-permute
        # storm that mixed divisible/indivisible dims otherwise produce.
        return {k: None for k in (
            "vocab", "embed", "mlp", "heads", "kv_heads", "head_dim", "experts",
            "expert_mlp", "kv_lora", "q_lora", "ssm_inner", "ssm_state",
            "ssm_heads", "conv", "layers", "stack", "null")}
    rules: Dict[str, Optional[Tuple[str, ...]]] = {
        "vocab": ("model",),
        "embed": data_axes if cfg.fsdp else None,
        "mlp": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "head_dim": None,
        "experts": ("model",),
        "expert_mlp": None,  # experts already own the model axis
        "kv_lora": None,
        "q_lora": None,
        "ssm_inner": ("model",),
        "ssm_state": None,
        "ssm_heads": None,
        "conv": None,
        "layers": None,
        "stack": None,
        "null": None,
    }
    return rules


def abstract_mesh(axis_sizes: Tuple[int, ...], axis_names: Tuple[str, ...]):
    """An ``AbstractMesh`` of the given axis sizes and names."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def _axis_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def spec_for(shape: Tuple[int, ...], logical: Tuple[str, ...], rules, mesh: Mesh) -> P:
    """Map one param's logical axes to a PartitionSpec with divisibility checks."""
    used: set = set()
    parts = []
    for dim, name in zip(shape, logical):
        mapped = rules.get(name)
        if mapped and not (set(mapped) & used) and dim % _axis_size(mesh, mapped) == 0:
            parts.append(mapped if len(mapped) > 1 else mapped[0])
            used.update(mapped)
        else:
            parts.append(None)
    return P(*parts)


def param_shardings(cfg, specs_axes, abstract, mesh: Mesh):
    """specs_axes: logical-axes tree; abstract: ShapeDtypeStruct tree."""
    rules = logical_rules(cfg, mesh)

    def one(axes, sds):
        return NamedSharding(mesh, spec_for(sds.shape, axes, rules, mesh))

    # logical-axes leaves are tuples of strings — stop tree_map from recursing
    return jax.tree_util.tree_map(
        one, specs_axes, abstract,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(s, str) for s in x),
    )


def zero1_shardings(param_sharding_tree, abstract_tree, mesh: Mesh):
    """Optimizer-state sharding: param sharding + largest free dim over data axes."""
    data_axes = tuple(n for n in mesh.axis_names if n != "model")
    dsize = _axis_size(mesh, data_axes)

    def one(psh: NamedSharding, sds):
        spec = list(psh.spec) + [None] * (len(sds.shape) - len(psh.spec))
        used = set()
        for s in spec:
            if s is None:
                continue
            used.update(s if isinstance(s, tuple) else (s,))
        if not (set(data_axes) & used):
            # shard the largest unsharded divisible dim over the data axes
            order = np.argsort([-d for d in sds.shape])
            for i in order:
                if spec[i] is None and sds.shape[i] % dsize == 0:
                    spec[i] = data_axes if len(data_axes) > 1 else data_axes[0]
                    break
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(one, param_sharding_tree, abstract_tree)


# ---------------------------------------------------------------------------
# Activation / batch / decode-state shardings
# ---------------------------------------------------------------------------

def batch_spec(shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Shard dim 0 (global batch) over (pod, data) when divisible."""
    data_axes = tuple(n for n in mesh.axis_names if n != "model")
    if shape and shape[0] % _axis_size(mesh, data_axes) == 0:
        first = data_axes if len(data_axes) > 1 else data_axes[0]
        return P(first, *([None] * (len(shape) - 1)))
    return P(*([None] * len(shape)))


def batch_shardings(batch_abstract, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda sds: NamedSharding(mesh, batch_spec(sds.shape, mesh)), batch_abstract
    )


def decode_state_shardings(cfg, state_abstract, mesh: Mesh):
    """Path-keyed rules for the decode caches.

    KV rings (L, B, S, K, D): batch over data axes when divisible, else the
    sequence dim; kv-heads over model when divisible. MLA latents (L, B, S, R):
    batch-else-sequence over data. SSM states (.., B, H, N, P): batch over
    data, heads over model. Conv states: batch over data.
    """
    data_axes = tuple(n for n in mesh.axis_names if n != "model")
    dsize = _axis_size(mesh, data_axes)
    msize = mesh.shape["model"]
    d_ax = data_axes if len(data_axes) > 1 else data_axes[0]

    def leaf(path, sds):
        name = str(getattr(path[-1], "key", path[-1]))
        nd = len(sds.shape)
        spec = [None] * nd
        if name == "pos":
            return NamedSharding(mesh, P())
        if name in ("k", "v", "cross_k", "cross_v"):
            # (..., B, S, K, D): batch over data; kv-heads over model when
            # divisible, else the *sequence* over model (flash-decode style —
            # softmax/readout become partial reductions instead of a full
            # cache all-gather every step).
            b, s, kh = nd - 4, nd - 3, nd - 2
            if sds.shape[b] % dsize == 0:
                spec[b] = d_ax
            elif sds.shape[s] % dsize == 0:
                spec[s] = d_ax
            if sds.shape[kh] % msize == 0:
                spec[kh] = "model"
            elif spec[s] is None and sds.shape[s] % msize == 0:
                spec[s] = "model"
        elif name in ("k_scale", "v_scale"):
            # (..., B, S, K): follow the int8 cache layout
            b, sq, kh = nd - 3, nd - 2, nd - 1
            if sds.shape[b] % dsize == 0:
                spec[b] = d_ax
            if sds.shape[kh] % msize == 0:
                spec[kh] = "model"
            elif sds.shape[sq] % msize == 0:
                spec[sq] = "model"
        elif name in ("ckv", "kr"):
            # MLA latent cache (..., B, S, R): batch over data, seq over model
            b, s = nd - 3, nd - 2
            if sds.shape[b] % dsize == 0:
                spec[b] = d_ax
            elif sds.shape[s] % dsize == 0:
                spec[s] = d_ax
            if spec[s] is None and sds.shape[s] % msize == 0:
                spec[s] = "model"
        elif name == "ssm":
            # (..., B, H, N, P)
            b, h = nd - 4, nd - 3
            if sds.shape[b] % dsize == 0:
                spec[b] = d_ax
            if sds.shape[h] % msize == 0:
                spec[h] = "model"
        elif name == "conv":
            # (..., B, K, C)
            b, c = nd - 3, nd - 1
            if sds.shape[b] % dsize == 0:
                spec[b] = d_ax
            if sds.shape[c] % msize == 0:
                spec[c] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(leaf, state_abstract)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
