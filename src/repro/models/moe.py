"""Mixture-of-Experts FFN with two execution paths.

* ``dense``            — one-hot combine over all experts (exact; used for
                         smoke tests, equivalence tests, and decode shapes
                         where the token count is below the device count).
* ``expert_parallel``  — GShard-style explicit dispatch under ``shard_map``:
                         tokens sharded over every mesh axis, experts sharded
                         over ``model``; two ``all_to_all`` collectives move
                         token copies to/from expert owners with a fixed
                         per-(device, expert) capacity. This is the path the
                         dry-run lowers for train/prefill shapes, so the
                         roofline's collective term reflects real MoE a2a
                         traffic.

Router: softmax -> top-k -> renormalize, with a Switch-style load-balance
auxiliary loss  aux = E * sum_e f_e * P_e.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.pspec import ParamSpec
from repro.models import layers


def moe_specs(cfg) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    f = cfg.d_ff_expert or cfg.d_ff
    E = cfg.n_experts
    dt = jnp.dtype(cfg.param_dtype)
    sp = {
        "router": ParamSpec((d, E), ("embed", "experts"), "scaled", jnp.float32),
        "wi": ParamSpec((E, d, f), ("experts", "embed", "expert_mlp"), "scaled", dt, fan_in=d),
        "wo": ParamSpec((E, f, d), ("experts", "expert_mlp", "embed"), "scaled", dt, fan_in=f),
    }
    if cfg.act == "swiglu":
        sp["wg"] = ParamSpec((E, d, f), ("experts", "embed", "expert_mlp"), "scaled", dt, fan_in=d)
    if cfg.n_shared_experts:
        sp["shared"] = layers.ffn_specs(cfg, d_ff=cfg.n_shared_experts * f)
    return sp


def _expert_ffn(cfg, p, h):
    """h: (E_local, C, d) -> (E_local, C, d) through per-expert FFN."""
    up = jnp.einsum("ecd,edf->ecf", h, p["wi"])
    if cfg.act == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", h, p["wg"])
        up = up * jax.nn.silu(g.astype(jnp.float32)).astype(up.dtype)
    elif cfg.act == "relu":
        up = jnp.maximum(up, 0)
    else:
        up = jax.nn.gelu(up.astype(jnp.float32)).astype(up.dtype)
    return jnp.einsum("ecf,efd->ecd", up, p["wo"])


def _router(cfg, router_w, x):
    """x: (T, d) -> weights (T, k), ids (T, k), probs (T, E)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, cfg.top_k)
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    return w, ids, probs


def _aux_loss(cfg, probs, ids):
    """Switch load-balance loss on local tokens (caller averages over devices)."""
    E = cfg.n_experts
    counts = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(ids.size, 1)  # fraction of copies per expert
    pmean = jnp.mean(probs, axis=0)
    return E * jnp.sum(f * pmean)


# ---------------------------------------------------------------------------
# Dense (exact) path
# ---------------------------------------------------------------------------

def moe_dense(cfg, p, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (..., d). Computes every expert on every token, one-hot combines."""
    shp = x.shape
    xt = x.reshape(-1, shp[-1])  # (T, d)
    w, ids, probs = _router(cfg, p["router"], xt)
    h = jnp.broadcast_to(xt[None], (cfg.n_experts,) + xt.shape)  # (E, T, d)
    y_all = _expert_ffn(cfg, p, h)  # (E, T, d)
    onehot = jax.nn.one_hot(ids, cfg.n_experts, dtype=jnp.float32)  # (T, k, E)
    combine = jnp.einsum("tk,tke->te", w, onehot)  # (T, E)
    y = jnp.einsum("te,etd->td", combine.astype(y_all.dtype), y_all)
    return y.reshape(shp), _aux_loss(cfg, probs, ids)


# ---------------------------------------------------------------------------
# Expert-parallel path (shard_map + all_to_all)
# ---------------------------------------------------------------------------

def _positions_within_expert(flat_e: jnp.ndarray, n_experts: int) -> jnp.ndarray:
    """Rank of each copy among same-expert copies (sort-based, O(N log N))."""
    n = flat_e.shape[0]
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    idx = jnp.arange(n)
    seg_start = jnp.concatenate([jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]])
    start_idx = jax.lax.associative_scan(jnp.maximum, jnp.where(seg_start, idx, 0))
    rank_sorted = idx - start_idx
    return jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))


def _dispatch_compute_combine(cfg, p, x_l, model_axis: str, n_model: int, capacity: int):
    """Per-device body under shard_map. x_l: (T_l, d) local tokens."""
    T_l, d = x_l.shape
    E, k = cfg.n_experts, cfg.top_k
    E_l, M, C = E // n_model, n_model, capacity

    w, ids, probs = _router(cfg, p["router"], x_l)
    # load-balance factors as LOCAL means; caller pmeans each factor before
    # combining so the aux loss equals the global (dense-path) value exactly
    counts = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f_local = counts / jnp.maximum(ids.size, 1)
    p_local = jnp.mean(probs, axis=0)
    flat_e = ids.reshape(-1)  # (N,)
    n = flat_e.shape[0]
    pos = _positions_within_expert(flat_e, E)
    keep = pos < C
    dest = flat_e // E_l
    le = flat_e % E_l
    tok = jnp.arange(n) // k
    safe_pos = jnp.where(keep, pos, C - 1)

    send = jnp.zeros((M, E_l, C, d), x_l.dtype)
    send = send.at[dest, le, safe_pos].add(
        jnp.where(keep[:, None], x_l[tok], 0).astype(x_l.dtype)
    )
    recv = jax.lax.all_to_all(send, model_axis, split_axis=0, concat_axis=0)
    h = recv.transpose(1, 0, 2, 3).reshape(E_l, M * C, d)
    y = _expert_ffn(cfg, p, h)
    y = y.reshape(E_l, M, C, d).transpose(1, 0, 2, 3)
    back = jax.lax.all_to_all(y, model_axis, split_axis=0, concat_axis=0)

    y_copies = back[dest, le, safe_pos] * keep[:, None].astype(back.dtype)
    y_tok = (y_copies.reshape(T_l, k, d) * w[..., None].astype(back.dtype)).sum(axis=1)
    return y_tok.astype(x_l.dtype), f_local, p_local


def moe_expert_parallel(cfg, p, x, rt) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) with B*S divisible by the total device count."""
    mesh = rt.mesh
    token_axes = rt.all_axes  # e.g. ("pod", "data", "model")
    n_dev = mesh.devices.size
    n_model = mesh.shape[rt.model_axis]
    B, S, d = x.shape
    T = B * S
    assert T % n_dev == 0, (T, n_dev)
    T_l = T // n_dev
    capacity = max(int(T_l * cfg.top_k / cfg.n_experts * cfg.capacity_factor), 1)
    capacity = min(capacity + (-capacity) % 4, T_l * cfg.top_k)

    # experts shard over the model axis; the router is replicated (every
    # device routes its own tokens over all E experts).
    expert_axes = {
        name: (P(None, None) if name == "router"
               else P(*[rt.model_axis if a == "experts" else None for a in spec.axes]))
        for name, spec in moe_specs(cfg).items()
        if name not in ("shared",)
    }
    in_specs = (
        P(token_axes, None),
        {name: expert_axes[name] for name in expert_axes},
    )
    out_specs = (P(token_axes, None), P())

    def body(xt, pl):
        y, f_local, p_local = _dispatch_compute_combine(
            cfg, pl, xt, rt.model_axis, n_model, capacity)
        f = jax.lax.pmean(f_local, token_axes)
        pm = jax.lax.pmean(p_local, token_axes)
        aux = cfg.n_experts * jnp.sum(f * pm)
        return y, aux

    p_expert = {name: p[name] for name in expert_axes}
    # pre-constrain the flat token layout so GSPMD reshards once, cheaply,
    # instead of falling into replicate-then-repartition at the shard_map
    # boundary (observed "involuntary full rematerialization" otherwise)
    xt = jax.lax.with_sharding_constraint(
        x.reshape(T, d),
        jax.sharding.NamedSharding(mesh, P(token_axes, None)),
    )
    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs
    )(xt, p_expert)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def moe_forward(cfg, p, x, rt=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    impl = cfg.moe_impl
    if impl == "auto":
        tokens = int(x.shape[0] * x.shape[1]) if x.ndim == 3 else int(x.shape[0])
        ok = (
            rt is not None
            and rt.mesh is not None
            and tokens % rt.mesh.devices.size == 0
            and cfg.n_experts % rt.mesh.shape[rt.model_axis] == 0
        )
        impl = "expert_parallel" if ok else "dense"
    if impl == "expert_parallel":
        y, aux = moe_expert_parallel(cfg, p, x, rt)
    else:
        y, aux = moe_dense(cfg, p, x)
    if cfg.n_shared_experts:
        y = y + layers.apply_ffn(cfg, p["shared"], x)
    return y, aux
