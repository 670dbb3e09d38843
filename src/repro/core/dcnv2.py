"""DCN-v2, stacked (Wang et al., "DCN V2", WWW 2021, arXiv:2008.13535): the
source paper's §2.2 baseline, and the serving engine's ``dcnv2`` head.

    x0      = concat_f( E[idx_f] * v_f )              F fields x k = D
    x_{l+1} = x0 * (x_l W_l + b_l) + x_l              l = 0 .. n_cross - 1
    logit   = MLP_relu(x_L)                           mlp_hidden, then 1

Row vectors throughout: ``x_l W_l`` is the paper's ``W_l x_l`` with ``W_l``
(D, D) stored input-major, as every dense layer of this repo stores it. The
embedding table is one hashed space shared by all fields, as the source
paper's baseline has it (it gave each value a hash of its own; here the
FFM heads' hashed feature indices are reused). Departures from the paper's
equations: none.

Params: ``emb`` (V, k) (an int8 row-quantized dict once the serving engine
quantizes it: ``quantization.quantize_params_rows``), ``cross`` {``w{l}``
(D, D), ``b{l}`` (D,)} and ``mlp`` {``w{i}``, ``b{i}``}.

:func:`forward` is the plain reference: jax.numpy, float32 at the highest
matmul precision, no cache, kernel or batching. The serving path splits it
where the prefix cache and the kernel need: a context state ``x0`` of the
context fields (:func:`extend_state_np`; a prefix of it is a slice), the
context's part of layer 0 once per request row, and the candidates'
remainder in ``kernels/dcn_cross`` (:func:`candidates_forward`, on the
weights :func:`serving_params` builds once per published params).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import pspec
from repro.common.config import FFMConfig
from repro.common.pspec import ParamSpec
from repro.core import ffm

HIGHEST = jax.lax.Precision.HIGHEST


def width(cfg: FFMConfig) -> int:
    """``D``: the concatenated embedding width."""
    return cfg.n_fields * cfg.k


def param_specs(cfg: FFMConfig) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.dtype)
    d = width(cfg)
    cross = {}
    for l in range(cfg.n_cross):
        cross[f"w{l}"] = ParamSpec((d, d), ("null", "null"), "scaled", dt)
        cross[f"b{l}"] = ParamSpec((d,), ("null",), "zeros", dt)
    dims = (d,) + tuple(cfg.mlp_hidden) + (1,)
    mlp = {}
    for i in range(len(dims) - 1):
        mlp[f"w{i}"] = ParamSpec((dims[i], dims[i + 1]), ("null", "null"),
                                 "scaled", dt)
        mlp[f"b{i}"] = ParamSpec((dims[i + 1],), ("null",), "zeros", dt)
    return {"emb": ParamSpec((cfg.hash_space, cfg.k), ("vocab", "null"),
                             "embed", dt),
            "cross": cross, "mlp": mlp}


def init_params(cfg: FFMConfig, key):
    return pspec.materialize(param_specs(cfg), key)


def rows(emb, idx) -> jnp.ndarray:
    """Embedding rows ``(..., k)`` of ``idx`` in float32: a plain gather, and
    for an int8 row-quantized table the codes times the row's scale plus its
    zero."""
    if isinstance(emb, dict):
        c = jnp.take(emb["codes"], idx, axis=0).astype(jnp.float32)
        return (c * jnp.take(emb["scale"], idx)[..., None]
                + jnp.take(emb["zero"], idx)[..., None])
    return jnp.take(emb, idx, axis=0).astype(jnp.float32)


def cross_and_deep(params, x0) -> jnp.ndarray:
    """``MLP_relu(x_L)`` of ``x0`` (..., D): the cross stack, then the deep
    network. Logits (...,)."""
    x = x0
    cross = params["cross"]
    for l in range(len(cross) // 2):
        x = x0 * (jnp.matmul(x, cross[f"w{l}"]) + cross[f"b{l}"]) + x
    mlp = params["mlp"]
    n = len(mlp) // 2
    for i in range(n):
        x = jnp.matmul(x, mlp[f"w{i}"]) + mlp[f"b{i}"]
        if i < n - 1:
            x = jnp.maximum(x, 0.0)
    return x[..., 0]


def forward(cfg: FFMConfig, params, idx, val) -> jnp.ndarray:
    """The reference: full feature rows ``idx``/``val`` (B, F) -> logits (B,)."""
    with jax.default_matmul_precision("highest"):
        x0 = (rows(params["emb"], idx)
              * jnp.asarray(val, jnp.float32)[..., None])
        return cross_and_deep(params, x0.reshape(idx.shape[0], width(cfg)))


def loss_fn(cfg: FFMConfig, params, batch):
    return ffm.bce_loss(forward(cfg, params, batch["idx"], batch["val"]),
                        batch["label"])


# -- serving: the context state and the candidates' forward -----------------

def empty_state_np(cfg: FFMConfig) -> Dict[str, np.ndarray]:
    return {"x0": np.zeros((0, cfg.k), np.float32)}


def extend_state_np(cfg: FFMConfig, emb, state: Dict[str, np.ndarray],
                    tail_idx: np.ndarray, tail_val: np.ndarray
                    ) -> Dict[str, np.ndarray]:
    """Host numpy: the context state ``x0`` (depth, k) of a prefix extended
    by the tail fields' value-scaled rows."""
    tail = (ffm.gather_rows_np(emb, np.asarray(tail_idx)).astype(np.float32)
            * np.asarray(tail_val, np.float32)[:, None])
    return {"x0": np.concatenate([state["x0"], tail])}


def slice_state(state: Dict[str, np.ndarray], depth: int
                ) -> Dict[str, np.ndarray]:
    """The state of the first ``depth`` context fields: a slice."""
    return {"x0": state["x0"][:depth]}


def serving_params(cfg: FFMConfig, backend: str, params):
    """What :func:`candidates_forward` takes, built once per published
    params object: where the kernel runs (the pallas backend over an int8
    table), the table, layer 0's context rows ``w0_ctx`` and bias ``b0``,
    and the kernel's padded split weights (``dcn_cross.ops.kernel_weights``)
    as host numpy; otherwise ``params`` itself."""
    if backend != "pallas" or not isinstance(params["emb"], dict):
        return params
    from repro.kernels.dcn_cross import ops as dcn_ops

    cross, dc = params["cross"], cfg.context_fields * cfg.k
    kernel = dcn_ops.kernel_weights(
        cross, params["mlp"], n_ctx=cfg.context_fields,
        n_cand=cfg.n_fields - cfg.context_fields, k=cfg.k)
    return {"emb": params["emb"], "w0_ctx": np.asarray(cross["w0"][:dc]),
            "b0": np.asarray(cross["b0"]),
            "kernel": jax.tree_util.tree_map(np.asarray, kernel)}


def candidates_forward(cfg: FFMConfig, params, x0_ctx, cand_idx, cand_val
                       ) -> jnp.ndarray:
    """Logits (R, N) of R request rows' candidates: ``params`` from
    :func:`serving_params`, ``x0_ctx`` (R, Fc, k) the rows' context states,
    ``cand_idx``/``cand_val`` (R, N, F - Fc).

    Where the kernel runs, the context's part of layer 0, ``x0_ctx @
    W0[:Dc] + b0``, is computed once per row at the highest precision, the
    candidates' codes are gathered here, and ``dcn_cross_q8`` does the rest
    (every cross and deep product float32 to about 2^-16 in three bfloat16
    MXU passes, float32 accumulation and elementwise work); otherwise the
    rows are dequantized and the layers run in jax.numpy at the platform's
    default matmul precision."""
    r, n, fa = cand_idx.shape
    dc = cfg.context_fields * cfg.k
    x0c = x0_ctx.reshape(r, dc)
    emb = params["emb"]
    if "kernel" in params:
        from repro.kernels.dcn_cross import ops as dcn_ops

        p = jnp.dot(x0c, params["w0_ctx"], precision=HIGHEST) + params["b0"]
        return dcn_ops.dcn_cross_q8(
            x0c, p, jnp.take(emb["codes"], cand_idx, axis=0),
            jnp.take(emb["scale"], cand_idx), jnp.take(emb["zero"], cand_idx),
            cand_val, params["kernel"])
    xa = (rows(emb, cand_idx) * cand_val[..., None]).reshape(r, n, fa * cfg.k)
    x0 = jnp.concatenate([jnp.broadcast_to(x0c[:, None], (r, n, dc)), xa], -1)
    return cross_and_deep(params, x0)
