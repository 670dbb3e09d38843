"""DeepFFM — the paper's model (§2.1, Figure 2) plus its CTR baselines.

  Dffm(x) = FFNN( MergeNormLayer( LR(x), DiagMask(FFM(x)) ) )

Model zoo (paper §2.2 benchmark):
  * ``linear``   — VW-linear analogue (hashed logistic regression)
  * ``mlp``      — VW-mlp analogue (LR + MLP over pooled field embeddings)
  * ``ffm``      — FW-FFM (LR + summed DiagMask'd interactions)
  * ``deepffm``  — FW-DeepFFM (the paper's architecture)
DCNv2 lives in ``repro.core.dcnv2``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.common import pspec
from repro.common.config import FFMConfig
from repro.common.pspec import ParamSpec
from repro.core import ffm, sparse_updates


def _mlp_specs(cfg: FFMConfig, d_in: int) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.dtype)
    sp = {}
    dims = (d_in,) + tuple(cfg.mlp_hidden) + (1,)
    for i in range(len(dims) - 1):
        # final layer zero-init: the MLP is a residual branch on top of the
        # additive LR/FFM terms, so it must start silent and learn its
        # contribution (otherwise an untrained random projection drowns the
        # wide signal in early online learning).
        init = "zeros" if i == len(dims) - 2 else "scaled"
        sp[f"w{i}"] = ParamSpec((dims[i], dims[i + 1]), ("null", "null"), init, dt)
        sp[f"b{i}"] = ParamSpec((dims[i + 1],), ("null",), "zeros", dt)
    return sp


def mlp_apply(cfg: FFMConfig, p, x, *, return_preacts: bool = False,
              return_masks: bool = False, sparse_backward: bool = True):
    """ReLU MLP head.

    Hidden layers route through :func:`sparse_updates.relu_linear` by default,
    so the §4.3 zero-global-gradient backward (the activation mask applied
    *before* the weight-gradient matmuls) is on for every DeepFFM training
    step — algebraically identical to autodiff, equivalence-tested.
    ``sparse_backward=False`` keeps the plain autodiff path (the oracle).

    ``return_masks`` additionally returns the per-hidden-layer (B, H)
    activation masks that feed ``sparse_updates.skip_stats``;
    ``return_preacts`` returns raw pre-activations (legacy §4.3 analysis).
    """
    n = len(cfg.mlp_hidden) + 1
    preacts, masks = [], []
    for i in range(n - 1):
        if sparse_backward and not return_preacts:
            x = sparse_updates.relu_linear(x, p[f"w{i}"], p[f"b{i}"], False)
            masks.append(x > 0)
        else:
            z = jnp.einsum("bi,ij->bj", x, p[f"w{i}"]) + p[f"b{i}"]
            preacts.append(z)
            masks.append(z > 0)
            x = jnp.maximum(z, 0)  # ReLU — the zero-gradient source for §4.3
    x = jnp.einsum("bi,ij->bj", x, p[f"w{n - 1}"]) + p[f"b{n - 1}"]
    out = x[:, 0]
    if return_preacts:
        return out, preacts
    if return_masks:
        return out, masks
    return out


def param_specs(cfg: FFMConfig, model: str = "deepffm") -> Dict[str, Any]:
    lr = ffm.lr_specs(cfg)
    if model == "linear":
        return {"lr": lr}
    if model == "mlp":
        return {
            "lr": lr,
            "emb": ffm.ffm_specs(cfg)["emb"],
            "mlp": _mlp_specs(cfg, cfg.n_fields * cfg.k),
        }
    if model == "ffm":
        return {"lr": lr, "ffm": ffm.ffm_specs(cfg)}
    if model == "deepffm":
        d_merge = cfg.n_pairs + 1
        dt = jnp.dtype(cfg.dtype)
        return {
            "lr": lr,
            "ffm": ffm.ffm_specs(cfg),
            "merge_scale": ParamSpec((d_merge,), ("null",), "ones", dt),
            "merge_bias": ParamSpec((d_merge,), ("null",), "zeros", dt),
            "mlp": _mlp_specs(cfg, d_merge),
        }
    raise ValueError(f"no parameters for model {model!r} here: linear, mlp, "
                     f"ffm and deepffm (DCN-v2's are repro.core.dcnv2's)")


def init_params(cfg: FFMConfig, key, model: str = "deepffm"):
    return pspec.materialize(param_specs(cfg, model), key)


def merge_norm(cfg: FFMConfig, p, lr_out, ffm_vec):
    """MergeNormLayer: concat + normalization (learnable scale/bias)."""
    z = jnp.concatenate([lr_out[:, None], ffm_vec], axis=-1)
    zf = z.astype(jnp.float32)
    mu = jnp.mean(zf, axis=-1, keepdims=True)
    var = jnp.var(zf, axis=-1, keepdims=True)
    zn = (zf - mu) * jax.lax.rsqrt(var + 1e-6)
    return (zn * p["merge_scale"] + p["merge_bias"]).astype(z.dtype)


def head_from_parts(cfg: FFMConfig, params, lr_out, ffm_vec,
                    model: str = "deepffm", *, with_masks: bool = False,
                    sparse_backward: bool = True):
    """Shared ffm/deepffm tail: LR logits (B,) + pair vector (B, n_pairs) -> logits.

    The single place that composes the wide and deep parts, whether the pair
    vector came from the full forward, the context-cache decomposition, or the
    Pallas candidate kernel.

    FFNN over MergeNorm(LR, FFM) plus the additive LR/FFM shortcut — FW
    composes blocks additively (regressor.rs sums block outputs), so the MLP
    learns a residual on top of the classic wide terms. This is what gives
    DeepFFM linear-level early learning with later gains (paper: "DeepFFMs
    dominate after enough data is seen").

    ``with_masks`` returns ``(logits, masks)`` where ``masks`` are the MLP's
    per-hidden-layer activation masks (empty for models without an MLP) —
    the §4.3 zero-global-gradient structure the trainer reports per round.
    """
    if model == "ffm":
        return (lr_out + jnp.sum(ffm_vec, axis=-1), []) if with_masks \
            else lr_out + jnp.sum(ffm_vec, axis=-1)
    if model == "deepffm":
        z = merge_norm(cfg, params, lr_out, ffm_vec)
        base = lr_out + jnp.sum(ffm_vec, axis=-1)
        if with_masks:
            mlp_out, masks = mlp_apply(cfg, params["mlp"], z, return_masks=True,
                                       sparse_backward=sparse_backward)
            return base + mlp_out, masks
        return base + mlp_apply(cfg, params["mlp"], z,
                                sparse_backward=sparse_backward)
    raise ValueError(model)


def split_request(cfg: FFMConfig, idx, val):
    """Split full feature rows (B, F) into the serving decomposition:
    ``(ctx_idx (Fc,), ctx_val (Fc,), cand_idx (B, F-Fc), cand_val (B, F-Fc))``.

    Inverse of the concatenation the serving oracle performs: all rows must
    share their first ``context_fields`` columns (one request = one context).
    The field-prefix structure this relies on is the same one the prefix
    cache exploits (``ffm.extend_context_prefix``).
    """
    fc = cfg.context_fields
    idx, val = jnp.asarray(idx), jnp.asarray(val)
    return idx[0, :fc], val[0, :fc], idx[:, fc:], val[:, fc:]


def forward(cfg: FFMConfig, params, idx, val, model: str = "deepffm",
            interactions_fn=None, *, with_masks: bool = False,
            sparse_backward: bool = True):
    """Returns logits (B,). ``interactions_fn`` lets the serving layer inject
    the Pallas kernel or the context-cached partial computation.
    ``with_masks`` returns ``(logits, masks)`` (see :func:`head_from_parts`).
    """
    lr_out = ffm.lr_forward(cfg, params["lr"], idx, val)
    if model == "linear":
        return (lr_out, []) if with_masks else lr_out
    if model == "mlp":
        e = ffm.gather_rows(params["emb"], idx)  # (B,F,F,k)
        pooled = (jnp.mean(e, axis=2) * val[..., None]).reshape(idx.shape[0], -1)
        if with_masks:
            mlp_out, masks = mlp_apply(cfg, params["mlp"], pooled,
                                       return_masks=True,
                                       sparse_backward=sparse_backward)
            return lr_out + mlp_out, masks
        return lr_out + mlp_apply(cfg, params["mlp"], pooled,
                                  sparse_backward=sparse_backward)
    inter = interactions_fn or ffm.interactions
    ffm_vec = inter(cfg, params["ffm"]["emb"], idx, val)
    return head_from_parts(cfg, params, lr_out, ffm_vec, model,
                           with_masks=with_masks,
                           sparse_backward=sparse_backward)


def loss_fn(cfg: FFMConfig, params, batch, model: str = "deepffm",
            sparse_backward: bool = True):
    logits = forward(cfg, params, batch["idx"], batch["val"], model,
                     sparse_backward=sparse_backward)
    return ffm.bce_loss(logits, batch["label"])


def loss_and_aux(cfg: FFMConfig, params, batch, model: str = "deepffm",
                 sparse_backward: bool = True):
    """Loss plus the training-pipeline aux: pre-update logits (progressive
    validation scores come from the same forward the gradient uses) and the
    §4.3 activation masks. Use with ``jax.value_and_grad(..., has_aux=True)``.
    """
    logits, masks = forward(cfg, params, batch["idx"], batch["val"], model,
                            with_masks=True, sparse_backward=sparse_backward)
    return ffm.bce_loss(logits, batch["label"]), {"logits": logits,
                                                  "masks": masks}


def predict_proba(cfg: FFMConfig, params, idx, val, model: str = "deepffm"):
    return jax.nn.sigmoid(forward(cfg, params, idx, val, model))
