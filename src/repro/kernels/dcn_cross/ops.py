"""Jitted wrapper of the DCN-v2 cross kernel: from the model's arrays to the
kernel's lane layout (``dcn_cross.py``) and back.

The kernel's padded, bfloat16-split weights depend on the published params
only: :func:`kernel_weights` builds them once per params object, and every
:func:`dcn_cross_q8` call takes them as they are.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.dcn_cross.dcn_cross import (BLOCK_N, LANES, MXU_DTYPE,
                                               dcn_cross_call, round_up, split)


def layout(n_ctx: int, n_cand: int, k: int) -> dict:
    """Lane sizes of ``n_ctx`` context and ``n_cand`` candidate fields of
    width ``k``: the padded width ``dp``, the first weight row ``k0`` of
    layer 0's candidate product, and the width ``kg`` of the factor block."""
    dc, d = n_ctx * k, (n_ctx + n_cand) * k
    return {"dc": dc, "d": d, "dp": round_up(d, LANES),
            "k0": dc // LANES * LANES, "kg": round_up(4 * n_cand, LANES)}


def tile(n: int, block_n: int = BLOCK_N) -> tuple:
    """``(tn, n_padded)``: candidates per grid step and the candidate count
    the kernel computes for a slate of ``n`` (a multiple of ``tn``)."""
    tn = min(block_n, round_up(n, 8))
    return tn, round_up(n, tn)


def spread_matrix(n_ctx: int, n_cand: int, k: int):
    """(kg, 2 dp) 0/1 bfloat16 matrix taking the factor block ``[s hi | s lo
    | z hi | z lo]`` (``n_cand`` columns each) to ``scale * value`` on a
    candidate field's lanes of the first half and ``zero * value`` on the
    second."""
    lay = layout(n_ctx, n_cand, k)
    dp = lay["dp"]
    rows = jnp.arange(lay["kg"])[:, None]
    cols = jnp.arange(2 * dp)[None, :]
    part, f = rows // n_cand, rows % n_cand
    lane = cols % dp - lay["dc"]
    hit = ((part < 4) & (cols // dp == part // 2)
           & (lane >= f * k) & (lane < (f + 1) * k))
    return hit.astype(MXU_DTYPE)


def _pad2(w, rows: int, cols: int):
    return jnp.pad(w, ((0, rows - w.shape[0]), (0, cols - w.shape[1])))


@partial(jax.jit, static_argnames=("n_ctx", "n_cand", "k"))
def kernel_weights(cross, mlp, *, n_ctx: int, n_cand: int, k: int) -> dict:
    """The kernel's weights from the model's float32 ``cross`` {``w{l}``
    (D, D), ``b{l}`` (D,)} and ``mlp`` {``w{i}``, ``b{i}``}, padded to its
    lanes: the spread matrix ``e``; layer 0's rows from ``k0`` as a bfloat16
    pair ``w0a``; layers 1 .. L - 1 as the pair ``wr`` and biases ``br``
    (None when L == 1); the deep ReLU layers' pairs and biases ``hidden``;
    the output layer's ``m_last``, ``c_last``. Layer 0's context rows and
    bias stay with the caller, which computes them once per request row."""
    lay = layout(n_ctx, n_cand, k)
    d, dp, k0 = lay["d"], lay["dp"], lay["k0"]
    n_cross = len(cross) // 2
    wr = br = None
    if n_cross > 1:
        wr = split(jnp.stack([_pad2(cross[f"w{l}"], dp, dp)
                              for l in range(1, n_cross)]))
        br = jnp.stack([jnp.pad(cross[f"b{l}"], (0, dp - d))
                        for l in range(1, n_cross)])
    n_mlp = len(mlp) // 2
    hidden, width = [], dp
    for i in range(n_mlp - 1):
        w = mlp[f"w{i}"]
        out = round_up(w.shape[1], LANES)
        hidden += [*split(_pad2(w, width, out)),
                   jnp.pad(mlp[f"b{i}"], (0, out - w.shape[1]))[None]]
        width = out
    last = mlp[f"w{n_mlp - 1}"][:, 0]
    return {"e": spread_matrix(n_ctx, n_cand, k),
            "w0a": split(_pad2(cross["w0"], dp, dp)[k0:]), "wr": wr, "br": br,
            "hidden": hidden,
            "m_last": jnp.pad(last, (0, width - last.shape[0]))[None],
            "c_last": mlp[f"b{n_mlp - 1}"].reshape(1, 1)}


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def dcn_cross_q8(x0_ctx, p_ctx, codes, scale, zero, cand_val, weights, *,
                 block_n: int = BLOCK_N, interpret=None):
    """DCN-v2 logits of request rows' candidates from their int8 rows.

    ``x0_ctx`` (R, Dc) f32: each row's context part of ``x0``; ``p_ctx`` (R,
    D) f32: ``x0_ctx @ W0[:Dc] + b0``; ``codes`` (R, N, Fa, k) int8 and
    ``scale``/``zero`` (R, N, Fa) f32: the candidates' gathered rows and
    grids; ``cand_val`` (R, N, Fa) f32; ``weights`` the model's
    :func:`kernel_weights`. Returns logits (R, N)."""
    r, n, fa, k = codes.shape
    lay = layout(x0_ctx.shape[1] // k, fa, k)
    dc, d, dp, k0, kg = (lay[key] for key in ("dc", "d", "dp", "k0", "kg"))
    tn, n_pad = tile(n, block_n)

    def cand(x):
        return jnp.pad(x, ((0, 0), (0, n_pad - n)) + ((0, 0),) * (x.ndim - 2))

    codes = jnp.pad(cand(codes).reshape(r, n_pad, fa * k),
                    ((0, 0), (0, 0), (dc, dp - d)))
    sv_hi, sv_lo = split(cand(scale * cand_val))
    zv_hi, zv_lo = split(cand(zero * cand_val))
    g = jnp.concatenate([sv_hi, sv_lo, zv_hi, zv_lo], axis=-1)
    g = jnp.pad(g, ((0, 0), (0, 0), (0, kg - 4 * fa)))

    def row(x):
        return jnp.pad(x, ((0, 0), (0, dp - x.shape[1])))[:, None, :]

    w = weights
    out = dcn_cross_call(row(x0_ctx), row(p_ctx), codes, g, w["e"], w["w0a"],
                         w["wr"], w["br"], w["hidden"], w["m_last"],
                         w["c_last"], k0=k0, block_n=tn, interpret=interpret)
    return out[:, :n, 0]
