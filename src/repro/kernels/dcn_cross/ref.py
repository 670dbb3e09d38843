"""Plain jnp statement of what ``dcn_cross_q8`` computes, on the unpadded
arrays.

With ``mirror=False`` every product is float32 at ``Precision.HIGHEST``:
the exact math. With ``mirror=True`` the cross and deep products take the
kernel's three bfloat16 passes (``dcn_cross.dot3``), so kernel and
reference then differ only in the order of float32 sums.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dcn_cross.dcn_cross import dot3, split

HIGHEST = jax.lax.Precision.HIGHEST


def _exact(a, w):
    return jnp.dot(a, w, precision=HIGHEST,
                   preferred_element_type=jnp.float32)


def cross_logits(x0_ctx, codes, scale, zero, cand_val, cross, mlp, *,
                 mirror: bool = False):
    """Arguments as :func:`repro.kernels.dcn_cross.ops.dcn_cross_q8`, less
    ``p_ctx``, which this computes. Returns logits (R, N)."""
    def dot(a, w):
        return dot3(a, *split(w)) if mirror else _exact(a, w)

    r, n, fa, k = codes.shape
    dc = x0_ctx.shape[1]
    xa = ((codes.astype(jnp.float32) * scale[..., None] + zero[..., None])
          * cand_val[..., None]).reshape(r, n, fa * k)
    x0 = jnp.concatenate(
        [jnp.broadcast_to(x0_ctx[:, None, :], (r, n, dc)), xa], axis=-1)
    w0 = cross["w0"]
    p = _exact(x0_ctx, w0[:dc]) + cross["b0"]
    x = x0 * (p[:, None, :] + dot(xa, w0[dc:])) + x0
    for l in range(1, len(cross) // 2):
        x = x0 * (dot(x, cross[f"w{l}"]) + cross[f"b{l}"]) + x
    n_mlp = len(mlp) // 2
    for i in range(n_mlp - 1):
        x = jnp.maximum(dot(x, mlp[f"w{i}"]) + mlp[f"b{i}"], 0.0)
    last = _exact(x, mlp[f"w{n_mlp - 1}"]) + mlp[f"b{n_mlp - 1}"]
    return last[..., 0]
