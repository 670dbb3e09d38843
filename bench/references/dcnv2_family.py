"""Plain reference of the DCN-v2 head, in jax.numpy.

No kernel, no cache, no dedup, no batching tricks: each prediction is its
full feature row (the request's context fields followed by the candidate's),
and its logit is (arXiv:2008.13535, stacked)

    x0      = concat_f( E[idx_f] * v_f )
    x_{l+1} = x0 * (x_l W_l + b_l) + x_l
    logit   = MLP_relu(x_L)

``E`` is the dequantized int8 table the seed defines and the weights are the
seed's (``table_rows`` and ``head_params`` of the DCN-v2 family,
``bench/families/dcnv2_family.py``), so the reference reads nothing the
program made, and only the rows the sample uses.

``dtype=float32`` runs every product at ``Precision.HIGHEST``: the
yardstick. ``dtype=bfloat16`` computes every product, sum and elementwise
step in bfloat16: the control, the next precision below the one the
configuration states.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import spec


@partial(jax.jit, static_argnums=(0,))
def _logits(dtype, emb_u, inv, val, hp):
    """emb_u (U, k): the unique rows; inv (B, F) indexes them."""
    prec = jax.lax.Precision.HIGHEST
    b = inv.shape[0]
    x0 = (emb_u.astype(dtype)[inv] * val.astype(dtype)[..., None]).reshape(
        b, -1)

    def dense(x, w, bias):
        return jnp.einsum("bi,ij->bj", x, w.astype(dtype), precision=prec,
                          preferred_element_type=dtype) + bias.astype(dtype)

    x = x0
    cross = hp["cross"]
    for l in range(len(cross) // 2):
        x = x0 * dense(x, cross[f"w{l}"], cross[f"b{l}"]) + x
    mlp = hp["mlp"]
    n = len(mlp) // 2
    for i in range(n):
        x = dense(x, mlp[f"w{i}"], mlp[f"b{i}"])
        if i < n - 1:
            x = jnp.maximum(x, 0)
    return x[:, 0]


def logits(cfg: dict, seed: int, idx: np.ndarray, val: np.ndarray, *,
           dtype=jnp.float32, block: int = 2048) -> np.ndarray:
    """Reference logits of full feature rows ``idx``/``val`` (B, F), computed
    ``block`` rows per call (rows padded to a whole block, so one program
    serves every sample)."""
    family = spec.family(cfg)
    hp = family.head_params(cfg, seed)
    out = []
    for s in range(0, len(idx), block):
        bi, bv = idx[s:s + block], val[s:s + block]
        m = len(bi)
        if m < block:
            bi = np.concatenate([bi, np.repeat(bi[:1], block - m, 0)])
            bv = np.concatenate([bv, np.repeat(bv[:1], block - m, 0)])
        rows, inv = np.unique(bi, return_inverse=True)
        # a fixed number of unique-row slots keeps the program's shape fixed
        pad = block * cfg["n_fields"] - rows.size
        rows = np.concatenate([rows, np.zeros(pad, rows.dtype)])
        emb_u = family.table_rows(cfg, seed, jnp.asarray(rows), jnp)
        got = _logits(dtype, emb_u,
                      jnp.asarray(inv.reshape(bi.shape), jnp.int32),
                      jnp.asarray(bv, jnp.float32), hp)
        out.append(np.asarray(got, np.float32)[:m])
    return np.concatenate(out) if out else np.zeros(0, np.float32)
