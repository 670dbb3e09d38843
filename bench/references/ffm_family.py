"""Plain reference of the FFM-family scoring heads, in jax.numpy.

No kernels, no cache, no dedup, no batching tricks: each prediction is its
full feature row (the request's context fields followed by the candidate's),
and its logit is

    lr    = b + sum_f w[idx_f] * v_f
    pairs = [ <E[idx_i, j], E[idx_j, i]> * v_i * v_j  for i < j ]   (DiagMask)
    ffm:      lr + sum(pairs)
    deepffm:  lr + sum(pairs) + MLP(MergeNorm([lr, pairs]))

with ``MergeNorm(z) = (z - mean(z)) / sqrt(var(z) + 1e-6) * scale + bias``
and a ReLU MLP (arXiv:2407.10115 §2.1; the additive shortcut is how the
paper's engine composes its blocks). ``E`` and ``w`` are the dequantized
int8 tables the seed defines (``table_rows`` of the FFM family,
``bench/families/ffm_family.py``), so the reference reads nothing the
program made.

``dtype=float32`` runs at the highest matmul precision: the yardstick.
``dtype=bfloat16`` computes every product and sum in bfloat16: the control,
the next precision below the one the configurations state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import spec


def _pairs(n_fields: int):
    i, j = np.triu_indices(n_fields, k=1)
    return i.astype(np.int32), j.astype(np.int32)


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _logits(head: str, n_fields: int, k: int, dtype, emb_u, lr_u, inv, val,
            hp):
    """emb_u (U, F, k) / lr_u (U,): the unique rows; inv (B, F) indexes them."""
    prec = jax.lax.Precision.HIGHEST
    emb_u = emb_u.astype(dtype)
    e = emb_u[inv]                                   # (B, F, F, k)
    v = val.astype(dtype)
    lr = jnp.sum(lr_u.astype(dtype)[inv] * v, axis=-1) + hp["lr_b"].astype(
        dtype)
    pi, pj = _pairs(n_fields)
    left = e[:, pi, pj]                              # E[idx_i, j]
    right = e[:, pj, pi]                             # E[idx_j, i]
    dots = jnp.einsum("bpk,bpk->bp", left, right, precision=prec,
                      preferred_element_type=dtype)
    pairs = dots * v[:, pi] * v[:, pj]
    base = lr + jnp.sum(pairs, axis=-1)
    if head == "ffm":
        return base
    z = jnp.concatenate([lr[:, None], pairs], axis=-1)
    mu = jnp.mean(z, axis=-1, keepdims=True)
    var = jnp.mean((z - mu) ** 2, axis=-1, keepdims=True)
    x = ((z - mu) * jax.lax.rsqrt(var + jnp.asarray(1e-6, dtype))
         * hp["merge_scale"].astype(dtype) + hp["merge_bias"].astype(dtype))
    mlp = hp["mlp"]
    n = len(mlp) // 2
    for i in range(n):
        x = jnp.einsum("bi,ij->bj", x, mlp[f"w{i}"].astype(dtype),
                       precision=prec, preferred_element_type=dtype) \
            + mlp[f"b{i}"].astype(dtype)
        if i < n - 1:
            x = jnp.maximum(x, 0)
    return base + x[:, 0]


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
        emb_u, lr_u = family.table_rows(cfg, seed, jnp.asarray(rows), jnp)
        got = _logits(cfg["head"], cfg["n_fields"], cfg["k"], dtype, emb_u,
                      lr_u, jnp.asarray(inv.reshape(bi.shape), jnp.int32),
                      jnp.asarray(bv, jnp.float32), hp)
        out.append(np.asarray(got, np.float32)[:m])
    return np.concatenate(out) if out else np.zeros(0, np.float32)
