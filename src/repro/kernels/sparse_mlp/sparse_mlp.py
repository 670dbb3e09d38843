"""Pallas TPU kernel: block-skip ReLU weight-gradient (paper §4.3, TPU form).

The paper skips whole update branches whose global gradient is provably zero
under ReLU. On TPU the profitable granularity is the MXU tile: computing
  dW[i, j] = sum_b x[b, i] * g[b, j]        (g already activation-masked)
as a (I_tile x J_tile) output grid with a sequential reduction over batch
blocks, where ``@pl.when`` skips the MXU contraction for any (batch-block,
j-tile) whose masked-gradient block is entirely zero. Dead output columns
(ReLU units never active in the batch) cost zero MXU work, reproducing the
paper's "identify zero global gradient scenarios upfront, prior to updating
any weights".

Grid order (i, j, k): k (batch blocks) is innermost/minor so each (i, j)
output tile stays resident in VMEM across its reduction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import interpret_mode


def _sparse_dw_kernel(x_ref, g_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = g_ref[...]  # (Bk, Jt) masked gradient block

    @pl.when(jnp.any(g != 0.0))
    def _accum():
        x = x_ref[...]  # (Bk, It)
        out_ref[...] += jnp.dot(
            x.T, g, preferred_element_type=out_ref.dtype
        )


def sparse_weight_grad_pallas(x: jnp.ndarray, g_masked: jnp.ndarray, *,
                              block_i: int = 128, block_j: int = 128,
                              block_b: int = 128, interpret=None
                              ) -> jnp.ndarray:
    """dW = x^T @ g_masked with zero-block skipping. x: (B, I); g: (B, J)."""
    b, i = x.shape
    j = g_masked.shape[1]
    bi, bj, bb = min(block_i, i), min(block_j, j), min(block_b, b)

    def padto(a, m, axis):
        pad = (-a.shape[axis]) % m
        if pad:
            width = [(0, 0)] * a.ndim
            width[axis] = (0, pad)
            a = jnp.pad(a, width)
        return a

    xp = padto(padto(x, bb, 0), bi, 1)
    gp = padto(padto(g_masked, bb, 0), bj, 1)
    grid = (xp.shape[1] // bi, gp.shape[1] // bj, xp.shape[0] // bb)
    out = pl.pallas_call(
        _sparse_dw_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bi), lambda i_, j_, k_: (k_, i_)),
            pl.BlockSpec((bb, bj), lambda i_, j_, k_: (k_, j_)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i_, j_, k_: (i_, j_)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[1], gp.shape[1]), jnp.float32),
        interpret=interpret_mode(interpret),
    )(xp.astype(jnp.float32), gp.astype(jnp.float32))
    return out[:i, :j]
