"""Pallas TPU kernels: dynamic-range 16-bit quantize / dequantize (paper §6).

The paper's budget is "tens of seconds at most ... for the full weight
space"; on TPU the two passes are trivially memory-bound elementwise sweeps,
so the kernel's job is purely to stream HBM->VMEM->HBM at full bandwidth with
lane-aligned (multiple-of-128) 1D tiles.

Pass 1 (min/max) is a blocked reduction kernel; pass 2 maps each weight to
``clip(round((w - min) / bucket), 0, 65535)`` as uint16 (stored as int32 in
interpret mode validation, bit-identical values).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import interpret_mode

B_MAX = 2**16
LANE = 128


def _minmax_kernel(w_ref, min_ref, max_ref):
    i = pl.program_id(0)
    w = w_ref[...]

    @pl.when(i == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, jnp.inf)
        max_ref[...] = jnp.full_like(max_ref, -jnp.inf)

    min_ref[...] = jnp.minimum(min_ref[...], jnp.min(w))
    max_ref[...] = jnp.maximum(max_ref[...], jnp.max(w))


def _quant_kernel(w_ref, scalars_ref, q_ref):
    w_min = scalars_ref[0]
    bucket = scalars_ref[1]
    q = jnp.round((w_ref[...] - w_min) / bucket)
    q_ref[...] = jnp.clip(q, 0, B_MAX - 1).astype(jnp.int32)


def _dequant_kernel(q_ref, scalars_ref, w_ref):
    w_min = scalars_ref[0]
    bucket = scalars_ref[1]
    w_ref[...] = w_min + q_ref[...].astype(jnp.float32) * bucket


def _pad_lane(x: jnp.ndarray, value: float) -> jnp.ndarray:
    pad = (-x.shape[0]) % LANE
    if pad:
        x = jnp.pad(x, (0, pad), constant_values=value)
    return x


def minmax(w: jnp.ndarray, *, block: int = 64 * LANE, interpret=None):
    """Blocked min/max reduction over a flat f32 array."""
    n = w.shape[0]
    wp = _pad_lane(w, w[0])
    block = min(block, wp.shape[0])
    # ensure block divides
    while wp.shape[0] % block:
        wp = jnp.pad(wp, (0, LANE), constant_values=wp[0])
    grid = (wp.shape[0] // block,)
    mn, mx = pl.pallas_call(
        _minmax_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1,), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(wp)
    return mn[0], mx[0]


def quantize_pallas(w: jnp.ndarray, w_min: jnp.ndarray, bucket: jnp.ndarray,
                    *, block: int = 64 * LANE, interpret=None) -> jnp.ndarray:
    """Flat f32 -> int32 codes in [0, 65535] (uint16 payload semantics)."""
    n = w.shape[0]
    wp = _pad_lane(w, 0.0)
    block = min(block, wp.shape[0])
    while wp.shape[0] % block:
        wp = jnp.pad(wp, (0, LANE))
    scalars = jnp.stack([w_min, bucket]).astype(jnp.float32)
    q = pl.pallas_call(
        _quant_kernel,
        grid=(wp.shape[0] // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((2,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((wp.shape[0],), jnp.int32),
        interpret=interpret_mode(interpret),
    )(wp, scalars)
    return q[:n]


def dequantize_pallas(q: jnp.ndarray, w_min: jnp.ndarray, bucket: jnp.ndarray,
                      *, block: int = 64 * LANE, interpret=None) -> jnp.ndarray:
    n = q.shape[0]
    qp = _pad_lane(q, 0)
    block = min(block, qp.shape[0])
    while qp.shape[0] % block:
        qp = jnp.pad(qp, (0, LANE))
    scalars = jnp.stack([w_min, bucket]).astype(jnp.float32)
    w = pl.pallas_call(
        _dequant_kernel,
        grid=(qp.shape[0] // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((2,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((qp.shape[0],), jnp.float32),
        interpret=interpret_mode(interpret),
    )(qp, scalars)
    return w[:n]
