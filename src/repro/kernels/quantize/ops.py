"""Jitted wrapper: full two-pass paper quantization on top of the Pallas kernels.

Matches ``repro.core.quantization`` bit-for-bit (same conservative bound
rounding, same header semantics) but runs both passes as Pallas sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantization import QuantMeta, _ceil_dec, _floor_dec, B_MAX
from repro.kernels.quantize.quantize import dequantize_pallas, minmax, quantize_pallas


def quantize(w: jnp.ndarray, alpha: int = 2, beta: int = 2, *, interpret=None):
    flat = w.reshape(-1).astype(jnp.float32)
    mn, mx = minmax(flat, interpret=interpret)
    w_min = _floor_dec(float(mn), beta)
    w_max = _ceil_dec(float(mx), alpha)
    if w_max <= w_min:
        w_max = w_min + 10.0 ** (-alpha)
    bucket = (w_max - w_min) / (B_MAX - 1)
    q = quantize_pallas(flat, jnp.float32(w_min), jnp.float32(bucket), interpret=interpret)
    return q, QuantMeta(w_min, bucket, int(flat.size))


def dequantize(q: jnp.ndarray, meta: QuantMeta, *, interpret=None) -> jnp.ndarray:
    return dequantize_pallas(
        q, jnp.float32(meta.w_min), jnp.float32(meta.bucket_size), interpret=interpret
    )
