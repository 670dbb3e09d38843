"""Pallas row-gather-and-dequantize kernel for the int8 serving tables (§6).

The serving hot path is one access pattern: gather a few thousand embedding
rows per microbatch out of a table of up to millions, by hashed feature
index. XLA's *generic* gather handles it, but on CPU it falls off its
fast path once the table outgrows the thread-partitioning heuristics
(measured on a 2-core box: a (R=8, N=64, Fc=8) candidate gather from a
``(V, 24, 8)`` table costs ~0.2-0.9 ms up to ``V=2^18`` and jumps to
~3-4 ms at ``V=2^19`` — for f32 *and* int8 alike), and the int8 codes
additionally miss the vectorized row-copy XLA uses for wide dtypes.

This kernel is the accelerator-side answer: the gather indices ride in as a
scalar-prefetch operand, so each grid step's *block index map* selects the
table row to DMA — the gather never exists as an XLA HLO at all, and the
dequantize (``code * scale + zero``, per-row grids from
``quantization.quantize_rows``) is fused into the same VMEM-resident step, so
the f32 row only ever materializes in-register. One gathered row per grid
step keeps the DMA descriptors trivially shaped.

Mosaic accepts a block only when its last two dims divide by the tile or
equal the array's, and a block of one int8 row would force the whole table
into a layout padded per row (4x at production widths). So the table is
viewed as ``(V/8, 8, rowlen)`` and each step fetches the 8-row block that
holds its row; the per-row grids are viewed as ``(V/1024, 8, 128)`` — the
layout a 1-D f32 array already has, so that view is free — and each step
fetches one (8, 128) block of each. The kernel picks its row and its two
scalars out of those blocks with exact one-hot sums.

On the CPU/interpret backend the per-row grid degenerates into a scan of
dynamic slices — correct (the parity tests run it at small sizes) but far
slower than a host-side packed gather, which is why
:func:`repro.kernels.row_gather.ops.use_host_gather` routes large-table CPU
serving through numpy instead (see ``ops.py`` for the selection contract).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode

_SQ = pl.Squeezed()
_ROWS = 8       # table rows per fetched codes block
_GRIDS = 1024   # per-row grid scalars per fetched (8, 128) f32 block


def _gather_dequant_kernel(idx_ref, codes_ref, scale_ref, zero_ref, out_ref):
    r = idx_ref[pl.program_id(0)]
    # the index maps fetched the 8-row block holding row r and the
    # (8, 128) grid blocks holding its scale and zero; select them with a
    # one-hot sum (exact: one term, the rest zeros)
    rows = codes_ref[...].astype(jnp.float32)                  # (8, rowlen)
    sub = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    row = jnp.sum(jnp.where(sub == r % _ROWS, rows, 0.0), axis=0,
                  keepdims=True)                               # (1, rowlen)
    pos = (jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0) * 128
           + jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1))
    hit = pos == r % _GRIDS

    def pick(ref):
        x = jnp.where(hit, ref[...], 0.0)
        return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0,
                       keepdims=True)                          # (1, 1)

    out_ref[...] = row * pick(scale_ref) + pick(zero_ref)


def _pad_rows(x, multiple):
    pad = (-x.shape[0]) % multiple
    if not pad:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))


def gather_dequant_rows_q8(codes: jnp.ndarray, scale: jnp.ndarray,
                           zero: jnp.ndarray, idx: jnp.ndarray, *,
                           interpret=None) -> jnp.ndarray:
    """Gather rows ``idx`` from an int8 row-quantized table and dequantize.

    codes: (V, ...) int8 per-row codes; scale/zero: (V,) f32 per-row grids;
    idx: any-shape int32 row indices -> f32 ``idx.shape + codes.shape[1:]``.

    The indices are a scalar-prefetch operand: the block index maps read
    ``idx[i]`` to place each grid step's table blocks, so the row gather is
    expressed as per-step DMA placement instead of a generic gather HLO.
    """
    row_shape = codes.shape[1:]
    rowlen = math.prod(row_shape)
    flat_codes = _pad_rows(codes.reshape(codes.shape[0], rowlen), _ROWS)
    scale = _pad_rows(scale, _GRIDS).reshape(-1, 8, 128)
    zero = _pad_rows(zero, _GRIDS).reshape(-1, 8, 128)
    flat_idx = idx.reshape(-1).astype(jnp.int32)
    m = flat_idx.shape[0]
    grid_block = lambda i, idx_ref: (idx_ref[i] // _GRIDS, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((_ROWS, rowlen),
                         lambda i, idx_ref: (idx_ref[i] // _ROWS, 0)),
            pl.BlockSpec((_SQ, 8, 128), grid_block),
            pl.BlockSpec((_SQ, 8, 128), grid_block),
        ],
        out_specs=pl.BlockSpec((_SQ, 1, rowlen), lambda i, idx_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_dequant_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, 1, rowlen), jnp.float32),
        interpret=interpret_mode(interpret),
    )(flat_idx, flat_codes, scale, zero)
    return out.reshape(idx.shape + row_shape)
