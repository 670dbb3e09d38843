"""Row-gather strategy selection for the quantized serving tables (§6).

One funnel decides *how* a table row gather executes, because no single
strategy survives every regime:

* ``jnp.take`` — XLA's generic gather. Fine below :data:`CLIFF_ROWS`; above
  it the XLA-CPU implementation falls off its fast path (the ROADMAP'd
  "int8 gather cliff": measured 4x slower than f32 at 2^18 on the original
  box, and on the current 2-core box both dtypes jump ~10x at 2^19 while a
  host gather stays flat). Still the in-trace reference everywhere a
  better strategy cannot apply.
* **Pallas gather-and-dequant** (:mod:`.row_gather`) — on TPU the indices
  become a scalar-prefetch operand and each grid step DMAs the block that
  holds its row, so the generic-gather HLO never exists. The TPU's only
  in-trace strategy, at every table size: the choice rests on the platform,
  never on a timing (GPU keeps the generic take, whose gather does not
  share the XLA-CPU cliff).
* **Host packed gather** (:func:`gather_codes_np` / :func:`gather_dequant_np`)
  — numpy ``take`` over the widest word view the row byte-length allows
  (int8 rows of 8k bytes move as u64 lanes). Immune to the XLA cliff and
  ~15x faster than the in-jit take at 2^19; only available when the table
  and indices are concrete host arrays, i.e. *before* entering a jitted
  call. The serving engine pre-gathers candidate codes this way above the
  cliff (``InferenceEngine`` ``host_gather``) and feeds the already-gathered
  block to the fused q8 kernel.

``gather_dequant_rows`` is the in-trace selector ``ffm.gather_rows`` calls;
``use_host_gather`` is the out-of-trace policy the engine consults.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.row_gather.row_gather import gather_dequant_rows_q8

# Above this many table rows XLA-CPU's generic gather leaves its fast path
# (ROADMAP "Quantized-path follow-ons"; see module docstring for numbers).
# This constant is the *fallback* threshold: the deployment box's real
# crossover is measured once per process by :func:`calibrate_cliff_rows`
# (export ``REPRO_CLIFF_CALIBRATE=0`` to disable probing and pin the
# constant), because the cliff location moved by a factor of 4 between the
# two CPU generations the sweep has already run on.
CLIFF_ROWS = 1 << 17

# calibration probe bounds: never move the cliff below 2^16 (tiny tables
# stay on the zero-copy in-trace path regardless of micro-timing noise) or
# above 2^20 (past that every measured box is deep into the slow path)
_PROBE_SIZES = (1 << 16, 1 << 17, 1 << 18, 1 << 19)
_PROBE_MAX = 1 << 20
_calibrated: Optional[int] = None
# N ShardRouter shard threads all hit their first gather at once; without
# serialization each would run the micro-probe (N x probe cost on the request
# path) and racing writers could leave shards disagreeing on strategy.
_calibrate_lock = threading.Lock()


def calibrate_cliff_rows(sizes: Sequence[int] = _PROBE_SIZES,
                         row_bytes: int = 192, n_idx: int = 4096,
                         repeats: int = 3) -> int:
    """Measure this box's actual gather cliff: the smallest probed table size
    at which the host packed gather (:func:`gather_codes_np`) beats XLA's
    ``jnp.take`` on an int8 row table of serving-realistic width
    (``row_bytes`` defaults to a 24-field x 8-wide int8 row). A few ms per
    size after the one-time ``take`` compiles; the serving engine caches the
    result per process via :func:`cliff_rows`. Returns ``_PROBE_MAX`` when
    the in-trace gather wins everywhere probed (host pre-gather then only
    activates on tables past every measured point)."""
    idx = np.random.default_rng(0).integers(0, min(sizes), size=n_idx)
    idx_dev = jnp.asarray(idx)
    for n_rows in sorted(sizes):
        table = np.zeros((n_rows, row_bytes), np.int8)
        table_dev = jnp.asarray(table)
        # eager jnp.take (what the in-trace gather lowers to on CPU): first
        # call compiles, timed calls measure steady state
        jax.block_until_ready(jnp.take(table_dev, idx_dev, axis=0))
        t_jit = min(_timed(lambda: jax.block_until_ready(
            jnp.take(table_dev, idx_dev, axis=0))) for _ in range(repeats))
        t_host = min(_timed(lambda: gather_codes_np(table, idx))
                     for _ in range(repeats))
        if t_host < t_jit:
            return int(n_rows)
    return _PROBE_MAX


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def cliff_rows() -> int:
    """The effective XLA-CPU gather-cliff threshold: the per-process
    calibrated crossover, or the :data:`CLIFF_ROWS` constant when probing is
    disabled (``REPRO_CLIFF_CALIBRATE=0``). A probe that fails raises (and
    is retried by the next call) rather than hiding behind the constant.
    Only CPU-backend decisions consult it."""
    if os.environ.get("REPRO_CLIFF_CALIBRATE", "1").lower() in ("0", "false"):
        return CLIFF_ROWS
    global _calibrated
    if _calibrated is None:  # double-checked: reads stay lock-free once set
        with _calibrate_lock:
            if _calibrated is None:
                _calibrated = calibrate_cliff_rows()
    return _calibrated


def use_host_gather(n_rows: int) -> bool:
    """True when the serving engine should pre-gather candidate rows on host
    (numpy) instead of gathering inside the jitted forward: CPU backend (the
    Pallas kernel's scalar-prefetch DMA path needs real accelerator hardware;
    in interpret mode it degenerates to a scan of dynamic slices) and a table
    past the gather cliff (calibrated per process — :func:`cliff_rows`).
    The platform is checked first, so no other platform runs the probe."""
    return jax.default_backend() == "cpu" and n_rows >= cliff_rows()


def _packed_view(flat: np.ndarray):
    """Widest-word view of a (V, rowbytes) byte-contiguous table: int8 rows
    move as u64/u32/u16 lanes when the row byte-length allows (numpy's take
    copies per element of the *viewed* dtype, so wider is strictly fewer
    moves)."""
    rowbytes = flat.shape[1] * flat.dtype.itemsize
    for width, dt in ((8, np.uint64), (4, np.uint32), (2, np.uint16)):
        if rowbytes % width == 0:
            return flat.view(dt)
    return flat


def gather_codes_np(table: np.ndarray, idx: np.ndarray,
                    out: Optional[np.ndarray] = None) -> np.ndarray:
    """Host packed row gather: ``table[idx]`` via ``np.take`` on the widest
    aligned word view. ``table``: (V, ...) any dtype; returns
    ``idx.shape + table.shape[1:]`` in the table dtype.

    ``out`` (optional) is a caller-provided destination of exactly that
    shape/dtype: the gather then writes straight into it (``np.take(...,
    out=...)`` on the packed view) instead of allocating — the parallel
    scoring pipeline double-buffers per-chunk gather output this way, so
    a burst reuses two steady buffers per worker instead of allocating a
    fresh block per chunk."""
    table = np.ascontiguousarray(table)
    idx = np.asarray(idx)
    flat = table.reshape(table.shape[0], -1)
    packed = _packed_view(flat)
    if out is None:
        g = np.take(packed, idx.reshape(-1), axis=0)
        return g.view(table.dtype).reshape(idx.shape + table.shape[1:])
    want = idx.shape + table.shape[1:]
    if out.shape != want or out.dtype != table.dtype:
        raise ValueError(
            f"out must be {want} {table.dtype}, got {out.shape} {out.dtype}")
    if idx.size == 0:
        return out
    dst = np.ascontiguousarray(out)  # no-op for a well-formed buffer
    np.take(packed, idx.reshape(-1), axis=0,
            out=_packed_view(dst.reshape(idx.size, -1)))
    if dst is not out:  # caller passed a non-contiguous view: copy back
        out[...] = dst
    return out


def gather_codes_chunked(table: np.ndarray, idx: np.ndarray,
                         out: np.ndarray, row_chunk: int = 8192) -> np.ndarray:
    """Chunked variant of :func:`gather_codes_np` into a caller buffer:
    gathers ``row_chunk`` index rows at a time so the transient packed view
    never exceeds the chunk (keeps the working set cache-resident when one
    worker's block is large). ``idx`` must be at least 1-D; ``out`` has
    shape ``idx.shape + table.shape[1:]`` in the table dtype."""
    idx = np.asarray(idx)
    flat_idx = idx.reshape(-1)
    flat_out = out.reshape((flat_idx.size,) + table.shape[1:])
    for lo in range(0, flat_idx.size, max(1, row_chunk)):
        hi = min(lo + row_chunk, flat_idx.size)
        gather_codes_np(table, flat_idx[lo:hi], out=flat_out[lo:hi])
    return out


def gather_dequant_np(qtable, idx: np.ndarray) -> np.ndarray:
    """Fused host gather + per-row dequantize of an int8 row-quantized table
    dict (``quantization.quantize_rows`` format) -> f32 rows."""
    idx = np.asarray(idx)
    codes = np.asarray(qtable["codes"])
    extra = (1,) * (codes.ndim - 1)
    c = gather_codes_np(codes, idx).astype(np.float32)
    s = np.asarray(qtable["scale"])[idx].reshape(idx.shape + extra)
    z = np.asarray(qtable["zero"])[idx].reshape(idx.shape + extra)
    return c * s + z


def _is_concrete(x) -> bool:
    return not isinstance(x, jax.core.Tracer)


def gather_dequant_rows(qtable, idx):
    """Strategy-selected gather+dequant from an int8 row-quantized table.

    TPU: the Pallas kernel, always. CPU: the host packed gather for eager
    host arrays above the cliff (e.g. the ``score_uncached`` oracle path),
    ``jnp.take`` otherwise. Other platforms: ``jnp.take`` (scalar-prefetch
    grid specs are TPU-only; a GPU gather does not share the XLA-CPU cliff).
    """
    codes = qtable["codes"]
    platform = jax.default_backend()
    if platform == "tpu":
        return gather_dequant_rows_q8(codes, qtable["scale"], qtable["zero"],
                                      idx)
    if (platform == "cpu" and _is_concrete(codes) and _is_concrete(idx)
            and codes.shape[0] >= cliff_rows()):
        return jnp.asarray(gather_dequant_np(qtable, np.asarray(idx)))
    extra = (1,) * (codes.ndim - 1)
    c = jnp.take(codes, idx, axis=0).astype(jnp.float32)
    s = jnp.take(qtable["scale"], idx).reshape(idx.shape + extra)
    z = jnp.take(qtable["zero"], idx).reshape(idx.shape + extra)
    return c * s + z


