"""Dynamic-range 16-bit weight quantization (paper §6).

The paper's algorithm, verbatim:

1. Per update window, scan all weights for min/max.
2. Round the bounds to ``beta``/``alpha`` decimals — full-precision bounds
   were observed to destabilize patch sizes ("quantization output tended to
   fluctuate more"), rounding stabilizes the bucket grid across updates so
   byte-diffs stay small.
3. ``bucket_size = (round(max, alpha) - round(min, beta)) / b_max``.
4. Each weight maps to ``round((w - min) / bucket_size)`` cast to uint16.
5. The weight file is enriched with a header carrying (min, bucket_size) —
   sufficient for reconstruction on the serving side.

Two implementations: a vectorized jnp one (jit-able, used in the transfer
channel for any architecture's pytree) and the Pallas kernel in
``repro.kernels.quantize`` for the TPU hot path.

This module also hosts the **serving-resident int8 row quantization**
(:func:`quantize_rows` and friends): per-row dynamic-range grids over the
embedding tables that the serving engine keeps resident instead of f32, so
the gather-bandwidth-dominated request path moves a quarter of the bytes and
delta-frame ingest requantizes only touched rows. See the section comment
below for the grid definition and error bounds.
"""
from __future__ import annotations

import struct
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HEADER_FMT = "<ffQQ"  # (w_min: f32, bucket_size: f32, n: u64, n_outliers: u64)
HEADER_SIZE = struct.calcsize(HEADER_FMT)
B_MAX = 2**16


@dataclass(frozen=True)
class QuantMeta:
    w_min: float
    bucket_size: float
    n: int
    n_outliers: int = 0


def _floor_dec(x: float, decimals: int) -> float:
    s = 10.0 ** decimals
    return float(np.floor(x * s) / s)


def _ceil_dec(x: float, decimals: int) -> float:
    s = 10.0 ** decimals
    return float(np.ceil(x * s) / s)


def compute_bounds(w: jnp.ndarray, alpha: int = 2, beta: int = 2) -> Tuple[float, float, float]:
    """First pass: (rounded) min/max and the bucket size.

    The paper rounds the bounds to alpha/beta decimals to stabilize the bucket
    grid across updates. We round *conservatively* (floor the min, ceil the
    max) so no weight is ever clipped — same stabilization effect, strictly
    bounded error (<= bucket/2).
    """
    w_min = _floor_dec(float(jnp.min(w)), beta)
    w_max = _ceil_dec(float(jnp.max(w)), alpha)
    if w_max <= w_min:  # degenerate (constant weights)
        w_max = w_min + 10.0 ** (-alpha)
    # divide by B_MAX-1 so w_max itself maps exactly to the top code
    bucket = (w_max - w_min) / (B_MAX - 1)
    return w_min, w_max, bucket


@jax.jit
def _quantize_core(w: jnp.ndarray, w_min: jnp.ndarray, bucket: jnp.ndarray) -> jnp.ndarray:
    q = jnp.round((w.astype(jnp.float32) - w_min) / bucket)
    return jnp.clip(q, 0, B_MAX - 1).astype(jnp.uint16)


@jax.jit
def _dequantize_core(q: jnp.ndarray, w_min: jnp.ndarray, bucket: jnp.ndarray) -> jnp.ndarray:
    return (w_min + q.astype(jnp.float32) * bucket).astype(jnp.float32)


def stable_bounds(w: jnp.ndarray, prev: Optional["QuantMeta"], alpha: int = 2,
                  beta: int = 2, shrink_limit: float = 4.0) -> Tuple[float, float]:
    """Grid hysteresis (beyond-paper improvement, documented in DESIGN.md).

    The paper rounds bounds to stabilize the bucket grid, but a weight drifting
    across a rounding boundary still shifts *every* code and blows up the next
    patch (we measured 77% changed bytes from one boundary crossing). Instead:
    reuse the previous update's grid verbatim unless (a) the new weights fall
    outside it, or (b) the occupied range shrank by more than ``shrink_limit``
    (keeping resolution adaptive, per the paper's "dynamically select viable
    weight ranges"). Expansion re-derives rounded bounds as usual.
    """
    w_min_raw = float(jnp.min(w))
    w_max_raw = float(jnp.max(w))
    if prev is not None:
        lo = prev.w_min
        hi = prev.w_min + prev.bucket_size * (B_MAX - 1)
        covers = lo <= w_min_raw and w_max_raw <= hi
        occupied = max(w_max_raw - w_min_raw, 1e-12)
        not_shrunk = (hi - lo) / occupied <= shrink_limit
        if covers and not_shrunk:
            return lo, hi
    w_min = _floor_dec(w_min_raw, beta)
    w_max = _ceil_dec(w_max_raw, alpha)
    if w_max <= w_min:
        w_max = w_min + 10.0 ** (-alpha)
    return w_min, w_max


OUTLIER_REGRID_FRAC = 1e-3


def quantize(w: jnp.ndarray, alpha: int = 2, beta: int = 2,
             prev: Optional[QuantMeta] = None):
    """Second pass: uint16 codes + header metadata. ``w`` is any float array.

    Pass ``prev`` (the previous update's meta) to enable grid hysteresis —
    required for consistently small byte patches across online updates. With
    hysteresis, weights that drift outside the previous grid are shipped
    exactly in an **outlier sidecar** (index, f32 value) instead of forcing a
    regrid that would churn every code; if the outlier fraction exceeds
    ``OUTLIER_REGRID_FRAC`` the grid is re-derived (the paper's dynamic range
    selection). Returns (codes, meta, outliers) where outliers is
    (idx u64 array, val f32 array) — empty without hysteresis.
    """
    flat = w.reshape(-1)
    empty = (np.zeros(0, np.uint64), np.zeros(0, np.float32))
    if prev is not None:
        # evaluate the PREVIOUS grid first: weights outside it become sidecar
        # outliers (shipped exact); only regrid when outliers exceed the
        # threshold or the occupied range shrank too much (resolution loss)
        lo = prev.w_min
        hi = prev.w_min + prev.bucket_size * (B_MAX - 1)
        wnp = np.asarray(flat, np.float32)
        occupied = max(float(wnp.max()) - float(wnp.min()), 1e-12)
        not_shrunk = (hi - lo) / occupied <= 4.0
        out_mask = (wnp < lo) | (wnp > hi)
        frac = float(out_mask.mean())
        if not_shrunk and frac <= OUTLIER_REGRID_FRAC:
            bucket = prev.bucket_size
            q = _quantize_core(flat, jnp.float32(lo), jnp.float32(bucket))
            if frac == 0.0:
                return q, QuantMeta(lo, bucket, int(flat.size), 0), empty
            idx = np.flatnonzero(out_mask).astype(np.uint64)
            vals = wnp[out_mask].astype(np.float32)
            return q, QuantMeta(lo, bucket, int(flat.size), int(idx.size)), (idx, vals)
        # too many outliers / shrunk range: dynamic regrid (paper behaviour)
    w_min, _, bucket = compute_bounds(flat, alpha, beta)
    q = _quantize_core(flat, jnp.float32(w_min), jnp.float32(bucket))
    return q, QuantMeta(w_min, bucket, int(flat.size), 0), empty


def dequantize(q: jnp.ndarray, meta: QuantMeta, outliers=None) -> jnp.ndarray:
    w = _dequantize_core(q, jnp.float32(meta.w_min), jnp.float32(meta.bucket_size))
    if outliers is not None and len(outliers[0]):
        w = np.asarray(w).copy()
        w[outliers[0].astype(np.int64)] = outliers[1]
        return jnp.asarray(w)
    return w


def max_error(meta: QuantMeta) -> float:
    """Quantization error bound: half a bucket (plus bound-rounding slack)."""
    return 0.5 * meta.bucket_size


# ---------------------------------------------------------------------------
# Int8 row quantization for the *serving-resident* weights (§6, serving side)
# ---------------------------------------------------------------------------
#
# The 16-bit machinery above is the paper's *wire* format: one global grid
# over the full weight space, optimized for byte-stable diffs. The serving
# engine's quantized inference path needs something different — per-row grids
# over the embedding table, so (a) the CPU-bound gather hot path moves 1 byte
# per element instead of 4, (b) a delta frame's touched rows requantize
# independently (untouched rows keep byte-identical codes — no global grid to
# churn), and (c) the per-row scale/zero pair is two f32 gathers the kernel
# folds into its in-register dequantize.
#
# Grid: symmetric-around-midpoint affine. For row r with values in
# [mn, mx]: scale_r = (mx - mn) / (ROW_LEVELS - 1), zero_r = (mn + mx) / 2,
# code = round((w - zero_r) / scale_r) in [-127, 127] (int8; -128 unused so
# the grid is symmetric). Dequantize: w ≈ code * scale_r + zero_r.
# Reconstruction error is bounded by scale_r / 2 per element
# (:func:`row_max_error`), which :func:`pair_logit_tolerance` lifts to a
# rigorous bound on the FFM interaction logits.

ROW_LEVELS = 255  # codes -127..127


def quantize_rows(w: np.ndarray):
    """Row-wise int8 quantization of a table ``w`` (rows on axis 0).

    Pure numpy (runs on the serving engine's background ingest thread — an
    XLA dispatch there would contend with scorers for the executor).
    Returns ``{"codes": int8 w.shape, "scale": f32 (rows,), "zero": f32
    (rows,)}`` — the quantized-table dict the serving layer stores in place
    of the f32 leaf (``ffm.gather_rows`` consumes it).
    """
    w = np.asarray(w, np.float32)
    flat = w.reshape(w.shape[0], -1)
    mn = flat.min(axis=1)
    mx = flat.max(axis=1)
    # degenerate (constant) rows: scale 1 and codes 0 reconstruct mn exactly
    scale = np.where(mx > mn, (mx - mn) / np.float32(ROW_LEVELS - 1),
                     np.float32(1.0)).astype(np.float32)
    zero = ((mn + mx) * np.float32(0.5)).astype(np.float32)
    bshape = (w.shape[0],) + (1,) * (w.ndim - 1)
    q = np.rint((w - zero.reshape(bshape)) / scale.reshape(bshape))
    codes = np.clip(q, -127, 127).astype(np.int8)
    return {"codes": codes, "scale": scale, "zero": zero}


def requantize_rows(qtable, w: np.ndarray, row_ranges) -> dict:
    """Requantize only ``row_ranges`` (iterable of ``(start, stop)``) of
    ``w`` into a *copy* of ``qtable``; untouched rows keep byte-identical
    codes/scale/zero (each row's grid depends only on that row's values).

    The copies matter: the previous table stays published to concurrent
    scorers until the engine's atomic swap, so it must never mutate. The
    codes copy is the 1-byte-per-element one — a quarter of what re-copying
    the f32 leaf would move.
    """
    out = {"codes": qtable["codes"].copy(), "scale": qtable["scale"].copy(),
           "zero": qtable["zero"].copy()}
    # scattered deltas produce many single-row ranges: gather them into one
    # block and quantize once, instead of a numpy round-trip per range
    rows = (np.concatenate([np.arange(r0, r1) for r0, r1 in row_ranges])
            if row_ranges else np.zeros(0, np.int64))
    if rows.size:
        part = quantize_rows(np.asarray(w, np.float32)[rows])
        out["codes"][rows] = part["codes"]
        out["scale"][rows] = part["scale"]
        out["zero"][rows] = part["zero"]
    return out


def dequantize_rows(qtable) -> np.ndarray:
    """Full-table f32 reconstruction (oracle/debug — the serving hot path
    never calls this; it dequantizes gathered rows in-register instead)."""
    codes = np.asarray(qtable["codes"])
    bshape = (codes.shape[0],) + (1,) * (codes.ndim - 1)
    return (codes.astype(np.float32) * np.asarray(qtable["scale"]).reshape(bshape)
            + np.asarray(qtable["zero"]).reshape(bshape))


def is_row_quantized(leaf) -> bool:
    """True for the quantized-table dict :func:`quantize_rows` produces
    (excluding the blocked variant — see :func:`is_block_quantized`)."""
    return (isinstance(leaf, dict) and "codes" in leaf and "scale" in leaf
            and "block" not in leaf)


# ---------------------------------------------------------------------------
# Blocked int8 quantization for *scalar-per-row* leaves (the LR table)
# ---------------------------------------------------------------------------
#
# The per-row grids above assume a row is a vector (F x k elements sharing one
# scale/zero). The LR table is (V,) — one scalar per hashed feature — so a
# per-row grid would store two f32 scalars per int8 code and *grow* the
# resident set. Blocked quantization views (V,) as (V/B, B) and fits one
# symmetric affine grid per block: resident bytes drop from 4V to
# V + 8V/B (~3.3x at B=64), reconstruction error is bounded by the coarsest
# block's scale/2 (:func:`block_max_error`), and a delta frame's touched
# elements map to touched *blocks*, which requantize independently — the
# exact analogue of the per-row independence the incremental ingest relies
# on. B trades resolution (weights sharing a grid) against overhead; 64 keeps
# the grid error comparable to the emb rows' (a block spans the same order of
# dynamic range as one F x k row) at 1/8th the f32 sidecar cost.

LR_BLOCK = 64


def quantize_blocks(w: np.ndarray, block: int = LR_BLOCK) -> dict:
    """Blocked int8 quantization of a flat ``(V,)`` float vector.

    Pure numpy (same ingest-thread contract as :func:`quantize_rows`).
    Returns ``{"codes": int8 (V,), "scale": f32 (ceil(V/B),), "zero": f32
    (ceil(V/B),), "block": B}``. A trailing partial block is padded with its
    own last element (does not perturb the block's min/max).
    """
    w = np.asarray(w, np.float32).reshape(-1)
    v = w.size
    nb = -(-v // block)
    wp = w if nb * block == v else np.concatenate(
        [w, np.full(nb * block - v, w[-1], np.float32)])
    wb = wp.reshape(nb, block)
    mn = wb.min(axis=1)
    mx = wb.max(axis=1)
    scale = np.where(mx > mn, (mx - mn) / np.float32(ROW_LEVELS - 1),
                     np.float32(1.0)).astype(np.float32)
    zero = ((mn + mx) * np.float32(0.5)).astype(np.float32)
    q = np.rint((wb - zero[:, None]) / scale[:, None])
    codes = np.clip(q, -127, 127).astype(np.int8).reshape(-1)[:v]
    return {"codes": codes, "scale": scale, "zero": zero, "block": int(block)}


def requantize_blocks(qtable: dict, w: np.ndarray, elem_ranges) -> dict:
    """Requantize only the blocks covering ``elem_ranges`` (iterable of
    element ``(start, stop)``) of ``w`` into a *copy* of ``qtable``; untouched
    blocks keep byte-identical codes/scale/zero (per-block grids are
    independent). The copy contract matches :func:`requantize_rows` — the
    previous table stays published to concurrent scorers until the swap."""
    block = int(qtable["block"])
    out = {"codes": qtable["codes"].copy(), "scale": qtable["scale"].copy(),
           "zero": qtable["zero"].copy(), "block": block}
    v = out["codes"].size
    blocks = (np.unique(np.concatenate(
        [np.arange(e0 // block, -(-e1 // block)) for e0, e1 in elem_ranges]))
        if elem_ranges else np.zeros(0, np.int64))
    if blocks.size:
        w = np.asarray(w, np.float32).reshape(-1)
        # gather the touched blocks' elements (trailing partial block padded
        # with its own last element — same padding quantize_blocks applies, so
        # the grids come out byte-identical to a full requantize), quantize
        # them as one exact-multiple vector, scatter codes back elementwise
        elem = blocks[:, None] * block + np.arange(block)[None, :]
        src = np.minimum(elem, v - 1).reshape(-1)
        part = quantize_blocks(w[src], block)
        keep = (elem < v).reshape(-1)
        out["codes"][elem.reshape(-1)[keep]] = part["codes"][keep]
        out["scale"][blocks] = part["scale"]
        out["zero"][blocks] = part["zero"]
    return out


def dequantize_blocks(qtable: dict) -> np.ndarray:
    """Full-vector f32 reconstruction (oracle/debug; the hot path gathers +
    dequantizes per element via ``ffm.gather_lr``)."""
    codes = np.asarray(qtable["codes"])
    block = int(qtable["block"])
    b = np.arange(codes.size) // block
    return (codes.astype(np.float32) * np.asarray(qtable["scale"])[b]
            + np.asarray(qtable["zero"])[b])


def is_block_quantized(leaf) -> bool:
    """True for the blocked-table dict :func:`quantize_blocks` produces."""
    return isinstance(leaf, dict) and "codes" in leaf and "block" in leaf


def block_max_error(qtable) -> float:
    """Max |w - dequantize(quantize(w))| over the vector: half the coarsest
    block's bucket (the blocked analogue of :func:`row_max_error`)."""
    return float(np.max(np.asarray(qtable["scale"]))) * 0.5


def row_max_error(qtable) -> float:
    """Max |w - dequantize(quantize(w))| over the table: half the coarsest
    row's bucket (the per-row analogue of :func:`max_error`)."""
    return float(np.max(np.asarray(qtable["scale"]))) * 0.5


def pair_logit_tolerance(cfg, emb_absmax: float, eps: float,
                         vmax: float = 1.0, lr_eps: float = 0.0) -> float:
    """Rigorous bound on the FFM-logit deviation caused by per-element
    embedding error ``eps`` (= :func:`row_max_error` of the serving table)
    plus per-weight LR error ``lr_eps`` (= :func:`block_max_error` of the
    blocked LR table; 0 when the LR table is served f32).

    Each DiagMask pair contributes ``e_i · e_j * v_i * v_j`` with both sides
    quantized, so its deviation is at most ``k * (2 * |e|_inf * eps + eps^2)
    * vmax^2``; the ``ffm`` head sums ``n_pairs`` of them plus ``n_fields``
    LR terms ``w_f * v_f``, each off by at most ``lr_eps * vmax``. For
    ``deepffm`` the MergeNorm/MLP head can amplify further — use the
    roundtrip-oracle parity check for exact head-agnostic equivalence and
    this bound for the additive part.
    """
    per_pair = cfg.k * (2.0 * emb_absmax * eps + eps * eps) * vmax * vmax
    return cfg.n_pairs * per_pair + cfg.n_fields * lr_eps * vmax


def fused_logit_tolerance(cfg, emb_absmax: float, eps: float,
                          vmax: float = 1.0, lr_max: float = 1.0) -> float:
    """Float-reassociation envelope between the fused int8-accumulator logit
    and the staged (dequantize-rows-then-f32-dots) oracle — the two paths
    score the *same* quantized model, so quantization error cancels and only
    f32 rounding from reordered sums remains.

    The fused kernel's cand-cand dots are exact in int32 (``|q| <= 127``,
    ``K`` terms: far inside int32 range) and dequantize once per scalar dot
    via the affine decomposition; the staged path rounds after every f32
    multiply-add along the ``K`` axis instead. Bounding each pair dot by
    ``k * amax^2`` (``amax = emb_absmax + eps``, the dequantized-row bound)
    and charging one ulp (``u = 2^-24``) per floating operation along the
    deepest reassociated chain — ``2k`` for the dot, ~``8`` for the affine
    recombination, ``n_pairs`` for the head-sum reorder — gives an additive
    per-logit envelope; the LR/base terms reorder across at most
    ``n_fields + 2`` adds of magnitude ``<= lr_max * vmax``.

    This is deliberately generous (a worst-case chain bound, not an expected
    error) so parity tests stay deterministic across BLAS/kernel versions.
    """
    u = 2.0 ** -24
    amax = emb_absmax + eps
    per_pair = cfg.k * amax * amax * vmax * vmax
    pair_part = cfg.n_pairs * per_pair * (2.0 * cfg.k + 8.0 + cfg.n_pairs) * u
    lr_part = cfg.n_fields * lr_max * vmax * (cfg.n_fields + 2.0) * u
    return pair_part + lr_part


ROW_QUANT_PATHS = (("ffm", "emb"), ("emb",))
BLOCK_QUANT_PATHS = (("lr", "w"),)


def _walk(tree, path):
    node = tree
    for key in path:
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    return node


def quantize_params_rows(params, prev=None, touched_rows=None,
                         paths=ROW_QUANT_PATHS, block_paths=BLOCK_QUANT_PATHS,
                         lr_block: int = LR_BLOCK, stats=None):
    """Serving-side quantize-on-ingest: replace the gather-table leaves of a
    params pytree with int8 quantized table dicts.

    ``paths`` names the row-gathered tables (DeepFFM's ``ffm/emb``, and the
    top-level ``emb`` of the mlp baseline and of DCN-v2, rows of width
    ``k``) — per-row grids (:func:`quantize_rows`).
    ``block_paths`` names the scalar-per-row tables (the LR vector) — blocked
    grids (:func:`quantize_blocks`, block ``lr_block``). Every other leaf
    (MergeNorm, MLP, LR bias — tiny next to the tables) stays f32. ``prev``
    is the previously published quantized params: when given together with
    ``touched_rows`` (a dict mapping "/".joined leaf paths to ``(start,
    stop)`` range lists — rows for row leaves, elements for blocked leaves),
    only those rows/blocks requantize — the steady-state delta-frame ingest
    cost. Returns a new top-level pytree; untouched subtrees are shared.
    ``stats`` (a mutable dict) gets ``"rows_requantized"`` /
    ``"blocks_requantized"`` incremented by the work actually done.
    """
    out = {k: v for k, v in params.items()}
    for path, blocked in ([(p, False) for p in paths]
                          + [(p, True) for p in block_paths]):
        node = _walk(out, path)
        quantized_already = (is_block_quantized(node) if blocked
                             else is_row_quantized(node))
        if node is None or quantized_already:
            continue
        # copy the subdict chain so the caller's pytree is never mutated
        sub = out
        for key in path[:-1]:
            sub[key] = dict(sub[key])
            sub = sub[key]
        pstr = "/".join(path)
        pq = None
        if prev is not None:
            pnode = _walk(prev, path)
            if blocked:
                if is_block_quantized(pnode) \
                        and pnode["codes"].shape == np.asarray(node).shape \
                        and int(pnode["block"]) == lr_block:
                    pq = pnode
            elif is_row_quantized(pnode) \
                    and pnode["codes"].shape == np.asarray(node).shape:
                pq = pnode
        if pq is not None and touched_rows is not None:
            ranges = touched_rows.get(pstr, ())
            if blocked:
                sub[path[-1]] = requantize_blocks(pq, node, ranges)
                blk = set()
                for e0, e1 in ranges:
                    blk.update(range(e0 // lr_block, -(-e1 // lr_block)))
                n_units = len(blk)
            else:
                sub[path[-1]] = requantize_rows(pq, node, ranges)
                n_units = sum(r1 - r0 for r0, r1 in ranges)
        elif blocked:
            sub[path[-1]] = quantize_blocks(np.asarray(node), lr_block)
            n_units = sub[path[-1]]["scale"].shape[0]
        else:
            sub[path[-1]] = quantize_rows(np.asarray(node))
            n_units = sub[path[-1]]["codes"].shape[0]
        if stats is not None:
            key = "blocks_requantized" if blocked else "rows_requantized"
            stats[key] = stats.get(key, 0) + n_units
    return out


def quantized_nbytes(params) -> int:
    """Total resident bytes of a params pytree, counting quantized-table
    dicts at their int8+scales size (the bench's ~4x-down assertion)."""
    import jax

    return sum(np.asarray(leaf).nbytes
               for leaf in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Byte-level weight-file format (header + payload), as shipped across DCs
# ---------------------------------------------------------------------------

def to_bytes(q: jnp.ndarray, meta: QuantMeta, outliers=None) -> bytes:
    header = struct.pack(HEADER_FMT, meta.w_min, meta.bucket_size, meta.n,
                         meta.n_outliers)
    body = header + np.asarray(q, dtype="<u2").tobytes()
    if meta.n_outliers:
        idx, vals = outliers
        body += np.asarray(idx, "<u8").tobytes() + np.asarray(vals, "<f4").tobytes()
    return body


def from_bytes(buf: bytes):
    w_min, bucket, n, n_out = struct.unpack(HEADER_FMT, buf[:HEADER_SIZE])
    q = np.frombuffer(buf, dtype="<u2", offset=HEADER_SIZE, count=n)
    meta = QuantMeta(w_min, bucket, n, n_out)
    outliers = (np.zeros(0, np.uint64), np.zeros(0, np.float32))
    if n_out:
        off = HEADER_SIZE + 2 * n
        idx = np.frombuffer(buf, dtype="<u8", offset=off, count=n_out)
        vals = np.frombuffer(buf, dtype="<f4", offset=off + 8 * n_out, count=n_out)
        outliers = (idx, vals)
    return q, meta, outliers


def quantize_to_bytes(w: jnp.ndarray, alpha: int = 2, beta: int = 2,
                      prev: Optional[QuantMeta] = None) -> bytes:
    q, meta, outliers = quantize(w, alpha, beta, prev=prev)
    return to_bytes(q, meta, outliers)


def dequantize_from_bytes(buf: bytes) -> np.ndarray:
    """Pure-numpy reconstruction (serving side).

    Deliberately avoids the jitted ``_dequantize_core``: this runs on the
    serving engine's background update-pipe thread, and an XLA dispatch there
    would contend with the scoring threads' XLA computations for the shared
    CPU executor — exactly the request-path stall async ingestion removes.
    numpy's f32 ``min + q * bucket`` matches the XLA kernel bit-for-bit
    (same IEEE ops, no fusion).
    """
    q, meta, outliers = from_bytes(buf)
    w = (np.float32(meta.w_min)
         + q.astype(np.float32) * np.float32(meta.bucket_size))
    if meta.n_outliers:
        idx, vals = outliers
        w[idx.astype(np.int64)] = vals
    return w
