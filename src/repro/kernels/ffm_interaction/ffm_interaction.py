"""Pallas TPU kernel: FFM pairwise field-aware interactions (paper §5).

This is the serving hot spot the paper attacks with SIMD intrinsics; the
TPU-native analogue is a VPU-tiled kernel over the batch with the whole
(F, F, K) field-embedding block of each example resident in VMEM.

Per example b the kernel computes the full field x field dot matrix
  D[b, i, j] = sum_k E[b, i, j, k] * E[b, j, i, k] * v[b,i] * v[b,j]
in one vectorized pass (the DiagMask upper-triangle extraction is a cheap
static gather done outside — Pallas TPU prefers dense regular access).

Block layouts follow the Mosaic rule that a block's last two dims either
divide by the (8, 128) tile or equal the array's own dims. Every operand
here keeps small trailing dims (K, a field count, or 1), so the wrappers
reshape per-row vectors into ``(..., F, 1)`` column and ``(..., 1, F)`` row
forms before the call: each block then spans its array's full trailing
dims, and the kernels only broadcast — they never move a value between the
lane and sublane axes. The request-row axis of the candidate kernels is a
squeezed block dim, and per-candidate scalars ride as ``(..., 1, 1)``
blocks, so any row and candidate count compiles.

The minor axis of every block is ``K`` (8 at production widths), which
pads to 128 lanes in VMEM: a ``(24, 24, 8)`` f32 example block occupies
288 KiB there, not 18 KiB. Tile sizes are chosen for that padded size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode

_SQ = pl.Squeezed()  # block dim of size 1, dropped from the kernel's view


def _ffm_kernel(e_ref, vcol_ref, vrow_ref, out_ref):
    e = e_ref[...]  # (Bt, F, F, K)
    et = jnp.swapaxes(e, 1, 2)  # E[b, j, i, k]
    dots = jnp.sum(e * et, axis=-1)  # (Bt, F, F)
    out_ref[...] = dots * (vcol_ref[...] * vrow_ref[...])


def ffm_interaction_matrix(e: jnp.ndarray, v: jnp.ndarray, *, block_b: int = 8,
                           interpret=None) -> jnp.ndarray:
    """e: (B, F, F, K) gathered embeddings; v: (B, F) -> (B, F, F) dot matrix.

    ``block_b`` examples per grid step: at F=24, K=8 one f32 example block
    pads to 288 KiB of VMEM, so 8 of them (double-buffered, plus the
    transposed copy and the product) stay inside the 16 MiB scoped limit."""
    b, f, _, k = e.shape
    bt = min(block_b, b)
    pad = (-b) % bt
    if pad:
        e = jnp.pad(e, ((0, pad), (0, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad), (0, 0)))
    bp = e.shape[0]
    out = pl.pallas_call(
        _ffm_kernel,
        grid=(bp // bt,),
        in_specs=[
            pl.BlockSpec((bt, f, f, k), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((bt, f, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((bt, 1, f), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, f, f), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, f, f), e.dtype),
        interpret=interpret_mode(interpret),
    )(e, v[:, :, None], v[:, None, :])
    return out[:b]


def _cand_pairs(ectx, vctx, ecx, ecc, vc_col, vc_row, xc_ref, aa_ref):
    """Shared body of the candidate-matrix kernels.

    ectx (Fc, Fcand, K) cached ctx embeddings in cand fields; vctx (Fc, 1);
    ecx (Nt, Fcand, Fc, K) cand embeddings in ctx fields; ecc (Nt, Fcand,
    Fcand, K) cand embeddings in cand fields; vc_col/vc_row (Nt, Fcand, 1) /
    (Nt, 1, Fcand) candidate values."""
    # ctx-cand: D[n, i, jc] = <ectx[i, jc], ecx[n, jc, i]> * vctx[i] * vc[n, jc]
    ecx_t = jnp.swapaxes(ecx, 1, 2)  # (Nt, Fc, Fcand, K)
    dots_xc = jnp.sum(ectx[None] * ecx_t, axis=-1)  # (Nt, Fc, Fcand)
    xc_ref[...] = dots_xc * vctx[None] * vc_row
    # cand-cand: D[n, ic, jc] = <ecc[n, ic, jc], ecc[n, jc, ic]> * vc[n,ic] * vc[n,jc]
    dots_aa = jnp.sum(ecc * jnp.swapaxes(ecc, 1, 2), axis=-1)  # (Nt, Fcand, Fcand)
    aa_ref[...] = dots_aa * vc_col * vc_row


def _cand_kernel(ectx_ref, vctx_ref, ecx_ref, ecc_ref, vcol_ref, vrow_ref,
                 xc_ref, aa_ref):
    _cand_pairs(ectx_ref[...], vctx_ref[...], ecx_ref[...], ecc_ref[...],
                vcol_ref[...], vrow_ref[...], xc_ref, aa_ref)


def _cand_call(kernel, ectx, vctx, cand_blocks, vcand, block_n, interpret):
    """pallas_call plumbing for the candidate-matrix kernels: grid over
    (request row, candidate tile). ``cand_blocks`` are (R, N, ...) arrays
    tiled along N; per step one row's cached context block stays resident.
    Returns xc (R, N, Fc, Fcand), aa (R, N, Fcand, Fcand) f32."""
    r, fc, fcand, k = ectx.shape
    n = vcand.shape[1]
    nt = min(block_n, n)
    pad = (-n) % nt
    if pad:
        vcand = jnp.pad(vcand, ((0, 0), (0, pad), (0, 0)))
        cand_blocks = [_pad_cands(b, pad) for b in cand_blocks]
    np_ = vcand.shape[1]
    cand_blocks = list(cand_blocks) + [vcand[..., :, None],
                                       vcand[..., None, :]]
    xc, aa = pl.pallas_call(
        kernel,
        grid=(r, np_ // nt),
        in_specs=[
            pl.BlockSpec((_SQ, fc, fcand, k), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((_SQ, fc, 1), lambda i, j: (i, 0, 0)),
            *[_cand_spec(b, nt) for b in cand_blocks],
        ],
        out_specs=[
            pl.BlockSpec((_SQ, nt, fc, fcand), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((_SQ, nt, fcand, fcand), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, np_, fc, fcand), jnp.float32),
            jax.ShapeDtypeStruct((r, np_, fcand, fcand), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(ectx, vctx[:, :, None], *cand_blocks)
    return xc[:, :n], aa[:, :n]


def _pad_cands(b, pad):
    return jnp.pad(b, ((0, 0), (0, pad)) + ((0, 0),) * (b.ndim - 2))


def _cand_spec(b, nt):
    """Block of a (R, N, ...) candidate operand: squeezed row, ``nt``
    candidates, the operand's full trailing dims. The index map ignores any
    scalar-prefetch refs the grid passes after the grid indices."""
    tail = b.ndim - 2
    return pl.BlockSpec(
        (_SQ, nt) + tuple(b.shape[2:]),
        lambda i, j, *_: (i, j) + (0,) * tail)


def ffm_candidate_matrices(ectx: jnp.ndarray, vctx: jnp.ndarray, ecx: jnp.ndarray,
                           ecc: jnp.ndarray, vcand: jnp.ndarray, *,
                           block_n: int = 32, interpret=None):
    """Candidate-block interactions consuming cached context partials (§5).

    The companion of :func:`ffm_interaction_matrix` for the context-cache
    serving path: the ctx-ctx block is already cached per request, so this
    kernel computes only the candidate-dependent ctx-cand and cand-cand dot
    matrices. Request-batched: grid (R, N tiles); each step keeps the request's
    whole cached (Fc, Fcand, K) context block plus one (Nt, Fcand, ·, K)
    candidate tile resident in VMEM.

    ectx:  (R, Fc, Fcand, K)    cached context embeddings for candidate fields
    vctx:  (R, Fc)              cached context values
    ecx:   (R, N, Fcand, Fc, K) candidate embeddings for context fields
    ecc:   (R, N, Fcand, Fcand, K) candidate embeddings for candidate fields
    vcand: (R, N, Fcand)        candidate values
    ->     xc (R, N, Fc, Fcand), aa (R, N, Fcand, Fcand) dot matrices
    """
    return _cand_call(_cand_kernel, ectx, vctx, [ecx, ecc], vcand,
                      block_n, interpret)


def _ctx_tail_block(ectx, vcol, vrow, p):
    """Shared fused-kernel context block: the full (Fc, Fc) ctx-ctx pair
    matrix (dots x value products) plus the *tail* pair sum — every pair
    (i, j) with i < j and j >= p, i.e. exactly the pairs a depth-p cached
    prefix is missing. This is ``ffm.extend_context_prefix``'s tail einsum
    folded into the candidate kernel, so a partial-depth context costs no
    host pair arithmetic on the scoring path. Returns the (1, 1) tail."""
    fc = ectx.shape[0]
    ec = ectx[:, :fc]                                  # (Fc, Fc, K)
    d = jnp.sum(ec * jnp.swapaxes(ec, 0, 1), axis=-1)  # (Fc, Fc)
    d = d * (vcol * vrow)
    ii = jax.lax.broadcasted_iota(jnp.int32, (fc, fc), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (fc, fc), 1)
    tail = jnp.where((ii < jj) & (jj >= p), d, 0.0)
    tail = jnp.sum(jnp.sum(tail, axis=1, keepdims=True), axis=0, keepdims=True)
    return d, tail


def _per_candidate_sum(x):
    """(Nt, A, B) -> (Nt, 1, 1) sum over the two pair axes (keepdims
    throughout, so no reduction result changes layout)."""
    return jnp.sum(jnp.sum(x, axis=2, keepdims=True), axis=1, keepdims=True)


def _cand_cand_sum(dots, vc_col, vc_row):
    """(Nt, Fcand, Fcand) cand-cand dots -> (Nt, 1, 1) sum of the pairs
    ic < jc, each times its two candidate values."""
    fcand = vc_row.shape[-1]
    ic = jax.lax.broadcasted_iota(jnp.int32, (fcand, fcand), 0)
    jc = jax.lax.broadcasted_iota(jnp.int32, (fcand, fcand), 1)
    return _per_candidate_sum(
        jnp.where((ic < jc)[None], dots * vc_col * vc_row, 0.0))


def _fused_kernel_q8(depth_ref, ectx_ref, vcol_ref, vrow_ref, base_ref,
                     qcx_ref, qcc_ref, scol_ref, srow_ref, zcol_ref, zrow_ref,
                     vccol_ref, vcrow_ref, out_ref, dots_ref):
    p = depth_ref[pl.program_id(0)]  # cached prefix depth of this row
    ectx = ectx_ref[...]   # (Fc, F, K) f32 — full-depth ctx embeddings
    vcol = vcol_ref[...]   # (Fc, 1) ctx values
    base = base_ref[...]   # (Nt, 1, 1) lr_ctx + lr_cand + bias + cached pairs
    s_col, s_row = scol_ref[...], srow_ref[...]  # (Nt, Fcand, 1) / (Nt, 1, Fcand)
    z_col, z_row = zcol_ref[...], zrow_ref[...]  # per-hash-row dequant grids
    vc_col, vc_row = vccol_ref[...], vcrow_ref[...]
    fc = ectx.shape[0]
    k = ectx.shape[-1]

    # ctx-ctx: cached pair sum arrives in `base`; only the tail pairs
    # (j >= p) are computed here, in-kernel
    d, tail = _ctx_tail_block(ectx, vcol, vrow_ref[...], p)
    dots_ref[...] = d

    # ctx-cand: f32 ctx activation x int8 candidate codes. Affine-decomposed
    # per candidate row (e = q*s + z): dot(ex, e) = s * dot(ex, q) +
    # z * sum(ex) — the zero-point never multiplies element-wise
    ex = ectx[:, fc:]                                  # (Fc, Fcand, K)
    qx = qcx_ref[...].astype(jnp.float32)              # (Nt, Fcand, Fc, K)
    dq = jnp.sum(ex[None] * jnp.swapaxes(qx, 1, 2), axis=-1)  # (Nt, Fc, Fcand)
    esum = jnp.sum(ex, axis=-1)                        # (Fc, Fcand)
    xc = s_row * dq + z_row * esum[None]
    xc_sum = _per_candidate_sum(xc * vcol[None] * vc_row)

    # cand-cand: int8 x int8 -> int32 accumulation; dequantization touches
    # only the scalar dot results, never the K-vectors. With e_i = q_i*s_i +
    # z_i (per-row grids): dot(e_i, e_j) = s_i s_j Q_ij + s_i z_j A_ij +
    # s_j z_i A_ji + K z_i z_j, where Q (code dot) and A (code row-sums)
    # are exact int32.
    q = qcc_ref[...].astype(jnp.int32)                 # (Nt, Fcand, Fcand, K)
    qt = jnp.swapaxes(q, 1, 2)
    qd = jnp.sum(q * qt, axis=-1).astype(jnp.float32)
    a = jnp.sum(q, axis=-1).astype(jnp.float32)        # (Nt, Fcand, Fcand)
    a_t = jnp.sum(qt, axis=-1).astype(jnp.float32)     # A_ji
    aa = (s_col * s_row * qd
          + s_col * z_row * a
          + s_row * z_col * a_t
          + k * z_col * z_row)
    aa_sum = _cand_cand_sum(aa, vc_col, vc_row)

    out_ref[...] = base + tail[None] + xc_sum + aa_sum


def _fused_kernel_rows(depth_ref, ectx_ref, vcol_ref, vrow_ref, base_ref,
                       ecx_ref, ecc_ref, vccol_ref, vcrow_ref, out_ref,
                       dots_ref):
    p = depth_ref[pl.program_id(0)]
    ectx = ectx_ref[...]   # (Fc, F, K)
    vcol = vcol_ref[...]
    base = base_ref[...]
    vc_col, vc_row = vccol_ref[...], vcrow_ref[...]

    d, tail = _ctx_tail_block(ectx, vcol, vrow_ref[...], p)
    dots_ref[...] = d

    ex = ectx[:, ectx.shape[0]:]                       # (Fc, Fcand, K)
    ecx = ecx_ref[...]                                 # (Nt, Fcand, Fc, K)
    dx = jnp.sum(ex[None] * jnp.swapaxes(ecx, 1, 2), axis=-1)
    xc_sum = _per_candidate_sum(dx * vcol[None] * vc_row)

    ecc = ecc_ref[...]                                 # (Nt, Fcand, Fcand, K)
    da = jnp.sum(ecc * jnp.swapaxes(ecc, 1, 2), axis=-1)
    out_ref[...] = base + tail[None] + xc_sum + _cand_cand_sum(da, vc_col,
                                                               vc_row)


def _fused_call(kernel, ectx, vctx, depth, base, cand_blocks, vcand,
                block_n: int, interpret):
    """Common pallas_call plumbing for the fused-logit kernels: grid over
    (request row, candidate tile); per step one row's whole context block
    plus one candidate tile is resident. ``depth`` is a scalar-prefetch
    operand (SMEM), read by row. Outputs the (R, N) logits and the per-row
    (Fc, Fc) ctx pair matrix (each candidate tile recomputes and writes the
    same ctx block — Fc^2 values, noise next to the tile math — which the
    engine reads back to insert full-depth prefix states)."""
    r, fc, f, k = ectx.shape
    n = vcand.shape[1]
    nt = min(block_n, n)
    pad = (-n) % nt
    if pad:
        base = jnp.pad(base, ((0, 0), (0, pad)))
        vcand = jnp.pad(vcand, ((0, 0), (0, pad), (0, 0)))
        cand_blocks = [_pad_cands(b, pad) for b in cand_blocks]
    np_ = vcand.shape[1]
    cand_blocks = ([base[..., None, None]] + list(cand_blocks)
                   + [vcand[..., :, None], vcand[..., None, :]])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r, np_ // nt),
        in_specs=[
            pl.BlockSpec((_SQ, fc, f, k), lambda i, j, _: (i, 0, 0, 0)),
            pl.BlockSpec((_SQ, fc, 1), lambda i, j, _: (i, 0, 0)),
            pl.BlockSpec((_SQ, 1, fc), lambda i, j, _: (i, 0, 0)),
            *[_cand_spec(b, nt) for b in cand_blocks],
        ],
        out_specs=[
            pl.BlockSpec((_SQ, nt, 1, 1), lambda i, j, _: (i, j, 0, 0)),
            pl.BlockSpec((_SQ, fc, fc), lambda i, j, _: (i, 0, 0)),
        ],
    )
    out, dots = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, np_, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((r, fc, fc), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(depth.astype(jnp.int32), ectx, vctx[:, :, None], vctx[:, None, :],
      *cand_blocks)
    return out.reshape(r, np_)[:, :n], dots


def ffm_fused_logits_q8(ectx: jnp.ndarray, vctx: jnp.ndarray,
                        depth: jnp.ndarray, base: jnp.ndarray,
                        qcx: jnp.ndarray, qcc: jnp.ndarray,
                        scale: jnp.ndarray, zero: jnp.ndarray,
                        vcand: jnp.ndarray, *, block_n: int = 64,
                        interpret=None):
    """One fused Pallas call per padding bucket: context-tail pairs +
    candidate pair terms + the additive FFM head, int8 pair arithmetic.

    The single-call serving path the roofline report motivates: instead of
    staging ``extend_context_prefix`` (host) -> candidate dot matrices ->
    pair-vector scatter -> head sum, each grid step takes one request row's
    full-depth context block and a candidate tile and emits *logits*
    directly — the (R, N, n_pairs) pair vector and the (R, N, Fc, Fcand) /
    (R, N, Fcand, Fcand) dot matrices never exist in memory. Candidate
    cand-cand pair dots accumulate as **int8 x int8 -> int32** (exact) and
    dequantize only the scalar dot result via the per-row ``(scale, zero)``
    grids; ctx-cand dots keep the f32 cached-activation side and decompose
    the candidate affine so the zero-point never multiplies element-wise.

    ectx:  (R, Fc, F, K) f32   full-depth context embeddings (tail rows
                               host-gathered; their *pairs* compute here)
    vctx:  (R, Fc)             context values
    depth: (R,) int32          cached prefix depth p per row — pairs with
                               j >= p are computed in-kernel, the rest
                               arrive pre-summed inside ``base``
    base:  (R, N) f32          lr_ctx + lr_cand + bias + cached ctx pair sum
    qcx:   (R, N, Fcand, Fc, K) int8    candidate codes, ctx-field columns
    qcc:   (R, N, Fcand, Fcand, K) int8 candidate codes, cand-field columns
    scale/zero: (R, N, Fcand) f32       per-candidate-row dequant grids
    vcand: (R, N, Fcand)
    ->     logits (R, N) f32, ctx_dots (R, Fc, Fc) f32 (pair matrix with
           value products applied — rows of it are the j-major tail pairs
           the engine inserts into the prefix cache after scoring)
    """
    grids = [scale[..., :, None], scale[..., None, :],
             zero[..., :, None], zero[..., None, :]]
    return _fused_call(_fused_kernel_q8, ectx, vctx, depth, base,
                       [qcx, qcc] + grids, vcand, block_n, interpret)


def ffm_fused_logits_rows(ectx: jnp.ndarray, vctx: jnp.ndarray,
                          depth: jnp.ndarray, base: jnp.ndarray,
                          ecx: jnp.ndarray, ecc: jnp.ndarray,
                          vcand: jnp.ndarray, *, block_n: int = 32,
                          interpret=None):
    """f32 twin of :func:`ffm_fused_logits_q8` for engines serving f32
    tables above the gather cliff: same single-call fusion (tail pairs +
    candidate pairs + additive head), pre-gathered f32 candidate rows
    ``ecx`` (R, N, Fcand, Fc, K) / ``ecc`` (R, N, Fcand, Fcand, K) instead
    of int8 codes + grids. Returns (logits (R, N), ctx_dots (R, Fc, Fc))."""
    return _fused_call(_fused_kernel_rows, ectx, vctx, depth, base,
                       [ecx, ecc], vcand, block_n, interpret)


def _cand_kernel_q8(ectx_ref, vctx_ref, qcx_ref, qcc_ref, s_ref, z_ref,
                    vcol_ref, vrow_ref, xc_ref, aa_ref):
    s = s_ref[...]  # (Nt, Fcand, 1, 1) per-hash-row grids
    z = z_ref[...]
    # in-register dequantize: the int8 codes are what crossed HBM; the f32
    # rows exist only in this tile's VMEM for the duration of the dot pass
    ecx = qcx_ref[...].astype(jnp.float32) * s + z  # (Nt, Fcand, Fc, K)
    ecc = qcc_ref[...].astype(jnp.float32) * s + z  # (Nt, Fcand, Fcand, K)
    _cand_pairs(ectx_ref[...], vctx_ref[...], ecx, ecc, vcol_ref[...],
                vrow_ref[...], xc_ref, aa_ref)


def ffm_candidate_matrices_q8(ectx: jnp.ndarray, vctx: jnp.ndarray,
                              qcx: jnp.ndarray, qcc: jnp.ndarray,
                              scale: jnp.ndarray, zero: jnp.ndarray,
                              vcand: jnp.ndarray, *, block_n: int = 64,
                              interpret=None):
    """Fused dequantize + candidate-block interactions (§5 hot loop x §6).

    The int8 twin of :func:`ffm_candidate_matrices`: candidate embeddings
    arrive as int8 codes gathered straight from the row-quantized serving
    table (``quantization.quantize_rows`` grids), with one ``(scale, zero)``
    f32 pair per candidate feature row. Dequantization happens in-register
    inside the kernel, so the request path's memory traffic for candidate
    rows is 1 byte/element + two scalars per row — the f32 candidate block
    never exists in memory. The cached context side stays f32: those are
    activations (computed partials), not resident weights.

    ectx:  (R, Fc, Fcand, K) f32   cached context embeddings (cand fields)
    vctx:  (R, Fc)                 cached context values
    qcx:   (R, N, Fcand, Fc, K)    int8 candidate codes for context fields
    qcc:   (R, N, Fcand, Fcand, K) int8 candidate codes for candidate fields
    scale: (R, N, Fcand) f32       per-candidate-row dequant scale
    zero:  (R, N, Fcand) f32       per-candidate-row dequant zero point
    vcand: (R, N, Fcand)           candidate values
    ->     xc (R, N, Fc, Fcand), aa (R, N, Fcand, Fcand) f32 dot matrices
    """
    return _cand_call(_cand_kernel_q8, ectx, vctx,
                      [qcx, qcc, scale[..., None, None], zero[..., None, None]],
                      vcand, block_n, interpret)
