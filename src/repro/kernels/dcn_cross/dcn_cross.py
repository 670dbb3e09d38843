"""Pallas TPU kernel: the DCN-v2 cross stack and deep network over int8 rows.

One grid step scores ``tn`` candidates of one request row. Everything is
laid out along ``Dp`` lanes, the embedding width ``D = F * k`` padded to a
multiple of 128: lanes ``[0, Dc)`` hold the context fields, ``[Dc, D)`` the
candidate fields, the rest stay zero through every layer.

Per step the kernel

1. dequantizes the candidate rows in-register: ``codes`` arrive as int8
   already at their lanes, and the per-(candidate, field) factors ``scale *
   value`` and ``zero * value`` are spread over their field's ``k`` lanes by
   one MXU product with a 0/1 expansion matrix. Each factor arrives as a
   bfloat16 pair ``hi + lo`` and every output lane sums exactly one ``hi``
   and one ``lo`` in float32, so the spread is exact to 2^-17 of the factor;
2. adds the row's context lanes ``x0c`` to get ``x0``;
3. runs layer 0 as ``y = p + x0a @ W0[K0:]``: the context's part of
   ``x0 @ W0`` (plus ``b0``) arrives precomputed per row as ``p``, and the
   candidate lanes ``x0a`` are zero below ``Dc``, so only weight rows from
   ``K0``, ``Dc`` rounded down to 128, enter the product;
4. runs ``x = x0 * y + x`` and the layers ``l >= 1``;
5. runs the deep network's ReLU layers and its width-1 output layer (a lane
   reduction in float32) and writes one logit per candidate.

Every product of the cross and deep layers is float32 to about 2^-16,
computed as three bfloat16 MXU passes with float32 accumulation: with ``a =
a_hi + a_lo`` and ``w = w_hi + w_lo`` split into bfloat16 parts,
``a @ w ~ a_hi @ w_hi + a_hi @ w_lo + a_lo @ w_hi`` (the weights arrive
split; activations split in-register). One bfloat16 pass would read, over
D = 1,521 and three cross layers, within a factor of two of a model run
wholly in bfloat16, so a check could not tell the two apart. Every
elementwise step is float32. The weights' blocks have a constant index
map, so the pipeline fetches them once per call and keeps them in VMEM
across tiles, in one buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode

MXU_DTYPE = jnp.bfloat16
LANES = 128
BLOCK_N = 256       # candidates per grid step (an MXU-friendly LHS height)
VMEM_LIMIT = 100 * 2 ** 20   # split weights (~36 MiB at Criteo widths) plus
#                              a tile's float32 temporaries

_SQ = pl.Squeezed()


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def split(x):
    """Float32 ``x`` as a bfloat16 pair ``(hi, lo)`` with ``hi + lo ~ x`` to
    2^-16: ``hi`` is ``x`` rounded to bfloat16 (to nearest, ties to even) in
    integer arithmetic, ``lo`` the rest. The plainer ``x - f32(bf16(x))``
    does not survive XLA on the TPU, which may fold that round trip to
    ``x`` (excess precision is allowed), zeroing ``lo`` and leaving one
    bfloat16 pass."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & -0x10000
    hi = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return hi.astype(MXU_DTYPE), (x - hi).astype(MXU_DTYPE)


def _mxu(a, w):
    return jnp.dot(a, w, preferred_element_type=jnp.float32)


def dot3(a, w_hi, w_lo):
    """``a @ w`` in three bfloat16 passes, float32 accumulation."""
    a_hi, a_lo = split(a)
    return _mxu(a_hi, w_lo) + _mxu(a_lo, w_hi) + _mxu(a_hi, w_hi)


def _kernel(n_cross: int, n_hidden: int, k0: int, x0c_ref, p_ref, codes_ref,
            g_ref, e_ref, w0h_ref, w0l_ref, *refs):
    refs = list(refs)
    out_ref = refs.pop()
    if n_cross > 1:
        wrh_ref, wrl_ref, br_ref = refs[:3]
        refs = refs[3:]
    hidden, (m_last_ref, c_last_ref) = refs[:3 * n_hidden], refs[3 * n_hidden:]
    dp = x0c_ref.shape[-1]

    spread = _mxu(g_ref[...], e_ref[...])                 # (tn, 2 Dp)
    xa = codes_ref[...].astype(jnp.float32) * spread[:, :dp] + spread[:, dp:]
    x0 = xa + x0c_ref[...]
    x = x0 * (p_ref[...] + dot3(xa[:, k0:], w0h_ref[...], w0l_ref[...])) + x0
    for l in range(n_cross - 1):
        x = x0 * (dot3(x, wrh_ref[l], wrl_ref[l]) + br_ref[l:l + 1, :]) + x
    h = x
    for i in range(n_hidden):
        w_hi, w_lo, b = hidden[3 * i:3 * i + 3]
        h = jnp.maximum(dot3(h, w_hi[...], w_lo[...]) + b[...], 0.0)
    out_ref[...] = (jnp.sum(h * m_last_ref[...], axis=-1, keepdims=True)
                    + c_last_ref[...])


def _const_spec(x):
    """A whole weight array as one block, fetched once per call into one
    buffer."""
    zeros = (0,) * x.ndim
    return pl.BlockSpec(tuple(x.shape), lambda i, j: zeros,
                        pipeline_mode=pl.Buffered(1))


def dcn_cross_call(x0c, p, codes, g, e, w0a, wr, br, hidden, m_last, c_last,
                   *, k0: int, block_n: int = BLOCK_N, interpret=None):
    """The padded call. ``x0c``/``p`` (R, 1, Dp) f32 per row; ``codes`` (R,
    N, Dp) int8 and ``g`` (R, N, Kg) bf16 per candidate; ``e`` (Kg, 2 Dp)
    bf16; ``w0a`` the pair (hi, lo) of layer 0's rows from ``k0`` (Dp - k0,
    Dp) and ``wr`` that of layers 1 .. L - 1 (L - 1, Dp, Dp), both bf16, and
    ``br`` (L - 1, Dp) f32 (``wr``, ``br`` None when L == 1); ``hidden``
    the deep ReLU layers' bf16 (hi, lo) weights (Hp_in, Hp_out) and f32
    biases (1, Hp_out), three arrays a layer; ``m_last`` (1, Hp) and
    ``c_last`` (1, 1) f32. ``N`` must be a multiple of ``min(block_n, N)``.
    Returns logits (R, N, 1) f32."""
    r, n, dp = codes.shape
    tn = min(block_n, n)
    if n % tn:
        raise ValueError(f"{n} candidates do not tile by {tn}")
    n_cross = 1 + (0 if wr is None else wr[0].shape[0])
    weights = [e, *w0a] + ([*wr, br] if wr is not None else []) \
        + list(hidden) + [m_last, c_last]
    row = pl.BlockSpec((_SQ, 1, dp), lambda i, j: (i, 0, 0))

    def cand(width):
        return pl.BlockSpec((_SQ, tn, width), lambda i, j: (i, j, 0))

    kernel = functools.partial(_kernel, n_cross, len(hidden) // 3, k0)
    return pl.pallas_call(
        kernel,
        grid=(r, n // tn),
        in_specs=[row, row, cand(dp), cand(g.shape[-1])]
        + [_const_spec(w) for w in weights],
        out_specs=pl.BlockSpec((_SQ, tn, 1), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((r, n, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        name="dcn_cross_q8",
        interpret=interpret_mode(interpret),
    )(x0c, p, codes, g, *weights)
