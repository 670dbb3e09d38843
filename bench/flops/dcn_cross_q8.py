"""Operations and HBM bytes of one ``dcn_cross_q8`` call.

The ``dcnv2`` head's Pallas kernel (``kernels/dcn_cross``): per request row
its context lanes ``x0c`` and layer-0 context projection ``p`` in; per
candidate its int8 codes (``Dp`` lanes), its bfloat16 block of value-scaled
grids (``Kg`` lanes) in and one logit out; the weights once per call (their
blocks stay in VMEM across tiles). Counted as the kernel runs them, at the
padded widths (``Dp`` = F * k, ``Kg`` = 4 * candidate fields and each deep
width, all rounded up to 128 lanes; layer 0 from lane ``K0``, the context
width rounded down to 128): the spread of the grids over lanes (one bfloat16
MXU pass), every cross and deep product as three bfloat16 passes, and the
model's elementwise work. The bfloat16 splits of activations and weights are
not counted. All of it is float work, against the bf16 peak.
"""

TRACE_NAMES = ("%dcn_cross_q8", "tpu_custom_call")
LANES = 128


def _up(n: int) -> int:
    return -(-n // LANES) * LANES


def cost(rows: int, cands: int, cfg: dict) -> dict:
    """``rows`` request rows of ``cands`` candidates each (the kernel's
    output shape, its tile padding included)."""
    fc, f, k = cfg["context_fields"], cfg["n_fields"], cfg["k"]
    fa = f - fc
    dp, k0, kg = _up(f * k), fc * k // LANES * LANES, _up(4 * fa)
    hidden = [_up(h) for h in cfg["mlp_hidden"]]
    n_cross = cfg["n_cross"]
    ops = (2 * kg * 2 * dp                              # grids to lanes
           + 3 * dp                                     # dequantize, + x0c
           + 3 * 2 * (dp - k0) * dp + 3 * dp            # layer 0
           + (n_cross - 1) * (3 * 2 * dp * dp + 3 * dp))  # layers 1 ..
    w_bytes = (kg * 2 * dp * 2 + (dp - k0) * dp * 4      # spread, w0 hi + lo
               + (n_cross - 1) * (dp * dp * 4 + dp * 4))  # w_l hi + lo, b_l
    width = dp
    for h in hidden:
        ops += 3 * 2 * width * h + h                    # product, bias
        w_bytes += width * h * 4 + h * 4
        width = h
    ops += 2 * width + 1                                # output layer
    w_bytes += width * 4 + 4
    per_row = 2 * dp * 4                                # x0c, p
    per_cand = dp + kg * 2 + 4                          # codes, grids, logit
    return {"float_ops": rows * cands * ops, "int8_ops": 0,
            "bytes": w_bytes + rows * per_row + rows * cands * per_cand}
