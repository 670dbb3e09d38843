"""Operations and HBM bytes of one ``ffm_candidate_matrices_q8`` call.

The staged scoring path's Pallas kernel (``kernels/ffm_interaction``): per
request row a cached context block, per candidate its int8 code rows, a
``(scale, zero)`` per candidate row and its values in; the ctx-cand and
cand-cand dot matrices out. Codes are dequantized to float32 in the kernel,
so all its arithmetic is float work.
"""

TRACE_NAMES = ("%candidate_interactions_q8", "tpu_custom_call")


def cost(rows: int, cands: int, cfg: dict) -> dict:
    """``rows`` request rows of ``cands`` candidates each (padded shapes)."""
    fc = cfg["context_fields"]
    fa = cfg["n_fields"] - fc
    k = cfg["k"]
    per_row = fc * fa * k * 4 + fc * 4                 # ctx block, ctx values
    per_cand = (fa * fc * k + fa * fa * k              # int8 code rows
                + 2 * fa * 4 + 2 * fa * 4              # scale, zero, values
                + fc * fa * 4 + fa * fa * 4)           # xc, aa out
    ops = (2 * fa * (fc + fa) * k                      # dequantize
           + 2 * fc * fa * k + 2 * fc * fa             # ctx-cand dots, values
           + 2 * fa * fa * k + 2 * fa * fa)            # cand-cand dots, values
    return {"float_ops": rows * cands * ops, "int8_ops": 0,
            "bytes": rows * per_row + rows * cands * per_cand}
