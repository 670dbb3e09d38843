"""Operations and HBM bytes of one ``ffm_fused_logits_q8`` call.

The fused scoring path's Pallas kernel (``kernels/ffm_interaction``): per
request row the full-depth context block in and its ctx pair matrix out;
per candidate its int8 code rows, grids, values and base logit in, one logit
out. Counted once per row: the context tail pairs. Per candidate: ctx-cand
dots against float32 codes with the affine correction, and cand-cand dots
in int8 x int8 -> int32 (the int8 work) with their affine recombination.
"""

TRACE_NAMES = ("%fused_candidate_logits_q8", "tpu_custom_call")


def cost(rows: int, cands: int, cfg: dict) -> dict:
    """``rows`` request rows of ``cands`` candidates each (padded shapes)."""
    fc = cfg["context_fields"]
    f = cfg["n_fields"]
    fa = f - fc
    k = cfg["k"]
    per_row = fc * f * k * 4 + 2 * fc * 4 + fc * fc * 4
    per_cand = (4 + fa * fc * k + fa * fa * k          # base, int8 codes
                + 4 * fa * 4 + 2 * fa * 4 + 4)         # grids, values, logit
    row_ops = 2 * fc * fc * k + 2 * fc * fc            # ctx tail pairs
    float_ops = (2 * fc * fa * k + 3 * fc * fa         # ctx-cand dots, affine
                 + 3 * fc * fa                         # values, sum
                 + 10 * fa * fa + 3 * fa * fa + 3)     # cand-cand affine, sum
    int8_ops = 4 * fa * fa * k                         # code dots, row sums
    return {"float_ops": rows * (row_ops + cands * float_ops),
            "int8_ops": rows * cands * int8_ops,
            "bytes": rows * per_row + rows * cands * per_cand}
