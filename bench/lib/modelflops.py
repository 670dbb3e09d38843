"""Model FLOPs of one prediction of an FFM-family head (multiply and add
count one each; ReLU and comparisons count nothing).

    LR        F products with values, F adds (bias included)       2F
    pairs     P = F(F-1)/2 dots of k, each times two values        P(2k + 2)
    head sum  P adds (pairs summed, LR added)                      P
  deepffm adds, over d = P + 1 inputs:
    MergeNorm mean d, variance 3d, normalize 2d + 1, affine 2d     7d + 1
    MLP       per layer 2 * in * out + out                         sum
    shortcut  one add                                              1
"""


def per_prediction(cfg: dict) -> int:
    f, k = cfg["n_fields"], cfg["k"]
    p = f * (f - 1) // 2
    total = 2 * f + p * (2 * k + 2) + p
    if cfg["head"] == "deepffm":
        d = p + 1
        dims = [d] + list(cfg["mlp_hidden"]) + [1]
        total += 7 * d + 1
        total += sum(2 * a * b + b for a, b in zip(dims, dims[1:]))
        total += 1
    return total
