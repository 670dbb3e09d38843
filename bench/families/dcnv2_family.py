"""The DCN-v2 family: the serving engine's ``dcnv2`` head (arXiv:2008.13535,
stacked; ``repro.core.dcnv2``), as a configuration names it by its ``head``
key.

What the harness, the calibration and the reference need of a head (see
``bench/families/ffm_family.py``):

  engine_params(cfg, seed)     the engine's params pytree for the seed
  build_engine(cfg, params)    the engine under test over those params
  flops_per_prediction(cfg)    model FLOPs of one prediction
  table_rows, head_params      the seeded rows and head the reference reads

The table is ``bench.lib.weights``' rows of ``k`` codes, one per hashed
feature (the LR part the table maker also makes is unused: DCN-v2 has no LR
term). The head takes stream ids 10-13, outside the tables' 1-6 and the FFM
family's 7-9 and 16 on: one stream per group of tensors, each group drawn
as one unit-scale tensor and cut into layers, each layer then scaled by a
power of two (exact in float32).

The scales, for the seed's rows (|e| < 0.25, root mean square about 0.11)
and the ``sessions`` values (1, or log1p of a lognormal):

  cross w_l  2^-2   x_l W_l then has a root mean square near 0.6, so each
                    cross layer moves x_l by about half its size: the
                    products of fields matter to the logit, and |x_l| grows
                    by at most ~1.2x a layer
  cross b_l  2^-4
  mlp w_i    2^-2 for the first layer, 2^-3 after it: pre-activations near
             unit size through the ReLU layers, and logits of a few units,
             a trained ranker's range
  mlp b_i    2^-4
"""
from __future__ import annotations

import numpy as np

from bench.lib import weights

HEAD_STREAMS = {"cross_w": 10, "cross_b": 11, "mlp_w": 12, "mlp_b": 13}
CROSS_W_SCALE = 2.0 ** -2
BIAS_SCALE = 2.0 ** -4
MLP_IN_SCALE = 2.0 ** -2    # the first deep layer, over the D-wide x_L
MLP_SCALE = 2.0 ** -3       # every later deep layer


def width(cfg: dict) -> int:
    return cfg["n_fields"] * cfg["k"]


def mlp_dims(cfg: dict):
    return (width(cfg),) + tuple(cfg["mlp_hidden"]) + (1,)


def head_params(cfg: dict, seed: int) -> dict:
    """Cross and deep weights of the seed, float32 (see the module's table
    of scales)."""
    d, n_cross = width(cfg), cfg["n_cross"]
    cw = weights.head_tensor(seed, HEAD_STREAMS["cross_w"], (n_cross, d, d),
                             CROSS_W_SCALE)
    cb = weights.head_tensor(seed, HEAD_STREAMS["cross_b"], (n_cross, d),
                             BIAS_SCALE)
    cross = {}
    for l in range(n_cross):
        cross[f"w{l}"], cross[f"b{l}"] = cw[l], cb[l]
    dims = mlp_dims(cfg)
    shapes = list(zip(dims, dims[1:]))
    mw = weights.head_tensor(seed, HEAD_STREAMS["mlp_w"],
                             (sum(a * b for a, b in shapes),), 1.0)
    mb = weights.head_tensor(seed, HEAD_STREAMS["mlp_b"], (sum(dims[1:]),),
                             BIAS_SCALE)
    mlp, w0, b0 = {}, 0, 0
    for i, (a, b) in enumerate(shapes):
        scale = np.float32(MLP_IN_SCALE if i == 0 else MLP_SCALE)
        mlp[f"w{i}"] = mw[w0:w0 + a * b].reshape(a, b) * scale
        mlp[f"b{i}"] = mb[b0:b0 + b]
        w0, b0 = w0 + a * b, b0 + b
    return {"cross": cross, "mlp": mlp}


def table_rows(cfg: dict, seed: int, rows, xp=np):
    """Dequantized embedding rows ``(len(rows), k)`` of the seed's table, in
    float32."""
    return weights.table_rows(seed, rows, cfg["k"], xp)[0]


def engine_params(cfg: dict, seed: int) -> dict:
    """The serving engine's params pytree for the seed: the int8 table made
    on the device and held as host numpy arrays, the form the engine
    publishes, plus the float32 cross and deep weights."""
    v, k = cfg["hash_space"], cfg["k"]
    t = weights.make_tables(seed, v, k)
    return {"emb": {"codes": t["codes"].reshape(v, k), "scale": t["scale"],
                    "zero": t["zero"]},
            **head_params(cfg, seed)}


def build_engine(cfg: dict, params):
    from repro.common.config import FFMConfig
    from repro.serving.engine import InferenceEngine

    fcfg = FFMConfig(n_fields=cfg["n_fields"],
                     context_fields=cfg["context_fields"],
                     hash_space=cfg["hash_space"], k=cfg["k"],
                     mlp_hidden=tuple(cfg["mlp_hidden"]),
                     n_cross=cfg["n_cross"])
    return InferenceEngine(fcfg, cfg["head"], params=params, **cfg["engine"])


def flops_per_prediction(cfg: dict) -> int:
    """Model FLOPs of one prediction (multiply and add count one each; ReLU
    counts nothing), over D = F * k:

        x0       one product of a row element with its value        D
        cross    per layer x W (2 D^2), + b, x0 *, + x (3 D)         2D^2 + 3D
        deep     per layer 2 * in * out + out                        sum
    """
    d = width(cfg)
    dims = mlp_dims(cfg)
    return (d + cfg["n_cross"] * (2 * d * d + 3 * d)
            + sum(2 * a * b + b for a, b in zip(dims, dims[1:])))
