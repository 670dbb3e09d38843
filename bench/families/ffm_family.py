"""The FFM family: the serving engine's ``ffm`` and ``deepffm`` heads
(arXiv:2407.10115 §2.1), as a configuration names them by its ``head`` key.

What the harness, the calibration and the reference need of a head, found
by the configuration's ``reference`` name (``bench.lib.spec.family``):

  engine_params(cfg, seed)     the engine's params pytree for the seed
  build_engine(cfg, params)    the engine under test over those params
  flops_per_prediction(cfg)    model FLOPs of one prediction
  table_rows, head_params      the seeded rows and head the reference reads

The tables are ``bench.lib.weights``' rows of ``F * k`` codes, one row per
hashed feature holding its ``F`` field-aware embeddings of width ``k``.
The head takes stream ids 7-9 and 16 on.
"""
from __future__ import annotations

import numpy as np

from bench.lib import weights

HEAD_STREAMS = {"lr_b": 7, "merge_scale": 8, "merge_bias": 9, "mlp": 16}

# the last MLP layer's scale on top of its fan-in scale: the MLP adds a
# residual of a trained model's size to the wide logit
MLP_OUT_SCALE = 0.25


def mlp_dims(cfg: dict):
    d_in = cfg["n_fields"] * (cfg["n_fields"] - 1) // 2 + 1
    return (d_in,) + tuple(cfg["mlp_hidden"]) + (1,)


def head_params(cfg: dict, seed: int) -> dict:
    """LR bias and, for the deepffm head, MergeNorm and MLP weights.

    MLP layer i is uniform with a fan-in scale (a power of two near
    ``1/sqrt(fan_in)``); the last layer is further scaled by ``MLP_OUT_SCALE``.
    """
    def leaf(stream, shape, scale, offset=0.0):
        return weights.head_tensor(seed, stream, shape, scale, offset)

    out = {"lr_b": leaf(HEAD_STREAMS["lr_b"], (), 2.0 ** -3)}
    if cfg["head"] != "deepffm":
        return out
    dims = mlp_dims(cfg)
    out["merge_scale"] = leaf(HEAD_STREAMS["merge_scale"], (dims[0],),
                              2.0 ** -3, 1.0)
    out["merge_bias"] = leaf(HEAD_STREAMS["merge_bias"], (dims[0],),
                             2.0 ** -3)
    mlp = {}
    for i in range(len(dims) - 1):
        fan = 2.0 ** -round(np.log2(np.sqrt(dims[i])))
        if i == len(dims) - 2:
            fan *= MLP_OUT_SCALE
        mlp[f"w{i}"] = leaf(HEAD_STREAMS["mlp"] + 2 * i,
                            (dims[i], dims[i + 1]), fan)
        mlp[f"b{i}"] = leaf(HEAD_STREAMS["mlp"] + 2 * i + 1, (dims[i + 1],),
                            2.0 ** -4)
    out["mlp"] = mlp
    return out


def table_rows(cfg: dict, seed: int, rows, xp=np):
    """Dequantized embedding rows ``(len(rows), F, k)`` and LR weights
    ``(len(rows),)`` of the seed's tables, in float32."""
    f, k = cfg["n_fields"], cfg["k"]
    emb, lr = weights.table_rows(seed, rows, f * k, xp)
    return emb.reshape(-1, f, k), lr


def engine_params(cfg: dict, seed: int) -> dict:
    """The serving engine's params pytree for the seed: int8 tables made on
    the device and held as host numpy arrays, the form the engine's update
    pipe publishes (so every scoring call passes them as it would in
    service), plus the float32 head."""
    v, f, k = cfg["hash_space"], cfg["n_fields"], cfg["k"]
    t = weights.make_tables(seed, v, f * k)
    head = head_params(cfg, seed)
    params = {
        "lr": {"w": {"codes": t["lr_codes"], "scale": t["lr_scale"],
                     "zero": t["lr_zero"], "block": weights.LR_BLOCK},
               "b": head["lr_b"]},
        "ffm": {"emb": {"codes": t["codes"].reshape(v, f, k),
                        "scale": t["scale"], "zero": t["zero"]}},
    }
    if cfg["head"] == "deepffm":
        params["merge_scale"] = head["merge_scale"]
        params["merge_bias"] = head["merge_bias"]
        params["mlp"] = head["mlp"]
    return params


def build_engine(cfg: dict, params):
    from repro.common.config import FFMConfig
    from repro.serving.engine import InferenceEngine

    sizes = {key: cfg[key] for key in
             ("n_fields", "context_fields", "hash_space", "k")}
    if "mlp_hidden" in cfg:  # the deepffm head's MLP
        sizes["mlp_hidden"] = tuple(cfg["mlp_hidden"])
    fcfg = FFMConfig(**sizes)
    return InferenceEngine(fcfg, cfg["head"], params=params, **cfg["engine"])


def flops_per_prediction(cfg: dict) -> int:
    """Model FLOPs of one prediction (multiply and add count one each; ReLU
    and comparisons count nothing).

        LR        F products with values, F adds (bias included)     2F
        pairs     P = F(F-1)/2 dots of k, each times two values      P(2k + 2)
        head sum  P adds (pairs summed, LR added)                    P
      deepffm adds, over d = P + 1 inputs:
        MergeNorm mean d, variance 3d, normalize 2d + 1, affine 2d   7d + 1
        MLP       per layer 2 * in * out + out                       sum
        shortcut  one add                                            1
    """
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
