"""Dry-run the paper's own model (DeepFFM) on the production mesh.

Answers the title question structurally: how many predictions/second does
the TPU deployment of DeepFFM support, per the same roofline methodology used
for the assigned LLM architectures? The paper's fleet hits >300M/s on CPUs
across data centers; here one v5e pod serves a production-scale DeepFFM
(hash 2^22 x 24 fields x k=8 ~ 806M FFM weights) with the hash table sharded
over the model axis and requests over the data axis.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common.config import PROD_FFM, FFMConfig
from repro.common import counting
from repro.core import deepffm
from repro.launch import hlo_analysis, mesh as mesh_lib, roofline


def _param_shardings(cfg: FFMConfig, mesh, specs, *, replicate: bool = False):
    """Hash-space dims shard over model (training default) or fully
    replicate (serving-fleet pattern: the table is ~3 GB, far under HBM —
    replication removes every lookup collective)."""
    import jax.tree_util as jtu
    from repro.common import pspec

    def one(spec):
        parts = [None] * len(spec.shape)
        if not replicate and spec.shape and spec.shape[0] == cfg.hash_space:
            parts[0] = "model"
        return NamedSharding(mesh, P(*parts))

    return jtu.tree_map(one, specs, is_leaf=pspec.is_spec)


def run_ffm(kind: str = "serve", batch: int = 65536, *,
            multi_pod: bool = False, replicate: bool = False,
            out_dir: str = "experiments/dryrun") -> Dict[str, Any]:
    from repro.common import pspec

    cfg = PROD_FFM
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    data_axes = tuple(n for n in mesh.axis_names if n != "model")
    dp = data_axes if len(data_axes) > 1 else data_axes[0]

    specs = deepffm.param_specs(cfg)
    p_abs = pspec.abstract(specs)
    p_shard = _param_shardings(cfg, mesh, specs, replicate=replicate)

    b_abs = {
        "idx": jax.ShapeDtypeStruct((batch, cfg.n_fields), jnp.int32),
        "val": jax.ShapeDtypeStruct((batch, cfg.n_fields), jnp.float32),
        "label": jax.ShapeDtypeStruct((batch,), jnp.float32),
    }
    # replicated serving uses every chip as a data shard (model axis too)
    req_axes = (dp if not replicate
                else (tuple(mesh.axis_names) if len(mesh.axis_names) > 1
                      else mesh.axis_names[0]))
    b_shard = {k: NamedSharding(mesh, P(req_axes, *([None] * (len(v.shape) - 1))))
               for k, v in b_abs.items()}
    rep = NamedSharding(mesh, P())

    if kind == "serve":
        def step(params, batch_):
            return deepffm.predict_proba(cfg, params, batch_["idx"], batch_["val"])

        jitted = jax.jit(step, in_shardings=(p_shard, b_shard),
                         out_shardings=NamedSharding(mesh, P(req_axes)))
    else:
        def step(params, batch_):
            loss, grads = jax.value_and_grad(
                lambda p: deepffm.loss_fn(cfg, p, batch_))(params)
            new = jax.tree_util.tree_map(lambda p, g: p - 0.05 * g, params, grads)
            return new, loss

        jitted = jax.jit(step, in_shardings=(p_shard, b_shard),
                         out_shardings=(p_shard, rep), donate_argnums=(0,))

    t0 = time.time()
    with mesh:
        compiled = jitted.lower(p_abs, b_abs).compile()
    t_compile = time.time() - t0
    a = hlo_analysis.analyze(compiled.as_text())
    chips = mesh.devices.size
    hw = roofline.TPU_V5E
    t_comp = a["flops_per_device"] / hw["flops_bf16"]
    t_mem = a["bytes_per_device"] / hw["hbm_bw"]
    t_coll = a["collective_bytes_per_device"] / hw["ici_bw"]
    bound = max(t_comp, t_mem, t_coll)
    preds_per_s = batch / max(bound, 1e-12)

    result = dict(
        arch="deepffm-ctr", shape=f"{kind}_{batch}", chips=chips,
        mesh="x".join(f"{mesh.shape[n]}{n}" for n in mesh.axis_names),
        t_compute=t_comp, t_memory=t_mem, t_collective=t_coll,
        bottleneck=max(
            {"compute": t_comp, "memory": t_mem, "collective": t_coll}.items(),
            key=lambda kv: kv[1])[0],
        step_time_bound=bound, predictions_per_s=preds_per_s,
        params=pspec.count(specs), t_compile_s=t_compile, status="ok",
    )
    os.makedirs(out_dir, exist_ok=True)
    tag = (f"deepffm-ctr_{kind}{batch}_{'2pod' if multi_pod else '1pod'}"
           + ("_replicated" if replicate else ""))
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(result, f, indent=1, default=str)
    return result


if __name__ == "__main__":
    import sys

    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
    for kind, batch, repl in (("serve", 65536, False), ("serve", 65536, True),
                              ("train", 8192, False)):
        for mp in (False, True):
            r = run_ffm(kind, batch, multi_pod=mp, replicate=repl)
            print(f"{r['arch']} {r['shape']:14s} {('replicated' if repl else 'sharded'):10s} {r['mesh']:20s} "
                  f"bound={r['step_time_bound']*1e3:.3f}ms "
                  f"bottleneck={r['bottleneck']} "
                  f"preds/s={r['predictions_per_s']:,.0f}", flush=True)
