"""Readings that fix a cell's offered rate and its correctness limit.

Not part of a benchmark run: these are the measurements ``PERF.md`` cites
for the numbers in the traffic and configuration files. Each runs in one
process, so the engine's programs compile (or load from the cache) once.

    python3 bench/calibrate.py sweep --workload <cell> --rates 40,80,160 \\
        --seconds 8 --seed <n>
        Offers the cell's open-loop mix at each rate and prints, per rate,
        the completion rate, p50/p99 latency, and whether latency grew over
        the window (a backlog): the highest rate without one is the knee.

    python3 bench/calibrate.py readings --workload <cell> --seeds 1,2,3 \\
        --seconds 4
        Per seed: new tables, the cell's traffic for ``--seconds``, and the
        largest gap of a sampled answer from the reference (the program's
        reading) and of the bfloat16 reference from the float32 one (the
        control's reading), at the cell's own sample size.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _setup(workload: str, seed: int):
    import jax

    from bench.lib import harness, spec, traffic
    from repro.common.compile_cache import setup_compile_cache

    if jax.devices()[0].platform != "tpu":
        sys.exit(f"calibrate: no TPU ({jax.devices()[0]})")
    setup_compile_cache()
    bench = spec.load()
    cell = spec.cell(bench, workload)
    cfg = spec.config(bench, cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    engine, split = harness.prepare(cfg, mix, seed)
    print(f"setup: {split}", flush=True)
    return harness, spec, cell, cfg, mix, engine


def sweep(args) -> None:
    import numpy as np

    harness, _, cell, cfg, mix, engine = _setup(args.workload, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        m = {**mix, "loop": "open", "rate_per_s": rate}
        run = harness.window(cell, cfg, m, engine, args.seed, args.seconds)
        lat = run.done - run.due_abs()
        q = len(lat) // 4
        span = run.t_end - run.t_start
        row = {"rate_per_s": rate, "requests": len(lat),
               "completed_per_s": len(lat) / span,
               "p50_ms": float(np.percentile(lat, 50)) * 1e3,
               "p99_ms": float(np.percentile(lat, 99)) * 1e3,
               "first_quarter_p50_ms": float(np.median(lat[:q])) * 1e3,
               "last_quarter_p50_ms": float(np.median(lat[-q:])) * 1e3,
               "calls": len(run.calls),
               "mean_batch": float(np.mean([len(c.idx) for c in run.calls])),
               "compiles": run.compiles_in_window}
        print("sweep " + json.dumps(row), flush=True)


def readings(args) -> None:
    import jax.numpy as jnp
    import numpy as np

    from bench.lib import weights

    seeds = [int(s) for s in args.seeds.split(",")]
    harness, spec, cell, cfg, mix, engine = _setup(args.workload, seeds[0])
    reference = spec.reference(cfg)
    prog, ctrl = [], []
    for i, seed in enumerate(seeds):
        if i:
            engine.install_params(weights.engine_params(cfg, seed))
        run = harness.window(cell, cfg, mix, engine, seed, args.seconds)
        ids = harness.sample_requests(run, seed,
                                      int(cfg["checks"]["sample_requests"]))
        idx, val = harness.feature_rows(run.reqs, ids)
        got = np.concatenate([np.asarray(run.answers[j], np.float32)
                              for j in ids])
        want = reference.logits(cfg, seed, idx, val)
        low = reference.logits(cfg, seed, idx, val, dtype=jnp.bfloat16)
        p = float(np.max(np.abs(got - want)))
        c = float(np.max(np.abs(low - want)))
        prog.append(p)
        ctrl.append(c)
        print("reading " + json.dumps({"seed": seed, "program": p,
                                       "control": c, "compared": int(got.size),
                                       "calls": len(run.calls)}), flush=True)
    print("readings " + json.dumps({"program_max": max(prog),
                                    "control_min": min(ctrl),
                                    "seeds": len(seeds)}), flush=True)
    engine.close()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="mode", required=True)
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--rates", required=True)
    s.add_argument("--seconds", type=float, default=8.0)
    s.add_argument("--seed", type=int, default=1)
    r = sub.add_parser("readings")
    r.add_argument("--workload", required=True)
    r.add_argument("--seeds", required=True)
    r.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args()
    {"sweep": sweep, "readings": readings}[args.mode](args)


if __name__ == "__main__":
    main()
