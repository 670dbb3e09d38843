"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are the entries of
``BENCHMARK.json`` at the repository root and the files their names lead to
(``bench.lib.spec``). One process per run: it makes the tables from the
seed, warms every bucket the mix can emit, measures for ``--seconds``,
checks a seeded sample of the answers against the plain reference, and
prints one JSON object as its last line of standard output. With
``--trace 1`` the window runs under the JAX profiler and the line carries
the per-layer metrics, the device's busy seconds and a breakdown.

It exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for. JAX's persistent compilation cache lives in
the checkout (``repro.common.compile_cache``), so only a cell's first run
there compiles.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def device_info(chips: int) -> dict:
    """The devices as JAX reports them; exits unless there are ``chips``
    TPUs."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" or info["count"] < chips:
        sys.exit(f"bench: needs {chips} TPU chip(s), JAX reports {info}")
    return info


def limits_line(checks: dict) -> str:
    return " ".join(f"{k}={v['value']} (limit {v['limit']})"
                    for k, v in checks.items())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.lib import harness, spec
    from repro.common.compile_cache import setup_compile_cache

    bench = spec.load()
    cell = spec.cell(bench, args.workload)
    info = device_info(int(cell["chips"]))
    setup_compile_cache()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        res = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                               bool(args.trace), t_process=T_PROCESS,
                               trace_dir=trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    device = {**info, "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"],
            "device": device}
    if args.trace:
        tr = res["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = res["checks"]
    print(f"checks: {limits_line(res['checks'])}", file=sys.stderr,
          flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
