# One function per paper table. Print ``name,us_per_call,derived`` CSV.
from __future__ import annotations

import argparse
import json
import sys
import traceback

from benchmarks._util import print_rows
from repro.common.compile_cache import setup_compile_cache

BENCHES = (
    ("table1_stability", "benchmarks.bench_stability"),
    ("table2_hogwild", "benchmarks.bench_hogwild"),
    ("table3_sparse_updates", "benchmarks.bench_sparse_updates"),
    ("table4_quantization", "benchmarks.bench_quantization"),
    ("fig4_context_cache", "benchmarks.bench_context_cache"),
    ("serving_engine", "benchmarks.bench_serving_engine"),
    ("training_pipeline", "benchmarks.bench_training_pipeline"),
    ("fig5_simd", "benchmarks.bench_simd"),
    ("fig6_patcher", "benchmarks.bench_patcher"),
    ("sec4.1_prefetch", "benchmarks.bench_prefetch"),
)


# fast CI smoke (implies --quick)
SMOKE = ("serving_engine", "training_pipeline")


def check_scenarios(mod) -> list:
    """A bench module may declare ``BENCH_FILE`` + ``SCENARIOS`` (top-level
    JSON keys it promises to write). Return the names missing from the file
    it just wrote — a scenario that silently stopped being written would
    otherwise leave a stale artifact claiming coverage it no longer has."""
    bench_file = getattr(mod, "BENCH_FILE", None)
    scenarios = getattr(mod, "SCENARIOS", ())
    if not bench_file or not scenarios:
        return []
    try:
        with open(bench_file) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return list(scenarios)
    return [s for s in scenarios if s not in data]


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", help="substring filter on bench name")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke check: run only the serving bench, quick")
    args = ap.parse_args()
    if args.smoke:
        args.quick = True

    failures = 0
    print("name,us_per_call,derived")
    if args.smoke:
        # PR 10 gate: the invariant linter (lock discipline, trace purity,
        # thread hygiene, jit-cache hygiene) must be clean before the bench
        # numbers mean anything — a silently-broken contract can produce
        # fast-but-wrong results (e.g. a device array re-keying a jit cache)
        from repro.analysis import run_lint

        violations = run_lint()
        for v in violations:
            print(f"analysis,0,FAILED: {v}")
        if violations:
            failures += 1
    for name, module in BENCHES:
        if args.smoke and name not in SMOKE:
            continue
        if args.only and args.only not in name:
            continue
        try:
            import importlib

            mod = importlib.import_module(module)
            rows = mod.run(quick=args.quick)
            print_rows(rows)
            missing = check_scenarios(mod)
            if missing:
                failures += 1
                print(f"{name},0,FAILED: scenarios missing from "
                      f"{mod.BENCH_FILE}: {missing}")
        except Exception:
            failures += 1
            print(f"{name},0,FAILED: {traceback.format_exc(limit=3)}".replace("\n", " "))
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
