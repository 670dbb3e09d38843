"""The per-layer metrics that read the serving engine's own phase clock and
counters (``bench/lib/phases.py``), in each cell's traced CPU window (small
tables, as in ``test_faults``)."""
import math
import time

import pytest

from bench.lib import harness, spec, traffic
from bench.tests import test_faults

BENCH = test_faults.BENCH
CELLS = test_faults.CELLS
PROGRAM_METRICS = ("resolve_ms", "tails_ms", "dedup_ms", "prepare_ms",
                   "pool_wait_ms", "launch_ms", "device_wait_ms",
                   "finish_ms", "host_arg_mb_per_call", "slots_per_pred")


def _suffix(cell):
    return traffic.load_mix(spec.cell(BENCH, cell)["traffic"])["loop"]


def test_every_program_metric_is_declared_for_both_cells():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for cell in CELLS:
        for name in PROGRAM_METRICS:
            m = declared[f"{name}.{_suffix(cell)}"]
            assert m["workloads"] == [cell] and m["better"] == "lower"


@pytest.mark.parametrize("cell", CELLS)
def test_program_metrics_read_in_the_traced_window(cell, tmp_path):
    res = test_faults._run(cell, trace=True, tmp=tmp_path)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    sfx = _suffix(cell)
    for name in PROGRAM_METRICS:
        value = m.get(f"{name}.{sfx}")
        assert value is not None and math.isfinite(value), name
    # every argument is host numpy: the counter agrees with the shapes
    assert m[f"host_arg_mb_per_call.{sfx}"] == pytest.approx(
        m[f"h2d_mb_per_call.{sfx}"], rel=0.01)
    assert m[f"slots_per_pred.{sfx}"] >= m[f"rows_per_pred.{sfx}"]


@pytest.mark.parametrize("cell", CELLS)
def test_slots_scored_matches_the_layout(cell):
    """Per call of a CPU window, the engine's padded-slot counter moves by
    the sum of row bucket x candidate bucket over the call's forward calls
    in ``bench.lib.layout``."""
    cfg, mix = test_faults._small(cell)
    seed = 2 ** 33 + 5
    engine, _ = harness.prepare(cfg, mix, seed)
    moved = []

    def score(batch):
        before = engine.stats.slots_scored
        out = engine.score_batch(batch)
        moved.append(engine.stats.slots_scored - before)
        return out

    try:
        run = harness.window(spec.cell(BENCH, cell), cfg, mix, engine,
                             seed, 1.0, score=score,
                             t_process=time.perf_counter())
        lays = run.layouts()
    finally:
        engine.close()
    assert lays and len(lays) == len(run.calls) == len(moved)
    for slots, (nb, _, spans) in zip(moved, lays):
        assert slots == sum(rb * nb for rb, _ in spans)
