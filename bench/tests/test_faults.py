"""The check that decides ``correct`` catches a broken timed path, and its
control reads outside every configuration's limit.

Each cell runs here on the CPU through the harness (everything but the
look for a chip) with its own configuration's widths over a 4096-row
table, slates of 8 to 64 candidates and two-second windows: once sound,
then with the timed call broken underneath.
"""
import time

import numpy as np
import pytest

from bench.lib import harness, spec, traffic

BENCH = spec.load()
CELLS = [w["name"] for w in BENCH["workloads"]]
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
             "hbm_bytes_per_s": 1e11}


def _small(cell_name):
    cell = spec.cell(BENCH, cell_name)
    cfg = spec.config(BENCH, cell["config"])
    cfg["hash_space"] = 4096
    cfg["checks"] = {**cfg["checks"], "min_predictions": 64}
    mix = traffic.load_mix(cell["traffic"])
    mix = {**mix, "max_batch": 8, "sessions": 200, "inventory": 2000,
           "candidates": {"median": 24, "sigma": 0.7, "min": 8, "max": 64}}
    if mix["loop"] == "open":
        mix["rate_per_s"] = 30
    else:
        mix["pool_per_s"] = 20000
    return cfg, mix


def answer_altered(score_batch):
    """One answer of every call changed where it is produced: the first
    request's first two candidates get each other's scores."""
    def score(reqs):
        out = [np.array(o) for o in score_batch(reqs)]
        if out[0].size >= 2:
            out[0][[0, 1]] = out[0][[1, 0]]
        return out
    return score


def half_left_out(score_batch):
    """Half of every batch left out: only its first half is scored."""
    def score(reqs):
        return score_batch(reqs[:max(1, len(reqs) // 2)])
    return score


def _run(cell_name, fault=None, seed=2 ** 32 + 17, trace=False, tmp=None):
    cfg, mix = _small(cell_name)
    return harness.run_cell(BENCH, cell_name, seed, 2.0, trace,
                            t_process=time.perf_counter(), cfg=cfg, mix=mix,
                            fault=fault, peaks=CPU_PEAKS,
                            trace_dir=str(tmp) if tmp else None)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tmp_path):
    res = _run(cell, trace=True, tmp=tmp_path)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    c = res["checks"]
    assert c["max_abs_dev"]["value"] < c["max_abs_dev"]["limit"] / 10
    m = res["metrics"]
    # the counters and host spans read on any platform
    loop = traffic.load_mix(spec.cell(BENCH, cell)["traffic"])["loop"]
    assert m[f"batch_ms.{loop}"]["value"] > 0
    assert m[f"rows_per_pred.{loop}"]["value"] <= 1
    assert all(np.isfinite(v["value"]) for v in m.values())


@pytest.mark.parametrize("fault", [answer_altered, half_left_out],
                         ids=["answer_altered", "half_left_out"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_path_is_not_correct(cell, fault):
    res = _run(cell, fault=fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_fails_the_limit(cell):
    """The reference in bfloat16 in the program's place reads outside the
    configuration's limit, on three seeds."""
    import jax.numpy as jnp

    cfg, mix = _small(cell)
    ref = spec.reference(cfg)
    limit = cfg["checks"]["max_abs_dev"]
    for seed in (5, 2 ** 31 + 3, 2 ** 40 + 1):
        reqs = traffic.generate(mix, cfg, seed, 24)
        idx, val = harness.feature_rows(reqs, list(range(24)))
        want = ref.logits(cfg, seed, idx, val)
        low = ref.logits(cfg, seed, idx, val, dtype=jnp.bfloat16)
        assert np.max(np.abs(low - want)) > limit
