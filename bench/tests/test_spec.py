"""Every name in BENCHMARK.json resolves to its files, and the file keeps
the shape the benchmark's readers rely on."""
import json
import os
import re

import pytest

from bench.lib import modelflops, spec, traffic

BENCH = spec.load()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"] for m in BENCH["end_to_end"]}


def _reports(cell, metric):
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_configs_resolve():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        cfg = spec.config(BENCH, c["name"])
        assert cfg["name"] == c["name"]
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert spec.reference(cfg).logits
        assert cfg["flops_per_prediction"] == modelflops.per_prediction(cfg)
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


def test_workloads_resolve():
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        spec.config(BENCH, w["config"])
        traffic.load_mix(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        e2e = [m["name"] for m in BENCH["end_to_end"]
               if _reports(w["name"], m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(_reports(w["name"], m) for m in BENCH["per_layer"])


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metrics_resolve(kind):
    cells = {w["name"] for w in BENCH["workloads"]}
    names = set()
    for m in BENCH[kind]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["name"] not in names
        names.add(m["name"])
        assert m["better"] in ("lower", "higher")
        assert callable(spec.metric_reader(m["name"]).read)
        assert set(m.get("workloads", cells)) <= cells
        if kind == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert m["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
            assert m["moves"] in E2E
            # every cell that lists it reports the metric it moves
            moved = next(e for e in BENCH["end_to_end"]
                         if e["name"] == m["moves"])
            for cell in m["workloads"]:
                assert _reports(cell, moved)


def test_roofline_metrics_name_their_kernel():
    kernels = spec.kernel_names()
    assert kernels
    for m in BENCH["per_layer"]:
        if "_roofline" in m["name"]:
            assert m["name"].split("_roofline")[0] in kernels
            assert m["unit"] == "%"
    for k in kernels:
        mod = spec.kernel(k)
        assert mod.TRACE_NAMES and callable(mod.cost)


def test_layers_are_named_in_perf_md():
    with open(os.path.join(spec.ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in BENCH["per_layer"]:
        assert m["layer"] in perf, m["layer"]


def test_peaks_table():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("cpu")
