"""``BENCHMARK.json`` and the files its names lead to.

  configuration ``c``   the file the entry names (``bench/configs/c.json``)
  its model family      ``bench/families/<reference>.py``: ``engine_params``,
                        ``build_engine``, ``flops_per_prediction`` and the
                        seeded rows and head its reference reads
  its reference         ``bench/references/<reference>.py``
  traffic mix ``t``     ``bench/traffic/t.json`` (``bench.lib.traffic``)
  metric ``m``          ``bench/metrics/m.py``, whose ``read(run)`` returns
                        the value or ``None`` where it finds nothing to read
  kernel ``k``          ``bench/flops/k.py``: ``TRACE_NAMES`` (patterns of
                        its operation names in the device trace) and
                        ``cost(rows, cands, cfg)`` (operations and bytes of
                        one invocation)
  peaks                 ``bench/peaks.json``, keyed by ``device_kind``
"""
from __future__ import annotations

import functools
import glob
import importlib.util
import json
import os
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
FAMILIES_DIR = os.path.join(BENCH_DIR, "families")
REFERENCES_DIR = os.path.join(BENCH_DIR, "references")


def load(path: Optional[str] = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    return _by_name(bench["workloads"], name, "workload")


def config(bench: dict, name: str) -> dict:
    entry = _by_name(bench["configs"], name, "configuration")
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def _module(directory: str, name: str, kind: str):
    """``<directory>/<name>.py``, loaded once."""
    s = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_"),
        os.path.join(directory, f"{name}.py"))
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def family(cfg: dict):
    """The configuration's model family: everything head-specific."""
    return _module(FAMILIES_DIR, cfg["reference"], "family")


def reference(cfg: dict):
    return _module(REFERENCES_DIR, cfg["reference"], "reference")


def metric_reader(name: str):
    return _module(os.path.join(BENCH_DIR, "metrics"), name, "metric")


def kernel(name: str):
    return _module(os.path.join(BENCH_DIR, "flops"), name, "flops")


def kernel_names():
    return sorted(os.path.basename(p)[:-3] for p in
                  glob.glob(os.path.join(BENCH_DIR, "flops", "*.py"))
                  if not os.path.basename(p).startswith("_"))


def kernel_patterns() -> dict:
    return {k: tuple(kernel(k).TRACE_NAMES) for k in kernel_names()}


def peaks(device_kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]


def metrics_of(bench: dict, cell_name: str, kind: str):
    """The ``kind`` ("end_to_end" or "per_layer") metrics a cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def read_metrics(bench: dict, cell_name: str, kind: str, run) -> dict:
    out = {}
    for m in metrics_of(bench, cell_name, kind):
        value = metric_reader(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
