"""The trace -> metric reduction: busy union, idle gaps named by host spans,
per-kernel time; on a hand-made trace and on one recorded on the chip."""
import gzip
import json
import os

import pytest

from bench.lib import spec, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
FUSION = "%fusion.1 = s8[64,24,8] fusion(s8[4096,24,8] %codes, s32[64] %i)"
KERNEL = ("%candidate_interactions_q8.3 = (f32[{},{},16,8], f32[1,8,8,8]) "
          "custom-call(f32[1,16,8,8] %a), custom_call_target=\"tpu_custom_call\"")


def test_union_merges_overlaps():
    assert trace.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]


def test_hand_made_trace():
    # window 100..200 ns; two devices; host spans name the gaps
    ev = {
        "host": [["bench.window", 100, 100], ["bench.score_batch", 100, 60],
                 ["bench.wait", 160, 40]],
        "device": {
            "/device:TPU:0": [[FUSION, 90, 20],             # clipped to 10
                              [KERNEL.format(2, 64), 120, 10],
                              ["%copy.2 = f32[8] copy(f32[8] %x)", 125, 10],
                              [KERNEL.format(1, 128), 180, 5]],
            "/device:TPU:1": [[FUSION, 150, 50]],
        },
    }
    r = trace.reduce(ev, {"ffm_candidate_matrices_q8": (
        "%candidate_interactions_q8", "tpu_custom_call")})
    assert r["window_s"] == pytest.approx(100e-9)
    # device 0: [100,110] + [120,135] + [180,185] = 30; device 1: 50
    assert r["busy_s"] == pytest.approx(40e-9)
    k = r["kernel_s"]["ffm_candidate_matrices_q8"]
    assert k["calls"] == 2 and k["seconds"] == pytest.approx(15e-9)
    assert k["shapes"] == [(2, 64), (1, 128)]
    # longest gaps: dev1 [100,150] under score_batch (50 of it), dev0
    # [135,180] (25 under score_batch, 20 under wait), dev0 [185,200]
    names = [(g[0], round(g[1] * 1e9)) for g in r["idle_gaps"][:3]]
    assert names == [("score_batch", 50), ("score_batch", 45), ("wait", 15)]
    assert r["device_ops"][0] == ["%fusion.1", pytest.approx(70e-9)]


def test_trace_recorded_on_the_chip():
    """A staged-engine window recorded on a TPU v5e (extracted with
    ``trace.extract``): the reduction agrees with a direct count."""
    path = os.path.join(DATA, "staged_v5e.json.gz")
    with gzip.open(path, "rt") as f:
        ev = json.load(f)
    r = trace.reduce(ev, spec.kernel_patterns())
    w0, w1 = trace.window(ev)
    ops = [o for ops in ev["device"].values() for o in ops
           if o[1] + o[2] > w0 and o[1] < w1]
    assert ops and len(ev["device"]) == 1
    total = sum(min(s + d, w1) - max(s, w0) for _, s, d in ops) * 1e-9
    assert 0 < r["busy_s"] <= total + 1e-12
    assert r["busy_s"] <= r["window_s"]
    k = r["kernel_s"]["ffm_candidate_matrices_q8"]
    assert k["calls"] == sum(1 for n, _, _ in ops
                             if n.startswith("%candidate_interactions_q8")
                             and "tpu_custom_call" in n)
    assert k["calls"] > 0 and None not in k["shapes"]
    gaps = sum(g[1] for g in r["idle_gaps"])
    assert gaps <= r["window_s"] - r["busy_s"] + 1e-12
    assert all(g[0] != "none" for g in r["idle_gaps"])
