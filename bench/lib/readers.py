"""The arithmetic of the metric readers (``bench/metrics/<name>.py`` pick
one of these). Each takes the finished ``harness.Run`` and returns a number,
or ``None`` where the run holds nothing to read."""
from __future__ import annotations

import sys

import numpy as np

from bench.lib import spec


def _ok_calls(run):
    return [c for c in run.calls if not c.failed]


# -- end to end (host clock) -------------------------------------------------

def p99_ms(run):
    """99th percentile over all requests of the window, each timed from its
    due time in the open-loop schedule to its scores back on the host."""
    if run.mix["loop"] != "open" or not len(run.reqs):
        return None
    lat = np.where(run.done > 0, run.done - run.due_abs(), np.inf)
    return float(np.percentile(lat, 99)) * 1e3


def preds_per_s(run):
    """Candidates scored over the window's seconds (first call out to last
    answer back)."""
    cands = sum(c.candidates for c in _ok_calls(run))
    span = run.t_end - run.t_start
    return cands / span if cands and span > 0 else None


def setup_s(run):
    """Process start to the first timed request: imports, tables, compiles
    or compile-cache reads, warmup calls, traffic generation."""
    return run.setup_s


# -- per layer ----------------------------------------------------------------

def gen_lag_ms(run):
    """99th percentile of how late the generator handed requests over."""
    if run.mix["loop"] != "open" or not len(run.reqs):
        return None
    return float(np.percentile(run.sent - run.due_abs(), 99)) * 1e3


def batch_ms(run):
    calls = _ok_calls(run)
    return float(np.mean([c.t1 - c.t0 for c in calls])) * 1e3 if calls else None


def ctx_fields_per_req(run):
    calls = _ok_calls(run)
    n = sum(c.requests for c in calls)
    return sum(c.tail_fields for c in calls) / n if n else None


def rows_per_pred(run):
    calls = _ok_calls(run)
    n = sum(c.candidates for c in calls)
    return sum(c.rows_scored for c in calls) / n if n else None


def _arg_bytes(run, rb: int, nb: int) -> int:
    """Bytes of host arrays the deployed forward takes at one bucket (all of
    them cross to the device on each forward call)."""
    import jax

    key = ("arg_bytes", rb, nb)
    if key not in run.memo:
        info = run.engine.lower_candidates_forward(rb, nb).args_info
        run.memo[key] = int(sum(
            int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
            for a in jax.tree_util.tree_leaves(info)))
    return run.memo[key]


def h2d_mb_per_call(run):
    lays = run.layouts()
    if not lays:
        return None
    per_call = [sum(_arg_bytes(run, rb, nb) for rb, _ in spans)
                for nb, _, spans in lays]
    return float(np.mean(per_call)) / 1e6


def fwd_device_ms(run):
    """Device busy time in the traced part of the window per call there."""
    if run.trace is None or not run.traced_calls:
        return None
    return run.trace["busy_s"] * 1e3 / run.traced_calls


def device_idle_share(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def kernel_roofline(run, kernel: str):
    """Least time the chip could take for the kernel's invocations in the
    window (operations over peak, or bytes over HBM bandwidth, whichever is
    larger) over the kernel's summed time in the trace, in percent. Each
    invocation's shape is read from its operation in the trace."""
    if run.trace is None:
        return None
    seen = run.trace["kernel_s"].get(kernel)
    if not seen or not seen["calls"] or None in seen["shapes"]:
        return None
    mod = spec.kernel(kernel)
    tot = {"float_ops": 0, "int8_ops": 0, "bytes": 0}
    for rows, cands in seen["shapes"]:
        for key, v in mod.cost(rows, cands, run.cfg).items():
            tot[key] += v
    pk = run.peaks
    compute_s = (tot["float_ops"] / pk["bf16_flops_per_s"]
                 + tot["int8_ops"] / pk["int8_ops_per_s"])
    memory_s = tot["bytes"] / pk["hbm_bytes_per_s"]
    bound = "compute" if compute_s >= memory_s else "memory"
    print(f"{kernel}: {seen['calls']} calls, {seen['seconds']:.6f} s traced, "
          f"{tot['float_ops'] + tot['int8_ops']:.4e} ops, "
          f"{tot['bytes']:.4e} bytes, {bound} bound", file=sys.stderr)
    return 100.0 * max(compute_s, memory_s) / seen["seconds"]


def mfu_per_call(run):
    """Mean over calls of the model FLOPs of the call's predictions over
    (its wall time x the bf16 peak), in percent."""
    calls = _ok_calls(run)
    if run.peaks is None or not calls:
        return None
    fpp = run.cfg["flops_per_prediction"]
    peak = run.peaks["bf16_flops_per_s"]
    return 100.0 * float(np.mean([c.candidates * fpp / ((c.t1 - c.t0) * peak)
                                  for c in calls]))


def mfu_rate(run):
    """Model FLOPs per prediction x predictions per second over the bf16
    peak, in percent."""
    rate = preds_per_s(run)
    if run.peaks is None or rate is None:
        return None
    return (100.0 * run.cfg["flops_per_prediction"] * rate
            / run.peaks["bf16_flops_per_s"])
