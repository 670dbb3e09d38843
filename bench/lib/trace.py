"""From a profiler trace to device busy time, idle gaps and kernel time.

Two steps, kept apart so the second can be checked on a recorded trace:

``extract(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
and keeps what the reduction needs, as plain lists: every device operation
(the ``XLA Ops`` line of each ``/device:`` plane) and every host span whose
name starts with ``bench.`` (the harness's ``TraceAnnotation``s, on the
same clock).

``reduce(events, kernels)`` works on that:

  busy_s      union of the device's operation intervals inside the traced
              window (the ``bench.window`` span), averaged over the devices
  window_s    length of that span
  op_s        seconds per operation, summed (named by its HLO instruction,
              the text before `` = ``)
  kernel_s    per kernel: summed seconds, invocation count and the
              ``(rows, candidates)`` of each invocation (the leading dims of
              its first output) of the operations whose name contains every
              one of the kernel's patterns
  gaps        idle intervals between busy ones, longest first, each named
              by the ``bench.`` host span that overlaps it most
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence

DEVICE_LINES = ("XLA Ops",)
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


_OUT_DIMS = re.compile(r"=\s*\(?\w+\[(\d+),(\d+)")


def out_dims(op: str):
    """``(rows, candidates)``: the two leading dims of an operation's first
    output, as its HLO text in the trace states them."""
    m = _OUT_DIMS.search(op)
    return (int(m.group(1)), int(m.group(2))) if m else None


def profile_options():
    """Device and host activity without the Python function tracer, which
    slows every Python call of the window."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def extract(path: str) -> dict:
    """``{"device": {plane: [[name, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: Dict[str, list] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name in DEVICE_LINES:
                    ops.extend([e.name, float(e.start_ns),
                                float(e.duration_ns)] for e in line.events)
            if ops:
                device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"device": device, "host": host}


def union(intervals: Sequence[Sequence[float]]) -> List[List[float]]:
    """Merge ``[start, end]`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def window(events: dict):
    spans = [h for h in events["host"] if h[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    _, s, d = spans[0]
    return s, s + d


def reduce(events: dict, kernels: Dict[str, Sequence[str]],
           top: int = 10) -> dict:
    w0, w1 = window(events)
    busy, op_s, gaps = [], {}, []
    kernel_s = {k: {"seconds": 0.0, "calls": 0, "shapes": []}
                for k in kernels}
    spans = [(n, s, s + d) for n, s, d in events["host"] if n != WINDOW_SPAN]
    for ops in events["device"].values():
        inside = [(n, s, d) for n, s, d in ops if s + d > w0 and s < w1]
        merged = union(_clip([[s, s + d] for _, s, d in inside], w0, w1))
        busy.append(sum(e - s for s, e in merged))
        for n, s, d in inside:
            short = n.split(" = ", 1)[0]
            op_s[short] = op_s.get(short, 0.0) + d * 1e-9
            for k, pats in kernels.items():
                if all(p in n for p in pats):
                    kernel_s[k]["seconds"] += d * 1e-9
                    kernel_s[k]["calls"] += 1
                    kernel_s[k]["shapes"].append(out_dims(n))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    gaps.sort(key=lambda g: g[0] - g[1])

    def name_gap(s, e):
        best, best_ov = "none", 0.0
        for n, hs, he in spans:
            ov = min(e, he) - max(s, hs)
            if ov > best_ov:
                best, best_ov = n[len(SPAN_PREFIX):], ov
        return best

    n_dev = max(1, len(events["device"]))
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) * 1e-9 / n_dev,
        "devices": len(events["device"]),
        "op_s": op_s,
        "kernel_s": kernel_s,
        "device_ops": sorted(([n, s] for n, s in op_s.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[name_gap(s, e), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
    }
