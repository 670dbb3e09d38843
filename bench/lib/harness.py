"""One run of one cell: build, warm up, measure, read the metrics, check.

Everything that belongs to a configuration, a traffic mix or a metric is a
file found by its name in ``BENCHMARK.json`` (see ``bench.lib.spec``); this
module holds no branch for any cell.
"""
from __future__ import annotations

import gc
import queue
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

from bench.lib import layout as layout_mod
from bench.lib import spec, traffic


@dataclass
class Call:
    """One ``score_batch`` call of the window and the engine counters it
    moved."""

    t0: float
    t1: float
    idx: np.ndarray
    candidates: int
    rows_scored: int
    tail_fields: int
    requests: int
    failed: bool = False


@dataclass
class Run:
    """What the window left behind; every metric reader reads from it."""

    cell: dict
    cfg: dict
    mix: dict
    seconds: float
    reqs: traffic.Requests
    t_start: float = 0.0          # window start (perf_counter)
    t_end: float = 0.0            # last answer back
    setup_s: float = 0.0
    calls: List[Call] = field(default_factory=list)
    sent: Optional[np.ndarray] = None
    done: Optional[np.ndarray] = None
    answers: dict = field(default_factory=dict)
    trace: Optional[dict] = None
    peaks: Optional[dict] = None
    engine: object = None
    compiles_in_window: int = 0
    traffic_s: float = 0.0
    traced_calls: int = 0    # calls inside the traced part of the window
    memo: dict = field(default_factory=dict)   # readers' shared results
    _layouts: Optional[list] = None

    def due_abs(self) -> np.ndarray:
        return self.t_start + self.reqs.due_s

    def layouts(self) -> Optional[list]:
        """Per call ``(nb, unique_rows, spans)`` from ``bench.lib.layout``,
        or ``None`` where it disagrees with the engine's ``rows_scored``."""
        if self._layouts is None:
            eng = self.engine
            out = []
            for c in self.calls:
                lay = layout_mod.call_layout(
                    [self.reqs.get(i) for i in c.idx],
                    min_bucket=eng.plan.min_bucket, workers=eng.parallel,
                    dedup=eng.dedup)
                if lay[1] != c.rows_scored:
                    print(f"layout: {lay[1]} unique rows, engine scored "
                          f"{c.rows_scored}; shape metrics left out",
                          file=sys.stderr)
                    self._layouts = []
                    return None
                out.append(lay)
            self._layouts = out
        return self._layouts or None


def _counters(engine):
    s = engine.stats
    return s.rows_scored, s.ctx_tail_fields, s.requests


def _score_call(run: Run, score, idx: np.ndarray):
    import jax

    with jax.profiler.TraceAnnotation("bench.dispatch"):
        batch = [run.reqs.get(int(i)) for i in idx]
    c0 = _counters(run.engine)
    t0 = time.perf_counter()
    failed = False
    try:
        with jax.profiler.TraceAnnotation("bench.score_batch"):
            out = score(batch)
    except Exception as e:  # a failed call counts against its requests
        print(f"score_batch failed: {type(e).__name__}: {e}", file=sys.stderr)
        out, failed = None, True
    t1 = time.perf_counter()
    c1 = _counters(run.engine)
    if out is not None:
        for i, o in zip(idx, out):
            run.answers[int(i)] = o
        run.done[idx] = t1
    run.calls.append(Call(t0, t1, idx, sum(len(b[2]) for b in batch),
                          c1[0] - c0[0], c1[1] - c0[1], c1[2] - c0[2],
                          failed))


def open_loop(run: Run, score, tick) -> None:
    """Arrivals on the mix's schedule from a generator thread; one
    dispatcher hands everything queued, up to ``max_batch``, to one call."""
    import jax

    n, cap = len(run.reqs), int(run.mix["max_batch"])
    q: "queue.SimpleQueue" = queue.SimpleQueue()
    due = run.due_abs()

    def generate():
        for i in range(n):
            delay = due[i] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            with jax.profiler.TraceAnnotation("bench.gen"):
                run.sent[i] = time.perf_counter()
                q.put(i)
        q.put(None)

    gen = threading.Thread(target=generate, name="bench-generator")
    gen.start()
    try:
        finished = False
        while not finished:
            with jax.profiler.TraceAnnotation("bench.wait"):
                i = q.get()
            if i is None:
                break
            idx = [i]
            while len(idx) < cap:
                try:
                    j = q.get_nowait()
                except queue.Empty:
                    break
                if j is None:
                    finished = True
                    break
                idx.append(j)
            _score_call(run, score, np.asarray(idx))
            tick()
    finally:
        gen.join()
    run.t_end = time.perf_counter()


def max_loop(run: Run, score, tick) -> None:
    """Saturation: the next call of ``max_batch`` requests goes out as the
    previous one returns, until ``seconds`` have passed."""
    n, cap = len(run.reqs), int(run.mix["max_batch"])
    i = 0
    while time.perf_counter() - run.t_start < run.seconds:
        if i + cap > n:
            raise RuntimeError(
                f"max loop: the pool of {n} requests ran out after "
                f"{time.perf_counter() - run.t_start:.1f} s; raise pool_per_s")
        idx = np.arange(i, i + cap)
        run.sent[idx] = time.perf_counter()
        _score_call(run, score, idx)
        tick()
        i += cap
    run.t_end = time.perf_counter()


LOOPS = {"open": open_loop, "max": max_loop}


TRACE_SECONDS = 10.0


class WindowTrace:
    """The profiler over the whole window. The ``bench.window`` span marks
    its first ``TRACE_SECONDS`` for ``bench.lib.trace``, which reads only
    that part: ``tick`` after each call ends the span once that time has
    passed. The profiler stops in ``close``, after the window: stopping it
    takes seconds of host work, which inside the window would stall every
    request due meanwhile."""

    def __init__(self, run: Run, trace_dir: str):
        from bench.lib.trace import profile_options
        import jax

        self.run, self.span = run, None
        jax.profiler.start_trace(trace_dir, profiler_options=profile_options())

    def open(self) -> None:
        import jax

        self.until = self.run.t_start + TRACE_SECONDS
        self.span = jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()

    def tick(self) -> None:
        if self.span is not None and time.perf_counter() >= self.until:
            self.end_span()

    def end_span(self) -> None:
        if self.span is None:
            return
        self.span.__exit__(None, None, None)
        self.span = None
        self.run.traced_calls = len(self.run.calls)

    def close(self) -> None:
        import jax

        self.end_span()
        jax.profiler.stop_trace()


class CompileCounter:
    """Counts backend compiles (``jax.monitoring``) while it is entered."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def _listen(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


def sample_requests(run: Run, seed: int, k: int) -> List[int]:
    """``k`` answered requests drawn from the seed, with the longest one."""
    answered = np.asarray(sorted(run.answers))
    if answered.size == 0:
        return []
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    pick = rng.choice(answered, size=min(k, answered.size), replace=False)
    longest = answered[np.argmax([run.reqs.length(int(i)) for i in answered])]
    return sorted(set(int(i) for i in pick) | {int(longest)})


def feature_rows(reqs: traffic.Requests, ids: List[int]):
    """Full ``(idx, val)`` feature rows of the requests: context broadcast
    over each candidate."""
    idx, val = [], []
    for i in ids:
        ci, cv, ki, kv = reqs.get(i)
        n = ki.shape[0]
        idx.append(np.concatenate([np.broadcast_to(ci, (n, ci.size)), ki], 1))
        val.append(np.concatenate([np.broadcast_to(cv, (n, cv.size)), kv], 1))
    return (np.concatenate(idx).astype(np.int32),
            np.concatenate(val).astype(np.float32))


def check(run: Run, seed: int, reference, checks: dict) -> dict:
    """Compare a seeded sample of the window's answers with the reference.
    Returns ``{name: {"value", "limit"}}`` for every number compared."""
    ids = sample_requests(run, seed, int(checks["sample_requests"]))
    missing = len(run.reqs) - len(run.answers)
    if run.mix["loop"] == "max":  # the pool is made larger than the window
        missing = sum(1 for c in run.calls for i in c.idx
                      if int(i) not in run.answers)
    bad = 0
    got = []
    for i in ids:
        a = np.asarray(run.answers[i])
        if a.shape != (run.reqs.length(i),) or not np.all(np.isfinite(a)):
            bad += 1
            a = np.full(run.reqs.length(i), np.nan, np.float32)
        got.append(a.astype(np.float32))
    idx, val = feature_rows(run.reqs, ids)
    want = reference.logits(run.cfg, seed, idx, val)
    got = np.concatenate(got) if got else np.zeros(0, np.float32)
    dev = float(np.max(np.abs(got - want))) if got.size else float("inf")
    if not np.isfinite(dev):
        dev = float("inf")
    return {
        "missing_answers": {"value": missing + bad, "limit": 0},
        "max_abs_dev": {"value": dev, "limit": float(checks["max_abs_dev"])},
        "compared_predictions": {"value": int(got.size),
                                 "limit": int(checks["min_predictions"])},
    }


def passed(checks: dict) -> bool:
    c = checks
    return (c["missing_answers"]["value"] <= c["missing_answers"]["limit"]
            and c["max_abs_dev"]["value"] <= c["max_abs_dev"]["limit"]
            and c["compared_predictions"]["value"]
            >= c["compared_predictions"]["limit"])


def _peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def prepare(cfg: dict, mix: dict, seed: int):
    """Tables from the seed, the engine over them (both made by the
    configuration's family), and a warmup of every bucket the mix can emit.
    Returns ``(engine, seconds per step)``."""
    family = spec.family(cfg)
    split = {}
    t = time.perf_counter()
    params = family.engine_params(cfg, seed)
    split["tables_s"] = time.perf_counter() - t
    t = time.perf_counter()
    engine = family.build_engine(cfg, params)
    rows = -(-int(mix["max_batch"]) // engine.parallel)
    engine.warmup(max_requests=rows, max_candidates=mix["candidates"]["max"])
    split["warmup_s"] = time.perf_counter() - t
    return engine, split


def window(cell: dict, cfg: dict, mix: dict, engine, seed: int,
           seconds: float, *, score=None, trace_dir: Optional[str] = None,
           t_process: Optional[float] = None) -> Run:
    """Generate the seed's traffic and run the mix's loop for ``seconds``
    (under the profiler when ``trace_dir`` is given)."""
    t = time.perf_counter()
    reqs = traffic.generate(mix, cfg, seed,
                            traffic.request_count(mix, seconds))
    traffic_s = time.perf_counter() - t
    run = Run(cell=cell, cfg=cfg, mix=mix, seconds=seconds, reqs=reqs,
              engine=engine)
    run.sent = np.zeros(len(reqs))
    run.done = np.zeros(len(reqs))
    tracer = WindowTrace(run, trace_dir) if trace_dir else None
    with CompileCounter() as compiles:
        run.t_start = time.perf_counter()
        run.setup_s = run.t_start - (t_process or t)
        if tracer:
            tracer.open()
        LOOPS[mix["loop"]](run, score or engine.score_batch,
                           tracer.tick if tracer else lambda: None)
        if tracer:
            tracer.close()
    run.compiles_in_window = compiles.n
    run.traffic_s = traffic_s
    return run


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, *, t_process: float, cfg: Optional[dict] = None,
             mix: Optional[dict] = None, fault: Optional[Callable] = None, trace_dir: Optional[str] = None,
             peaks: Optional[dict] = None, log=sys.stderr) -> dict:
    """Run one cell and return the result line's dict (without ``device``).

    ``cfg`` and ``mix`` replace the cell's configuration and traffic (the
    tests' small tables and slates);
    ``fault(score_batch) -> score_batch`` wraps the timed call (the tests'
    broken paths); ``peaks`` stands in for the device's row of
    ``bench/peaks.json`` (the tests' CPU).
    """
    import jax

    cell = spec.cell(bench, cell_name)
    cfg = cfg or spec.config(bench, cell["config"])
    mix = mix or traffic.load_mix(cell["traffic"])
    reference = spec.reference(cfg)

    engine, split = prepare(cfg, mix, seed)
    setup_peak = _peak_bytes()
    score = fault(engine.score_batch) if fault else None
    run = window(cell, cfg, mix, engine, seed, seconds, score=score,
                 trace_dir=trace_dir if trace else None, t_process=t_process)
    split["traffic_s"] = run.traffic_s
    print(f"setup: {run.setup_s:.3f} s = tables {split['tables_s']:.3f} + "
          f"warmup {split['warmup_s']:.3f} + traffic {split['traffic_s']:.3f}"
          f" + start {run.setup_s - sum(split.values()):.3f}", file=log)
    print(f"window: {run.t_end - run.t_start:.3f} s, {len(run.calls)} calls, "
          f"{sum(c.candidates for c in run.calls)} predictions, "
          f"{run.compiles_in_window} compiles", file=log)

    result = {"setup_split": split}
    dev = jax.devices()[0]
    # the process's peak: set-up makes the tables a chunk at a time, so
    # what the peak holds is the window's own (its warmup calls alike)
    result["memory_peak_bytes"] = _peak_bytes()
    print(f"device memory peak: {setup_peak} B after set-up, "
          f"{result['memory_peak_bytes']} B after the window", file=log)

    if trace:
        from bench.lib import trace as trace_mod

        events = trace_mod.extract(trace_mod.find_xplane(trace_dir))
        run.trace = trace_mod.reduce(events, spec.kernel_patterns())
        run.peaks = peaks or spec.peaks(dev.device_kind)
    kind = "per_layer" if trace else "end_to_end"
    result["metrics"] = spec.read_metrics(bench, cell_name, kind, run)
    result["trace"] = run.trace
    result["attempted"] = (len(run.reqs) if mix["loop"] == "open"
                           else sum(len(c.idx) for c in run.calls))
    result["failed"] = sum(len(c.idx) for c in run.calls if c.failed)

    # free the program's state before the reference runs on the device
    engine.close()
    run.engine = engine = None
    gc.collect()
    t = time.perf_counter()
    result["checks"] = check(run, seed, reference, cfg["checks"])
    result["correct"] = passed(result["checks"]) and result["failed"] == 0
    print(f"reference: {time.perf_counter() - t:.3f} s", file=log)
    return result
