"""The serving engine's profiler spans and its host-transfer counters.

Every ``score_batch`` call opens ``serve.*`` spans (``jax.profiler``
``TraceAnnotation``s) that share the call's batch id and whose wall time
adds up in ``ServeStats.phase_s``, and counts the host bytes it hands to the
jitted forwards and the padded slots they compute
(``ServeStats.host_arg_bytes`` / ``slots_scored``).
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import FFMConfig
from repro.core import deepffm
from repro.serving.engine import InferenceEngine, ServeStats, host_arg_nbytes

CFG = FFMConfig(n_fields=12, context_fields=8, hash_space=2**12, k=4,
                mlp_hidden=(16,))
FC, FCAND = CFG.context_fields, CFG.n_fields - CFG.context_fields
SPANS = ("serve.score_batch", "serve.resolve", "serve.tails", "serve.dedup",
         "serve.prepare", "serve.pool_wait", "serve.launch",
         "serve.device_wait", "serve.finish")


def _params(model, seed=0):
    params = deepffm.init_params(CFG, jax.random.PRNGKey(seed), model)
    return jax.tree_util.tree_map(np.asarray, params)


def _batch(rng, n_req=6, n_cand=24):
    return [(rng.integers(0, CFG.hash_space, FC).astype(np.int32),
             rng.normal(1, 0.25, FC).astype(np.float32),
             rng.integers(0, CFG.hash_space, (n_cand, FCAND)).astype(np.int32),
             rng.normal(1, 0.25, (n_cand, FCAND)).astype(np.float32))
            for _ in range(n_req)]


ENGINES = {
    "staged": dict(model="deepffm", backend="reference", quantized=False),
    "fused": dict(model="ffm", backend="pallas", quantized=True, fused=True),
}


def _engine(kind, **kw):
    spec = dict(ENGINES[kind])
    model = spec.pop("model")
    return InferenceEngine(CFG, model, params=_params(model),
                           warmup_buckets=(8, 32), **spec, **kw)


def _program_spans(trace_dir):
    """``[(name, start, end, line, call)]`` of the ``serve.*`` host events."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    out, line_no = [], 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    call = dict(e.stats).get("call")
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, line_no, call))
            line_no += 1
    return out


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_spans_cover_each_call_under_the_profiler(kind, tmp_path):
    eng = _engine(kind, parallel=2)
    rng = np.random.default_rng(3)
    batches = [_batch(rng) for _ in range(3)]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for reqs in batches:
            eng.score_batch(reqs)
    finally:
        jax.profiler.stop_trace()
        eng.close()
    spans = _program_spans(str(tmp_path))
    assert {s[0] for s in spans} == set(SPANS)

    batch = {s[4]: s for s in spans if s[0] == "serve.score_batch"}
    assert len(batch) == len(batches)
    caller_lines = {s[3] for s in batch.values()}
    for name, t0, t1, line, call in spans:
        assert call in batch, (name, call)  # every span names its call
        if line in caller_lines:  # children lie inside their parent
            _, p0, p1, p_line, _ = batch[call]
            assert line == p_line and p0 <= t0 <= t1 <= p1, name
    resolves = [s for s in spans if s[0] == "serve.resolve"]
    for _, t0, t1, line, call in (s for s in spans
                                  if s[0] == "serve.tails"):
        assert any(r[4] == call and r[3] == line and r[1] <= t0 <= t1 <= r[2]
                   for r in resolves)
    pool_lines = {s[3] for s in spans if s[0] == "serve.prepare"}
    assert pool_lines - caller_lines  # prepared on a pool thread

    # the engine's own phase clock times the same spans
    phase_s = eng.stats.phase_s
    assert set(phase_s) == set(SPANS)
    for name in SPANS:
        traced = 1e-9 * sum(t1 - t0 for n, t0, t1, _, _ in spans
                            if n == name)
        assert phase_s[name] == pytest.approx(traced, rel=0.1, abs=5e-3), name
    inside = ("serve.resolve", "serve.dedup", "serve.pool_wait",
              "serve.launch", "serve.device_wait", "serve.finish")
    assert sum(phase_s[n] for n in inside) <= phase_s["serve.score_batch"]
    assert phase_s["serve.tails"] <= phase_s["serve.resolve"]


def _spy_forward_args(eng):
    """Record the argument tuple of every forward call the engine builds."""
    seen = []
    build = eng._forward_args

    def spy(*a, **kw):
        fn, args = build(*a, **kw)
        seen.append(args)
        return fn, args

    eng._forward_args = spy
    return seen


def _numpy_bytes(args):
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(args)
               if isinstance(x, (np.ndarray, np.generic)))


@pytest.mark.parametrize("host_gather", [False, True],
                         ids=["in_trace", "host_gather"])
def test_host_arg_bytes_counts_the_numpy_arguments(host_gather):
    """In-trace gather: the table is an argument of every forward call and
    is counted each time. Host pre-gather: only the gathered blocks are."""
    params = _params("deepffm")
    eng = InferenceEngine(CFG, "deepffm", params=params, quantized=True,
                          host_gather=host_gather, parallel=2,
                          warmup_buckets=(8, 32))
    seen = _spy_forward_args(eng)
    eng.score_batch(_batch(np.random.default_rng(5)))
    eng.close()
    assert len(seen) == 2  # the batch split across both workers
    table = sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(
        eng.params["ffm"]["emb"]))
    assert eng.stats.host_arg_bytes == sum(_numpy_bytes(a) for a in seen)
    if host_gather:
        assert eng.stats.host_arg_bytes < table
    else:
        assert eng.stats.host_arg_bytes > len(seen) * table


def test_device_resident_table_counts_zero():
    params = _params("ffm")
    on_device = dict(params, ffm={"emb": jnp.asarray(params["ffm"]["emb"])})
    counts = {}
    for name, p in (("host", params), ("device", on_device)):
        eng = InferenceEngine(CFG, "ffm", params=p, host_gather=False,
                              parallel=1, warmup_buckets=(8, 32))
        eng.score_batch(_batch(np.random.default_rng(7)))
        counts[name] = eng.stats.host_arg_bytes
        eng.close()
    assert counts["host"] - counts["device"] == params["ffm"]["emb"].nbytes
    assert host_arg_nbytes((np.zeros(3, np.float32), np.float32(1),
                            jnp.zeros(5), "ffm", CFG)) == 16


def test_slots_scored_counts_padded_slots_and_merges():
    eng = _engine("fused", parallel=1)
    reqs = _batch(np.random.default_rng(11), n_req=3, n_cand=20)
    eng.score_batch(reqs)
    eng.close()
    # three distinct contexts, one chunk each: one forward call of a
    # 4-row bucket (3 rows padded) x the 32-candidate bucket
    assert eng.stats.slots_scored == 4 * 32
    total = ServeStats()
    total.merge(eng.stats)
    total.merge(eng.stats)
    assert total.slots_scored == 2 * 4 * 32
    assert total.host_arg_bytes == 2 * eng.stats.host_arg_bytes
    assert total.phase_s == pytest.approx(
        {k: 2 * v for k, v in eng.stats.phase_s.items()})


def test_no_spans_outside_a_call():
    """Prewarm resolves contexts on the ingest thread, outside any
    ``score_batch``: it adds no phase time."""
    eng = _engine("staged", parallel=1)
    eng.score_batch(_batch(np.random.default_rng(13), n_req=2))
    before = dict(eng.stats.phase_s)
    assert eng.prewarm_contexts() == 2
    eng.close()
    assert eng.stats.phase_s == before
