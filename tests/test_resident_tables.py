"""Device-resident gather tables of in-trace engines.

An engine whose forward gathers in-trace on an accelerator uploads each
published params object to the device once, before the swap, and every
forward call takes that twin in place of host numpy
(``InferenceEngine._device_params``; counted by ``ServeStats.table_uploads``
and ``table_upload_bytes``). The CPU backend keeps no twin, so the tests
turn residency on with the ``resident`` fixture, which clears the module's
list of host-memory backends.
"""
import jax
import numpy as np
import pytest

from repro.checkpoint import transfer
from repro.common.config import FFMConfig
from repro.core import deepffm
from repro.serving import engine as engine_mod
from repro.serving.engine import (InferenceEngine, ServeStats,
                                  batched_candidates_forward, host_arg_nbytes)
from repro.serving.shard_router import ShardRouter

CFG = FFMConfig(n_fields=12, context_fields=8, hash_space=2**12, k=4,
                mlp_hidden=(16,))


def _params(model="deepffm", seed=0, cfg=CFG):
    params = deepffm.init_params(cfg, jax.random.PRNGKey(seed), model)
    params["lr"]["w"] = jax.random.normal(
        jax.random.PRNGKey(seed + 1), params["lr"]["w"].shape) * 0.1
    return jax.tree_util.tree_map(np.asarray, params)


def _batch(rng, n_req=6, n_cand=24, cfg=CFG):
    fc, fcand = cfg.context_fields, cfg.n_fields - cfg.context_fields
    # a few shared contexts and repeated candidates, so dedup has work
    ctxs = [(rng.integers(0, cfg.hash_space, fc).astype(np.int32),
             rng.normal(1, 0.25, fc).astype(np.float32)) for _ in range(3)]
    out = []
    for i in range(n_req):
        ki = rng.integers(0, cfg.hash_space, (n_cand, fcand)).astype(np.int32)
        ki[n_cand // 2:] = ki[:n_cand - n_cand // 2]
        out.append((*ctxs[i % 3], ki,
                    rng.normal(1, 0.25, (n_cand, fcand)).astype(np.float32)))
    return out


@pytest.fixture
def resident(monkeypatch):
    """Residency as on an accelerator: the CPU backend no longer counts as
    host memory."""
    monkeypatch.setattr(engine_mod, "_HOST_MEMORY_BACKENDS", ())


def _in_trace(model="deepffm", quantized=True, parallel=1, **kw):
    kw.setdefault("params", _params(model))
    return InferenceEngine(CFG, model, backend="pallas", quantized=quantized,
                           host_gather=False, parallel=parallel, **kw)


def _score(eng, batches):
    return [eng.score_batch(b) for b in batches]


def _assert_same(a, b):
    for xs, ys in zip(a, b, strict=True):
        for x, y in zip(xs, ys, strict=True):
            np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("parallel", [1, 2])
@pytest.mark.parametrize("quantized", [True, False], ids=["int8", "f32"])
@pytest.mark.parametrize("model", ["deepffm", "ffm"])
def test_resident_tables_score_bit_identically(model, quantized, parallel,
                                                monkeypatch):
    rng = np.random.default_rng(1)
    batches = [_batch(rng), _batch(rng, n_req=3, n_cand=9)]
    host = _in_trace(model, quantized, parallel)
    want = _score(host, batches)
    host.close()
    assert host.stats.table_uploads == 0

    monkeypatch.setattr(engine_mod, "_HOST_MEMORY_BACKENDS", ())
    eng = _in_trace(model, quantized, parallel)
    got = _score(eng, batches)
    eng.close()
    _assert_same(got, want)
    assert eng.stats.table_uploads == 1
    twin = eng._device_params(eng.params)
    assert all(isinstance(x, jax.Array) or not isinstance(x, np.ndarray)
               for x in jax.tree_util.tree_leaves(twin))


def _spy_calls(eng):
    """Count the forward calls the engine builds."""
    calls = []
    build = eng._forward_args

    def spy(*a, **kw):
        out = build(*a, **kw)
        calls.append(out)
        return out

    eng._forward_args = spy
    return calls


def test_host_arg_bytes_drop_by_the_tables_bytes(resident, monkeypatch):
    batch = _batch(np.random.default_rng(2))
    eng = _in_trace(parallel=2)
    calls = _spy_calls(eng)
    eng.score_batch(batch)
    eng.close()
    assert len(calls) == 2  # the batch split across both workers
    tables = host_arg_nbytes(eng.params)
    assert eng.stats.table_upload_bytes == tables

    monkeypatch.setattr(engine_mod, "_HOST_MEMORY_BACKENDS", ("cpu",))
    host = _in_trace(parallel=2)
    host_calls = _spy_calls(host)
    host.score_batch(batch)
    host.close()
    assert len(host_calls) == 2
    assert (host.stats.host_arg_bytes - eng.stats.host_arg_bytes
            == len(calls) * tables)
    # what still crosses per call: context states and candidate blocks
    assert 0 < eng.stats.host_arg_bytes < tables


@pytest.mark.lockcheck
def test_one_upload_per_install_and_publish_none_per_call(resident):
    rng = np.random.default_rng(3)
    eng = _in_trace(warmup_buckets=(8, 32))
    assert eng.stats.table_uploads == 1
    eng.score_batch(_batch(rng))
    eng.score_batch(_batch(rng))
    assert eng.stats.table_uploads == 1

    eng.install_params(_params(seed=1))
    assert eng.stats.table_uploads == 2
    snd = transfer.Sender(mode="raw")
    p2, p3 = _params(seed=2), _params(seed=3)
    eng.apply_update(snd.make_update(p2), snd.manifest, p2)
    assert eng.stats.table_uploads == 3
    eng.submit_update(snd.make_update(p3))
    eng.update_pipe().flush()
    assert eng.stats.table_uploads == 4
    assert eng.generation == 3  # construction is generation 0
    for _ in range(3):
        eng.score_batch(_batch(rng))
    assert eng.stats.table_uploads == 4
    assert eng.stats.table_upload_bytes == 4 * host_arg_nbytes(eng.params)
    eng.update_pipe().close()
    eng.close()

    total = ServeStats()
    total.merge(eng.stats)
    total.merge(eng.stats)
    assert total.table_uploads == 8
    assert total.table_upload_bytes == 2 * eng.stats.table_upload_bytes


def test_delta_frame_scores_match_a_host_argument_engine(monkeypatch):
    rng = np.random.default_rng(4)
    batches = [_batch(rng), _batch(rng, n_req=2)]
    p1 = _params(seed=5)
    p2 = {**p1, "ffm": {"emb": p1["ffm"]["emb"].copy()}}
    rows = np.unique(batches[0][0][2][:2])  # rows the first request scores
    p2["ffm"]["emb"][rows] += 0.5
    snd = transfer.Sender(mode="raw")
    frames = [snd.make_update(p1),
              snd.make_update(p2, touched={"ffm/emb": rows,
                                           "lr/w": np.zeros(0, np.int64)})]
    assert transfer.unframe(frames[1]).is_delta

    def run():
        eng = _in_trace(params=None)
        eng.apply_update(frames[0], snd.manifest, p1)
        before = _score(eng, batches)
        eng.apply_update(frames[1])
        after = _score(eng, batches)
        eng.update_pipe().close()
        eng.close()
        return eng, before, after

    host, want_before, want_after = run()
    monkeypatch.setattr(engine_mod, "_HOST_MEMORY_BACKENDS", ())
    eng, got_before, got_after = run()
    assert (host.stats.table_uploads, eng.stats.table_uploads) == (0, 2)
    _assert_same(got_before, want_before)
    _assert_same(got_after, want_after)
    # the delta moved the touched rows' scores
    assert any(not np.array_equal(a, b)
               for xs, ys in zip(want_before, want_after)
               for a, b in zip(xs, ys))


def test_rotated_successor_adopts_the_twin(resident):
    rng = np.random.default_rng(6)
    eng = _in_trace(warmup_buckets=(4, 16))
    eng.score_batch(_batch(rng, n_req=4, n_cand=12))
    succ = eng.rotate()
    assert succ.stats.table_uploads == 0
    assert succ._device_params(succ.params) is eng._device_params(eng.params)
    batch = _batch(rng, n_req=4, n_cand=12)
    _assert_same([succ.score_batch(batch)], [eng.score_batch(batch)])
    assert succ.stats.table_uploads == 0
    assert eng.stats.table_uploads == 1
    succ.close()
    eng.close()


def test_host_side_gathers_upload_nothing(resident):
    """Fused and host pre-gather engines take no table argument, and the
    router's own surface holds sharded views: none builds a twin. The
    router's shard engines gather in-trace and keep their own."""
    rng = np.random.default_rng(7)
    batch = _batch(rng)
    fused = InferenceEngine(CFG, "ffm", backend="pallas", quantized=True,
                            fused=True, params=_params("ffm"), parallel=1)
    pre = InferenceEngine(CFG, "deepffm", quantized=True, host_gather=True,
                          params=_params(), parallel=1)
    for eng in (fused, pre):
        eng.score_batch(batch)
        eng.close()
        assert eng.stats.table_uploads == 0
        assert eng._device_tables == ()

    params = _params()
    outs = []
    for n in (1, 2):
        router = ShardRouter(CFG, n_shards=n, params=params, quantized=True)
        outs.append(router.score_batch(batch))
        assert router.stats.table_uploads == 0
        assert router._device_tables == ()
        assert [s.stats.table_uploads for s in router.shards] == [1] * n
        router.close()
    _assert_same([outs[1]], [outs[0]])


def test_warmup_covers_the_resident_arguments(resident):
    """jit keys on whether an argument is numpy or a device array: the
    warmup must compile the entries the twin's calls hit, so scoring adds
    none. (Own table size, so no other test has compiled these shapes.)"""
    cfg = FFMConfig(n_fields=12, context_fields=8, hash_space=2**11, k=4,
                    mlp_hidden=(16,))
    eng = InferenceEngine(cfg, "deepffm", backend="pallas", quantized=True,
                          host_gather=False, parallel=2,
                          params=_params(cfg=cfg))
    before = batched_candidates_forward._cache_size()
    eng.warmup(max_requests=8, max_candidates=32)
    warmed = batched_candidates_forward._cache_size()
    assert warmed > before
    rng = np.random.default_rng(8)
    for n_req, n_cand in ((8, 32), (3, 5), (1, 17)):
        eng.score_batch(_batch(rng, n_req, n_cand, cfg=cfg))
    eng.close()
    assert batched_candidates_forward._cache_size() == warmed
    assert eng.stats.table_uploads == 1


def test_cpu_backend_keeps_tables_on_host():
    params = _params()
    assert not engine_mod._tables_resident(False, params)
    eng = _in_trace(params=params, warmup_buckets=(4, 16))
    eng.score_batch(_batch(np.random.default_rng(9), n_req=4, n_cand=12))
    eng.install_params(_params(seed=1))
    eng.close()
    assert eng.stats.table_uploads == eng.stats.table_upload_bytes == 0
    assert eng._device_tables == ()
    assert eng._device_params(eng.params) is eng.params
