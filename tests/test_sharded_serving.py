"""Sharded multi-worker serving: topology, scatter-gather router, fan-out.

The fleet contracts (see ``serving/shard_router.py`` module docstring):

* **Topology exactness** — contiguous LR-block-aligned ranges make
  ``quantize(shard_slice(w)) == shard_slice(quantize(w))`` byte-for-byte,
  and shard params concatenate back to the full-space pytree.
* **Cross-N bit identity** — router scores are bit-identical for every
  shard count N (quantized and f32 fleets, divisible and non-divisible
  splits), and within quantization tolerance of the ``deepffm.forward``
  oracle. This is the partial-sum reduction contract: one fixed einsum
  form over compacted entries + fixed-shard-order disjoint scatter.
* **Fan-out byte exactness** — per-shard ``ShardedSender`` frames decode to
  exactly the shard slices of the full-space frames at every generation
  (full + deltas), so the streamed fleet equals the single-engine ingest
  oracle byte-for-byte in its int8 tables.
* **Failure modes** — killing a shard degrades (zero contributions,
  ``degraded`` flag) without a request-path exception; a torn generation
  vector (one shard updated, one behind) still serves; ``rotate_shard``
  swaps a successor in without breaking the delta chain.
"""
import numpy as np
import pytest

import jax

from repro.checkpoint import layout, transfer
from repro.common.config import FFMConfig
from repro.core import deepffm
from repro.core import quantization as Q
from repro.launch import topology
from repro.serving.engine import InferenceEngine
from repro.serving.shard_router import ShardRouter
from repro.train.pipeline import TrainingPipeline

pytestmark = pytest.mark.lockcheck

CFG = FFMConfig(n_fields=8, context_fields=5, hash_space=1024, k=4,
                mlp_hidden=(16,))


@pytest.fixture(scope="module")
def params():
    p = deepffm.init_params(CFG, jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(np.asarray, p)


def _requests(rng, n_req=5, n_cand=7, cfg=CFG):
    fc, fcand = cfg.context_fields, cfg.n_fields - cfg.context_fields
    return [(rng.integers(0, cfg.hash_space, fc).astype(np.int32),
             rng.standard_normal(fc).astype(np.float32),
             rng.integers(0, cfg.hash_space, (n_cand, fcand)).astype(np.int32),
             rng.standard_normal((n_cand, fcand)).astype(np.float32))
            for _ in range(n_req)]


# ---------------------------------------------------------------------------
# Topology
# ---------------------------------------------------------------------------

def test_shard_ranges_cover_aligned():
    ranges = topology.shard_ranges(1024, 3)
    assert ranges[0][0] == 0 and ranges[-1][1] == 1024
    for (_, hi), (lo, _) in zip(ranges, ranges[1:]):
        assert hi == lo
    for lo, _ in ranges:
        assert lo % Q.LR_BLOCK == 0
    # ownership is total and consistent with the ranges
    owner = topology.owner_of(ranges, np.arange(1024))
    for s, (lo, hi) in enumerate(ranges):
        assert (owner[lo:hi] == s).all()


def test_shard_ranges_too_many_shards():
    with pytest.raises(ValueError):
        topology.shard_ranges(128, 3)  # only 2 alignment units


def test_row_sharded_paths_from_specs():
    assert topology.row_sharded_paths(CFG, "deepffm") == ("ffm/emb", "lr/w")


def test_quantize_commutes_with_slicing(params):
    """quantize(shard_slice(w)) == shard_slice(quantize(w)) byte-for-byte."""
    topo = topology.ShardTopology.build(CFG, "deepffm", 3)
    full_q = Q.quantize_params_rows(params)
    for s, (lo, hi) in enumerate(topo.ranges):
        local_q = Q.quantize_params_rows(topo.shard_params(params, s))
        sliced = topo.shard_params(full_q, s)
        for key in ("codes", "scale", "zero"):
            assert np.array_equal(local_q["ffm"]["emb"][key],
                                  sliced["ffm"]["emb"][key])
            assert np.array_equal(local_q["lr"]["w"][key],
                                  sliced["lr"]["w"][key])


def test_materialized_params_roundtrip(params):
    router = ShardRouter(CFG, n_shards=3, params=params, quantized=True)
    full_q = Q.quantize_params_rows(params)
    mat = router.materialized_params()
    router.close()
    for key in ("codes", "scale", "zero"):
        assert np.array_equal(mat["ffm"]["emb"][key], full_q["ffm"]["emb"][key])
        assert np.array_equal(mat["lr"]["w"][key], full_q["lr"]["w"][key])


# ---------------------------------------------------------------------------
# Cross-N bit identity + oracle tolerance (the reduction contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quantized", [True, False])
def test_scores_bit_identical_across_shard_counts(params, quantized):
    rng = np.random.default_rng(1)
    reqs = _requests(rng)
    outs = {}
    for n in (1, 2, 3, 4):  # 3: non-divisible split
        router = ShardRouter(CFG, n_shards=n, params=params,
                             quantized=quantized)
        outs[n] = np.concatenate(router.score_batch(reqs))
        router.close()
    for n in (2, 3, 4):
        assert np.array_equal(outs[n], outs[1]), f"N={n} bits != N=1"


def test_router_within_tolerance_of_forward_oracle(params):
    rng = np.random.default_rng(2)
    reqs = _requests(rng)
    router = ShardRouter(CFG, n_shards=2, params=params, quantized=False)
    got = np.concatenate(router.score_batch(reqs))
    want = np.concatenate([
        np.asarray(router.score_uncached(ci, cv, ki, kv))
        for ci, cv, ki, kv in reqs])
    router.close()
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_quantized_router_matches_single_quantized_engine(params):
    rng = np.random.default_rng(3)
    reqs = _requests(rng)
    router = ShardRouter(CFG, n_shards=2, params=params, quantized=True)
    single = InferenceEngine(CFG, params=params, quantized=True)
    got = np.concatenate(router.score_batch(reqs))
    want = np.concatenate(single.score_batch(reqs))
    router.close()
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_resident_bytes_split_across_shards(params):
    single = InferenceEngine(CFG, params=params, quantized=True)
    router = ShardRouter(CFG, n_shards=4, params=params, quantized=True)
    per_shard = router.shard_resident_bytes()
    # tables split ~1/N; the small replicated head rides along per shard
    assert max(per_shard) < single.resident_weight_bytes / 2
    assert sum(per_shard) == router.resident_weight_bytes
    router.close()


# ---------------------------------------------------------------------------
# Fan-out delta ingestion
# ---------------------------------------------------------------------------

def _mk_batch(rng, cfg=CFG, n=64):
    return {"idx": rng.integers(0, cfg.hash_space,
                                (n, cfg.n_fields)).astype(np.int32),
            "val": rng.standard_normal((n, cfg.n_fields)).astype(np.float32),
            "label": rng.integers(0, 2, n).astype(np.float32)}


def test_sharded_frames_decode_to_slices_of_full_frames():
    """Per-shard delta-frame filtering vs the full-space ingest oracle,
    byte-for-byte, at every generation while deltas stream."""
    rng = np.random.default_rng(7)
    ranges = topology.shard_ranges(CFG.hash_space, 2)
    pipe_s = TrainingPipeline(CFG, lr=0.05, seed=3, shard_ranges=ranges)
    pipe_f = TrainingPipeline(CFG, lr=0.05, seed=3)
    like = jax.tree_util.tree_map(np.asarray, pipe_f.params)
    rec_full = transfer.Receiver()
    recs = [transfer.Receiver() for _ in ranges]
    kinds = []
    for rnd in range(3):
        batch = [_mk_batch(rng)]
        frames = pipe_s.run_round(iter(batch))
        full = pipe_f.run_round(iter(batch))
        kinds.append(transfer.unframe(full).kind)
        assert [transfer.unframe(f).kind for f in frames] == \
            [transfer.unframe(full).kind] * len(ranges)  # grid coherence
        rec_full.apply_update(full)
        want = rec_full.materialize(manifest=pipe_f.sender.manifest,
                                    like=like)
        want_flat = dict(layout.flatten_with_paths(want))
        for s, (frame, rec) in enumerate(zip(frames, recs)):
            rec.apply_update(frame)
            assert rec.version == transfer.unframe(full).version
            got = rec.materialize(manifest=pipe_s.sender.manifests[s])
            lo, hi = ranges[s]
            for path, arr in got.items():
                ref = want_flat[path]
                if path in ("ffm/emb", "lr/w"):
                    ref = ref[lo:hi]
                assert np.array_equal(np.asarray(ref, np.float32),
                                      np.asarray(arr, np.float32)), \
                    f"round {rnd} shard {s} {path}"
    assert kinds[0] == transfer.KIND_FULL  # first round ships full
    assert transfer.KIND_DELTA in kinds[1:]  # steady state goes delta


def test_streamed_fleet_matches_single_engine_ingest(params):
    """Stream full + delta rounds through per-shard pipes; the fleet's int8
    tables must be byte-exact slices of the single engine's, the generation
    vector must advance, and scores must match within tolerance."""
    rng = np.random.default_rng(8)
    ranges = topology.shard_ranges(CFG.hash_space, 2)
    pipe_s = TrainingPipeline(CFG, lr=0.05, seed=4, shard_ranges=ranges)
    pipe_f = TrainingPipeline(CFG, lr=0.05, seed=4)
    router = ShardRouter(CFG, n_shards=2, quantized=True)
    single = InferenceEngine(CFG, quantized=True)
    like = jax.tree_util.tree_map(np.asarray, pipe_f.params)

    rounds = []
    for _ in range(3):
        batch = [_mk_batch(rng)]
        rounds.append((pipe_s.run_round(iter(batch)),
                       pipe_f.run_round(iter(batch))))
    router.configure_fanout(pipe_s.sender.manifests, like)
    for frames, full in rounds:
        assert router.submit_updates(frames) == 2
        single.submit_update(full, manifest=pipe_f.sender.manifest,
                             like_params=like)
    gens = router.flush_updates()
    single.update_pipe().flush()
    assert all(g == (3, 3) for g in gens)
    assert router.weights_version == 3

    sp = single.params
    for s, shard in enumerate(router.shards):
        lo, hi = ranges[s]
        for key in ("codes", "scale", "zero"):
            assert np.array_equal(shard.params["ffm"]["emb"][key],
                                  sp["ffm"]["emb"][key][lo:hi])
    reqs = _requests(rng)
    got = np.concatenate(router.score_batch(reqs))
    want = np.concatenate(single.score_batch(reqs))
    router.close()
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_streamed_bits_invariant_across_shard_counts():
    """N=2 streamed fleet == N=1 streamed fleet bit-for-bit at the final
    generation (the reduction contract holds for ingested weights too)."""
    rng = np.random.default_rng(9)
    outs = {}
    for n in (1, 2):
        pipe = TrainingPipeline(
            CFG, lr=0.05, seed=5,
            shard_ranges=topology.shard_ranges(CFG.hash_space, n))
        router = ShardRouter(CFG, n_shards=n, quantized=True)
        like = jax.tree_util.tree_map(np.asarray, pipe.params)
        batch_rng = np.random.default_rng(10)  # same batches for both fleets
        frames = [pipe.run_round(iter([_mk_batch(batch_rng)]))
                  for _ in range(2)]
        router.configure_fanout(pipe.sender.manifests, like)
        for f in frames:
            router.submit_updates(f)
        router.flush_updates()
        req_rng = np.random.default_rng(11)
        outs[n] = np.concatenate(router.score_batch(_requests(req_rng)))
        router.close()
    assert np.array_equal(outs[2], outs[1])


# ---------------------------------------------------------------------------
# Failure modes
# ---------------------------------------------------------------------------

def test_kill_shard_degrades_gracefully(params):
    rng = np.random.default_rng(12)
    reqs = _requests(rng)
    router = ShardRouter(CFG, n_shards=3, params=params, quantized=True)
    before = np.concatenate(router.score_batch(reqs))
    router.kill_shard(1)
    assert router.degraded
    after = np.concatenate(router.score_batch(reqs))  # must not raise
    assert np.isfinite(after).all()
    assert not np.array_equal(before, after)  # the dead rows really zeroed
    assert router.fleet_generations()[1] is None
    # oracle path still works against the zero-filled materialized tables
    o = router.score_uncached(*reqs[0])
    assert np.isfinite(np.asarray(o)).all()
    router.close()


def test_torn_generation_vector_serves(params):
    """One shard a generation ahead of the other: the router serves a mixed
    snapshot without raising, and converges once both shards flush."""
    rng = np.random.default_rng(13)
    ranges = topology.shard_ranges(CFG.hash_space, 2)
    pipe = TrainingPipeline(CFG, lr=0.05, seed=6, shard_ranges=ranges)
    router = ShardRouter(CFG, n_shards=2, quantized=True)
    like = jax.tree_util.tree_map(np.asarray, pipe.params)
    f0 = pipe.run_round(iter([_mk_batch(rng)]))
    f1 = pipe.run_round(iter([_mk_batch(rng)]))
    router.configure_fanout(pipe.sender.manifests, like)
    router.submit_updates(f0)
    router.flush_updates()
    # tear: only shard 0 gets round 2
    router.shards[0].submit_update(f1[0])
    router.shards[0]._pipe.flush()
    gens = router.fleet_generations()
    assert gens[0][1] == 2 and gens[1][1] == 1  # torn vector
    reqs = _requests(rng)
    torn = np.concatenate(router.score_batch(reqs))  # must not raise
    assert np.isfinite(torn).all()
    # heal: shard 1 catches up; parity with an untorn fleet ingest
    router.shards[1].submit_update(f1[1])
    router.flush_updates()
    assert all(g[1] == 2 for g in router.fleet_generations())
    healed = np.concatenate(router.score_batch(reqs))
    other = ShardRouter(CFG, n_shards=2, quantized=True)
    other.configure_fanout(pipe.sender.manifests, like)
    for f in (f0, f1):
        other.submit_updates(f)
    other.flush_updates()
    assert np.array_equal(healed,
                          np.concatenate(other.score_batch(reqs)))
    router.close()
    other.close()


def test_rotate_shard_swaps_successor_and_keeps_delta_chain(params):
    rng = np.random.default_rng(14)
    ranges = topology.shard_ranges(CFG.hash_space, 2)
    pipe = TrainingPipeline(CFG, lr=0.05, seed=7, shard_ranges=ranges)
    router = ShardRouter(CFG, n_shards=2, quantized=True)
    like = jax.tree_util.tree_map(np.asarray, pipe.params)
    f0 = pipe.run_round(iter([_mk_batch(rng)]))
    router.configure_fanout(pipe.sender.manifests, like)
    router.submit_updates(f0)
    router.flush_updates()
    reqs = _requests(rng)
    before = np.concatenate(router.score_batch(reqs))
    old = router.shards[0]
    succ = router.rotate_shard(0)
    assert router.shards[0] is succ and succ is not old
    assert succ.generation >= old.generation  # monotonic across the swap
    assert np.array_equal(np.concatenate(router.score_batch(reqs)), before)
    # the delta chain continues through the re-pointed pipe
    f1 = pipe.run_round(iter([_mk_batch(rng)]))
    assert transfer.unframe(f1[0]).kind == transfer.KIND_DELTA
    router.submit_updates(f1)
    router.flush_updates()
    assert succ.weights_version == 2
    assert np.isfinite(np.concatenate(router.score_batch(reqs))).all()
    router.close()


def test_engine_rotate_adopts_params_and_version(params):
    eng = InferenceEngine(CFG, params=params, quantized=True)
    rng = np.random.default_rng(15)
    reqs = _requests(rng)
    want = np.concatenate(eng.score_batch(reqs))
    succ = eng.rotate()
    assert succ.params is eng.params  # adopted by reference, not requantized
    assert succ.generation == eng.generation
    assert succ.weights_version == eng.weights_version
    assert np.array_equal(np.concatenate(succ.score_batch(reqs)), want)


# ---------------------------------------------------------------------------
# Gather-cliff calibration (satellites 1+2)
# ---------------------------------------------------------------------------

def test_cliff_env_kill_switch(monkeypatch):
    from repro.kernels.row_gather import ops as rg_ops

    monkeypatch.setenv("REPRO_CLIFF_CALIBRATE", "0")
    assert rg_ops.cliff_rows() == rg_ops.CLIFF_ROWS


def test_cliff_calibration_cached_and_bounded(monkeypatch):
    from repro.kernels.row_gather import ops as rg_ops

    monkeypatch.delenv("REPRO_CLIFF_CALIBRATE", raising=False)
    monkeypatch.setattr(rg_ops, "_calibrated", None)
    got = rg_ops.cliff_rows()
    assert min(rg_ops._PROBE_SIZES) <= got <= rg_ops._PROBE_MAX
    assert rg_ops._calibrated == got  # cached per process
    monkeypatch.setattr(rg_ops, "calibrate_cliff_rows",
                        lambda *a, **k: (_ for _ in ()).throw(RuntimeError()))
    monkeypatch.setattr(rg_ops, "_calibrated", None)
    with pytest.raises(RuntimeError):  # a failed probe surfaces, uncached
        rg_ops.cliff_rows()
    assert rg_ops._calibrated is None


def test_f32_host_gather_parity(params):
    """Satellite 2: an f32 engine forced onto the host packed pre-gather
    scores bit-compatible (within float tolerance) with the in-trace one."""
    rng = np.random.default_rng(16)
    reqs = _requests(rng)
    host = InferenceEngine(CFG, params=params, host_gather=True)
    trace = InferenceEngine(CFG, params=params, host_gather=False)
    assert host.host_gather and not trace.host_gather
    got = np.concatenate(host.score_batch(reqs))
    want = np.concatenate(trace.score_batch(reqs))
    np.testing.assert_allclose(got, want, atol=1e-5)
