"""The unified online-training pipeline (PR 3): jitted sparse-backward round
step, row-delta update frames, and async hot-swap ingestion — the full
train->serve loop against a from-scratch forward oracle."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import layout, transfer
from repro.common.config import PROD_FFM, FFMConfig
from repro.core import deepffm
from repro.data.synthetic import CTRStream
from repro.optim import make_optimizer
from repro.serving.engine import InferenceEngine
from repro.train.loop import OnlineTrainer
from repro.train.pipeline import (TrainingPipeline, device_row_width,
                                  emb_leaf_path, from_device_rows,
                                  make_round_step, make_sparse_round_step,
                                  to_device_rows, touched_paths)

pytestmark = pytest.mark.tier1

CFG = FFMConfig(n_fields=8, context_fields=4, hash_space=2**12, k=4,
                mlp_hidden=(16,))


def _stack(batches):
    return {k: np.stack([b[k] for b in batches]) for k in batches[0]}


# ---------------------------------------------------------------------------
# Trainer layer
# ---------------------------------------------------------------------------

def _sparse_step_vs_dense(cfg, model):
    """One round of the dense step on ``(V, F, k)`` tables and of the
    sparse step on the :func:`to_device_rows` form, from the same start;
    returns both results, the sparse tables viewed back as ``(V, F, k)``,
    and the sparse step's own tables."""
    device_rows = emb_leaf_path(model) is not None
    opt = make_optimizer("adagrad", lr=0.1)
    stream = CTRStream(cfg, seed=1)
    stacked = _stack([stream.sample(32) for _ in range(4)])
    results = {}
    for name, maker in (("dense", make_round_step),
                        ("sparse", make_sparse_round_step)):
        params = deepffm.init_params(cfg, jax.random.PRNGKey(0), model)
        if device_rows and name == "sparse":
            params = to_device_rows(cfg, model, params)
        state = opt.init(params)
        rf = maker(cfg, model, opt, donate=False)
        p, st, step, outs = rf(params, state, jnp.zeros((), jnp.int32),
                               stacked)
        raw = (p, st)
        if device_rows and name == "sparse":
            p = from_device_rows(cfg, model, p)
            st = {"acc": from_device_rows(cfg, model, st["acc"])}
        results[name] = (p, st, step, outs)
    for a, b in zip(jax.tree_util.tree_leaves(results["dense"][:2]),
                    jax.tree_util.tree_leaves(results["sparse"][:2])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(results["dense"][3]["scores"]),
                               np.asarray(results["sparse"][3]["scores"]),
                               rtol=1e-4, atol=1e-6)
    return raw


@pytest.mark.parametrize("model", ["linear", "mlp", "ffm", "deepffm"])
def test_sparse_round_step_matches_dense(model):
    """The O(batch) gather/scatter AdaGrad step is the dense full-space step
    restricted to the touched rows — params, acc, and pre-update scores all
    agree (duplicate feature occurrences included). The sparse step keeps
    its embedding tables as the trainer's ``(V, W)`` device rows, unpadded
    off the TPU."""
    _sparse_step_vs_dense(CFG, model)


def test_sparse_round_step_on_lane_padded_rows(monkeypatch):
    """On TPU the device rows are padded to a multiple of 128 lanes; the
    sparse step on such rows still equals the dense step, and the padding
    columns stay zero."""
    assert device_row_width(PROD_FFM) == PROD_FFM.n_fields * PROD_FFM.k
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # a config of its own, so that to_device_rows traces under the TPU width
    cfg = CFG.replace(hash_space=2 ** 11)
    fk = cfg.n_fields * cfg.k
    assert device_row_width(cfg) == 128 > fk
    assert device_row_width(PROD_FFM) == 256
    params, state = _sparse_step_vs_dense(cfg, "deepffm")
    for table in (params["ffm"]["emb"], state["acc"]["ffm"]["emb"]):
        assert table.shape == (cfg.hash_space, 128)
        assert not np.asarray(table[:, fk:]).any()


def test_sparse_backward_grads_equal_autodiff_on_deepffm():
    """§4.3 on by default: DeepFFM's MLP routed through ``relu_linear`` must
    produce the same gradients as the plain autodiff oracle."""
    params = deepffm.init_params(CFG, jax.random.PRNGKey(0))
    params["mlp"]["w1"] = jax.random.normal(jax.random.PRNGKey(1),
                                            params["mlp"]["w1"].shape) * 0.3
    batch = CTRStream(CFG, seed=2).sample(64)
    gs = jax.grad(lambda p: deepffm.loss_fn(CFG, p, batch,
                                            sparse_backward=True))(params)
    gd = jax.grad(lambda p: deepffm.loss_fn(CFG, p, batch,
                                            sparse_backward=False))(params)
    for a, b in zip(jax.tree_util.tree_leaves(gs),
                    jax.tree_util.tree_leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_round_report_and_frame_version_agree():
    """The PR 3 off-by-one fix: ``RoundReport.round`` == the frame stamp."""
    stream = CTRStream(CFG, seed=3)
    trainer = OnlineTrainer(CFG, lr=0.1)
    for expect in (1, 2):
        update = trainer.run_round(stream.batches(64, 3))
        frame = transfer.unframe(update)
        assert trainer.reports[-1].round == frame.version == expect


def test_skip_stats_surface_in_round_report():
    pl = TrainingPipeline(CFG, lr=0.1)
    pl.run_round(CTRStream(CFG, seed=4).batches(64, 3))
    rep = pl.reports[-1]
    assert set(rep.skip_stats) >= {"unit_skip_frac", "tile_skip_frac",
                                   "modeled_update_speedup"}
    assert 0.0 <= rep.skip_stats["unit_skip_frac"] <= 1.0
    assert rep.touched_rows > 0 and rep.examples_per_s > 0


def test_local_sgd_workers_must_be_power_of_two():
    """Averaging W identical untouched rows is bit-exact only for 2^k workers
    — the row-delta frames rely on untouched rows staying byte-stable."""
    with pytest.raises(ValueError, match="power of two"):
        TrainingPipeline(CFG, backend="local_sgd", local_sgd_workers=3)


# ---------------------------------------------------------------------------
# Transfer layer
# ---------------------------------------------------------------------------

def _drift_rows(params, rows):
    p = jax.tree_util.tree_map(lambda x: np.array(x, np.float32), params)
    p["ffm"]["emb"][rows] += 0.01
    p["lr"]["w"][rows] -= 0.01
    p["mlp"]["w0"] += 0.001  # dense leaves always change
    return jax.tree_util.tree_map(jnp.asarray, p)


@pytest.mark.parametrize("mode", transfer.MODES)
def test_delta_frame_roundtrip_byte_exact(mode):
    """KIND_DELTA reconstructs the receiver buffer byte-for-byte in every
    mode (``delta_verify`` additionally scans for changes the touched set
    would have missed)."""
    p0 = deepffm.init_params(CFG, jax.random.PRNGKey(0))
    rows = np.array([1, 57, 1033, 4000])
    p1 = _drift_rows(p0, rows)
    snd = transfer.Sender(mode=mode, delta_verify=True)
    rcv = transfer.Receiver()
    rcv.apply_update(snd.make_update(p0))
    update = snd.make_update(p1, touched={"ffm/emb": rows, "lr/w": rows})
    assert transfer.unframe(update).is_delta
    rcv.apply_update(update)
    assert rcv._current == snd._last  # byte-identical server state
    got = rcv.materialize(mode, snd.manifest, like=p1)
    for (_, a), (_, b) in zip(layout.flatten_with_paths(p1),
                              layout.flatten_with_paths(got)):
        if "quant" in mode:
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=5e-4)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_multiple_deltas_between_materialize_calls():
    """The receiver's incremental dequantize must cover the union of every
    delta applied since the last materialize — streaming several frames and
    materializing once is the classic Receiver usage."""
    p0 = deepffm.init_params(CFG, jax.random.PRNGKey(0))
    rows1, rows2 = np.array([5, 900]), np.array([42, 2222])
    p1 = _drift_rows(p0, rows1)
    p2 = _drift_rows(p1, rows2)
    snd = transfer.Sender(mode="patch+quant", delta_verify=True)
    rcv = transfer.Receiver()
    rcv.apply_update(snd.make_update(p0))
    rcv.materialize("patch+quant", snd.manifest)  # arms the incremental path
    all_rows = np.concatenate([rows1, rows2])
    rcv.apply_update(snd.make_update(
        p1, touched={"ffm/emb": rows1, "lr/w": rows1}))
    rcv.apply_update(snd.make_update(
        p2, touched={"ffm/emb": all_rows, "lr/w": all_rows}))
    got = rcv.materialize("patch+quant", snd.manifest, like=p2)
    for (_, a), (_, b) in zip(layout.flatten_with_paths(p2),
                              layout.flatten_with_paths(got)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-4)


def test_sync_ingest_never_overtakes_queued_frames():
    """apply_update while frames sit in the submit queue must drain them
    first — a sync frame applied against the wrong base bytes would silently
    corrupt the patch/delta chain."""
    stream = CTRStream(CFG, seed=11)
    pl = TrainingPipeline(CFG, lr=0.1, delta_updates=True)
    engine = InferenceEngine(CFG)
    updates = [pl.run_round(stream.batches(64, 2)) for _ in range(4)]
    engine.apply_update(updates[0], pl.sender.manifest, pl.params)
    engine.submit_update(updates[1])
    engine.submit_update(updates[2])
    engine.apply_update(updates[3])  # must land after 1 and 2
    assert engine.weights_version == 4 and engine.generation == 4
    ci, cv, ki, kv = stream.request(4)
    np.testing.assert_allclose(np.asarray(engine.score(ci, cv, ki, kv)),
                               _oracle(engine, ci, cv, ki, kv),
                               rtol=2e-4, atol=2e-5)
    engine.update_pipe().close()


def test_delta_verify_catches_incomplete_touched_set():
    p0 = deepffm.init_params(CFG, jax.random.PRNGKey(0))
    rows = np.array([3, 99])
    p1 = _drift_rows(p0, np.array([3, 99, 2048]))  # 2048 changes too
    snd = transfer.Sender(mode="raw", delta_verify=True)
    snd.make_update(p0)
    with pytest.raises(ValueError, match="outside the touched rows"):
        snd.make_update(p1, touched={"ffm/emb": rows, "lr/w": rows})


def test_pipeline_emits_delta_frames_in_steady_state():
    pl = TrainingPipeline(CFG, lr=0.1, delta_updates=True)
    stream = CTRStream(CFG, seed=5)
    kinds = []
    for _ in range(3):
        update = pl.run_round(stream.batches(64, 3))
        kinds.append(transfer.unframe(update).kind)
    assert kinds[0] == transfer.KIND_FULL           # nothing to delta against
    assert set(kinds[1:]) == {transfer.KIND_DELTA}  # steady state
    assert kinds == [
        {"full": transfer.KIND_FULL, "patch": transfer.KIND_PATCH,
         "delta": transfer.KIND_DELTA}[r.update_kind] for r in pl.reports]


# ---------------------------------------------------------------------------
# The full train -> serve round trip
# ---------------------------------------------------------------------------

def _oracle(engine, ci, cv, ki, kv):
    n = ki.shape[0]
    fc = CFG.context_fields
    idx = np.concatenate([np.broadcast_to(ci, (n, fc)), ki], axis=1)
    val = np.concatenate([np.broadcast_to(cv, (n, fc)), kv], axis=1)
    return np.asarray(deepffm.forward(CFG, engine.params, idx, val,
                                      engine.model))


@pytest.mark.parametrize("mode", transfer.MODES)
def test_train_serve_roundtrip(mode):
    """N trainer rounds piped through every transfer mode (+ row deltas) into
    the engine: at each generation the engine's scores equal a from-scratch
    ``deepffm.forward`` on the engine's params, and those params match the
    trainer's within the mode's tolerance."""
    stream = CTRStream(CFG, seed=6)
    pl = TrainingPipeline(CFG, lr=0.1, transfer_mode=mode, delta_updates=True)
    engine = InferenceEngine(CFG)
    for rnd in range(1, 4):
        update = pl.run_round(stream.batches(64, 4))
        engine.apply_update(update, pl.sender.manifest, pl.params)
        assert engine.generation == rnd
        assert engine.weights_version == pl.reports[-1].round == rnd
        ci, cv, ki, kv = stream.request(5)
        got = np.asarray(engine.score(ci, cv, ki, kv))
        np.testing.assert_allclose(got, _oracle(engine, ci, cv, ki, kv),
                                   rtol=2e-4, atol=2e-5)
        tol = 5e-4 if "quant" in mode else 1e-7
        for a, b in zip(jax.tree_util.tree_leaves(pl.params),
                        jax.tree_util.tree_leaves(engine.params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=tol)
    assert pl.reports[-1].update_kind == "delta"  # steady state, every mode


@pytest.mark.parametrize("backend", ["hogwild", "local_sgd"])
def test_alternate_backends_through_the_same_pipe(backend):
    """Hogwild / local-SGD rounds produce finite losses and valid frames that
    flow through the identical transfer+engine pipe."""
    stream = CTRStream(CFG, seed=7)
    pl = TrainingPipeline(CFG, backend=backend, lr=0.05, delta_updates=True)
    engine = InferenceEngine(CFG)
    for _ in range(2):
        update = pl.run_round(stream.batches(64, 4))
        engine.apply_update(update, pl.sender.manifest, pl.params)
    rep = pl.reports[-1]
    assert np.isfinite(rep.mean_loss) and rep.examples > 0
    assert engine.generation == 2 and engine.weights_version == 2
    ci, cv, ki, kv = stream.request(4)
    got = np.asarray(engine.score(ci, cv, ki, kv))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, _oracle(engine, ci, cv, ki, kv),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# Async ingestion
# ---------------------------------------------------------------------------

def test_async_update_pipe_publishes_in_order():
    stream = CTRStream(CFG, seed=8)
    pl = TrainingPipeline(CFG, lr=0.1, delta_updates=True)
    engine = InferenceEngine(CFG)
    updates = [pl.run_round(stream.batches(64, 2)) for _ in range(4)]
    for u in updates:
        assert engine.submit_update(u, pl.sender.manifest, pl.params)
    assert engine.update_pipe().flush()  # True: drained, not killed
    assert engine.generation == 4
    assert engine.weights_version == 4  # frames applied FIFO
    assert engine.update_pipe().stats.published == 4
    ci, cv, ki, kv = stream.request(5)
    np.testing.assert_allclose(np.asarray(engine.score(ci, cv, ki, kv)),
                               _oracle(engine, ci, cv, ki, kv),
                               rtol=2e-4, atol=2e-5)
    engine.update_pipe().close()


def test_scoring_concurrent_with_async_ingest_never_tears():
    """Scores taken while the pipe ingests in the background always match the
    oracle for *some* published generation — never a mix.

    The oracle score set is precomputed by replaying the identical update
    chain through a reference engine, one sync apply per generation."""
    stream = CTRStream(CFG, seed=9)
    pl = TrainingPipeline(CFG, "ffm", lr=0.1, delta_updates=True)
    updates = [pl.run_round(stream.batches(64, 2)) for _ in range(5)]
    ci, cv, ki, kv = stream.request(6)

    ref = InferenceEngine(CFG, "ffm")
    valid = []
    for u in updates:
        ref.apply_update(u, pl.sender.manifest, pl.params)
        valid.append(_oracle(ref, ci, cv, ki, kv))

    engine = InferenceEngine(CFG, "ffm")
    engine.apply_update(updates[0], pl.sender.manifest, pl.params)
    engine.warmup(max_requests=1, max_candidates=8)

    errors = []

    def scorer():
        for _ in range(60):
            got = np.asarray(engine.score(ci, cv, ki, kv))
            if not any(np.allclose(got, want, rtol=2e-4, atol=2e-5)
                       for want in valid):
                errors.append(got)

    t = threading.Thread(target=scorer)
    t.start()
    for u in updates[1:]:
        engine.submit_update(u, pl.sender.manifest, pl.params)
    engine.update_pipe().flush()
    t.join()
    engine.update_pipe().close()
    assert not errors
    assert engine.generation == len(updates)


def test_sync_apply_update_still_works_without_thread():
    """The thin wrapper never spawns a thread for synchronous use."""
    stream = CTRStream(CFG, seed=10)
    pl = TrainingPipeline(CFG, lr=0.1)
    engine = InferenceEngine(CFG)
    engine.apply_update(pl.run_round(stream.batches(64, 2)),
                        pl.sender.manifest, pl.params)
    assert engine.update_pipe()._thread is None
    assert engine.generation == 1
