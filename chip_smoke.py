"""Smoke test of the train -> ingest -> score path on one TPU chip.

Runs the path a deployment runs, in one process, through the repository's
own entry points, at ``PROD_FFM`` widths (a 2^22-row hash table of 24
fields, 16 of them request context, k=8):

  device   JAX must report a TPU. Anything else exits non-zero.
  train    ``TrainingPipeline(model="deepffm", backend="jit")`` takes a few
           rounds of AdaGrad steps on ``CTRStream`` data and emits its
           versioned update frames (one full frame, then row deltas).
  serve    Two quantized ``InferenceEngine``s ingest those frames through
           their update pipes (``submit_update`` + ``flush``), warm one
           candidate bucket and answer ``score_batch`` requests:
             staged  deepffm head, in-trace gather, Pallas candidate kernel;
             fused   ffm head, one fused Pallas call per bucket.
           The staged engine's uncached forward (``score_uncached`` on the
           Pallas backend) runs the row-gather and interaction-matrix
           kernels.
  compare  Scores against the f32 ``deepffm.forward`` oracle, run at float32
           matmul precision: the staged and uncached scores within
           ``quantization.pair_logit_tolerance`` of the trainer's weights,
           the fused scores within ``fused_logit_tolerance`` of the engine's
           own dequantized tables (the same quantized model).
  exact    Every engine against the oracle over its own dequantized rows,
           within ``EXACT_TOL``: all that is left there is f32 rounding in
           another summation order. The staged engine rescores the same
           requests at float32 matmul precision for it (its MLP head runs at
           the platform's default precision, bf16 passes on TPU), and so
           does the uncached forward. The oracle over the same rows rounded
           to bfloat16 is the control: it must fall outside the limit, or
           the check could not see a kernel that computes in bf16.

Each phase prints one line: its seconds (compilation reported as set-up),
requests scored, the largest deviation next to its tolerance, and the bytes
of host arrays each scoring call passes into its jitted forward (they cross
to the device on every call). Any failure raises, so the script exits
non-zero; only when every phase passed does it print, as its last line,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.common.compile_cache import setup_compile_cache  # noqa: E402
from repro.common.config import PROD_FFM, FFMConfig  # noqa: E402
from repro.core import deepffm, ffm  # noqa: E402
from repro.core import quantization as Q  # noqa: E402
from repro.data.synthetic import CTRStream  # noqa: E402
from repro.serving.engine import InferenceEngine  # noqa: E402
from repro.train.pipeline import TrainingPipeline  # noqa: E402


# Widths are PROD_FFM's, uncut. Training and traffic are cut to what the chip
# runs in a few minutes: rounds x steps x examples, batches x requests x
# candidates.
ROUNDS, STEPS, BATCH = 3, 8, 512
N_BATCHES, N_REQUESTS, N_CANDIDATES = 2, 16, 256

# Limit of the exact checks (see the module docstring): above the sound
# readings, below the bf16 control's. Readings in PERF.md.
EXACT_TOL = 1e-4


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"{phase}: {body}", flush=True)


def device_info() -> dict:
    """The device as JAX reports it; raises unless it is a TPU."""
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    check(info["platform"] == "tpu", f"no TPU: JAX reports {info}")
    return info


def train(cfg: FFMConfig, *, rounds: int, steps: int, batch: int,
          seed: int = 0):
    """Rounds of the jitted AdaGrad step. Returns the trained f32 params
    (on the device), the wire manifest and the update frames; the
    pipeline itself, with its optimizer state, is dropped to free HBM."""
    pipe = TrainingPipeline(cfg, "deepffm", "jit", transfer_mode="raw",
                            seed=seed)
    stream = CTRStream(cfg, seed=seed)
    frames = [pipe.run_round(stream.batches(batch, steps))
              for _ in range(rounds)]
    reps = pipe.reports
    check(all(np.isfinite(r.mean_loss) for r in reps),
          f"non-finite training loss: {[r.mean_loss for r in reps]}")
    check(reps[0].update_kind == "full", "first frame is not a full frame")
    steady = reps[1:] or reps
    say("train", rounds=rounds, steps_per_round=steps, batch=batch,
        examples=sum(r.examples for r in reps),
        setup_s=f"{reps[0].seconds:.3f}(round 1, includes compile)",
        round_s=[f"{r.seconds:.3f}" for r in steady],
        examples_per_s=f"{np.mean([r.examples_per_s for r in steady]):.1f}",
        mean_loss=f"{reps[-1].mean_loss:.4f}",
        progressive_auc=f"{reps[-1].progressive_auc:.4f}",
        frames=[f"{r.update_kind}:{r.update_bytes}" for r in reps])
    return pipe.params, pipe.sender.manifest, frames


def make_requests(cfg: FFMConfig, *, n_batches: int, n_requests: int,
                  n_candidates: int, seed: int = 1):
    stream = CTRStream(cfg, seed=seed)
    return [[stream.request(n_candidates) for _ in range(n_requests)]
            for _ in range(n_batches)]


def h2d_bytes_per_call(engine: InferenceEngine, rb: int, nb: int) -> int:
    """Bytes of the host arrays the deployed forward takes at one bucket;
    all of them cross to the device on each call."""
    info = engine.lower_candidates_forward(rb, nb).args_info
    return int(sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                   for a in jax.tree_util.tree_leaves(info)))


def serve(cfg: FFMConfig, model: str, params, manifest, frames, batches,
          *, fused: bool, name: str):
    """A quantized Pallas engine that ingests ``frames`` through its update
    pipe, warms the buckets ``batches`` use, and scores them."""
    n_req, n_cand = len(batches[0]), batches[0][0][2].shape[0]
    eng = InferenceEngine(cfg, model, backend="pallas", quantized=True,
                          fused=fused, min_bucket=n_cand, parallel=1)
    check(eng.fused == fused, f"{name}: fused={eng.fused}, wanted {fused}")
    t0 = time.perf_counter()
    eng.update_pipe(manifest, params)
    for frame in frames:
        eng.submit_update(frame)
    check(eng.update_pipe().flush(timeout=None), f"{name}: flush failed")
    ingest_s = time.perf_counter() - t0
    st = eng.update_pipe().stats
    check(st.frames_failed == 0 and st.frames_rejected == 0,
          f"{name}: ingest failed: {st.last_ingest_error or st.last_frame_error}")
    check(st.published == len(frames) and eng.weights_version == len(frames),
          f"{name}: published {st.published} of {len(frames)} frames")
    check(Q.is_row_quantized(eng.params["ffm"]["emb"]),
          f"{name}: table not int8")

    t0 = time.perf_counter()
    calls = eng.warmup(max_requests=n_req, max_candidates=n_cand)
    warmup_s = time.perf_counter() - t0
    h2d = h2d_bytes_per_call(eng, eng.plan.bucket(n_req, minimum=1),
                             eng.plan.bucket(n_cand))
    outs, score_s = [], []
    for reqs in batches:
        t0 = time.perf_counter()
        got = eng.score_batch(reqs)
        score_s.append(time.perf_counter() - t0)
        for (_, _, ki, _), o in zip(reqs, got):
            check(o.shape == (ki.shape[0],) and np.isfinite(o).all(),
                  f"{name}: bad scores {o.shape}")
        outs.append(np.concatenate(got))
    say(f"serve[{name}]", model=model, fused=eng.fused,
        host_gather=eng.host_gather, ingest_s=f"{ingest_s:.3f}",
        frames=len(frames), rows_requantized=st.rows_requantized,
        setup_s=f"{warmup_s:.3f}(warmup, {calls} compiled buckets)",
        score_batch_s=[f"{s:.3f}" for s in score_s],
        requests=sum(len(b) for b in batches),
        candidates=sum(o.size for o in outs),
        h2d_bytes_per_call=h2d)
    return eng, np.concatenate(outs)


def feature_rows(cfg: FFMConfig, reqs):
    """(idx, val) full feature rows of requests, context broadcast over
    each request's candidates — the oracle's input."""
    fc = cfg.context_fields
    idx = np.concatenate([np.concatenate(
        [np.broadcast_to(ci, (len(ki), fc)), ki], axis=1)
        for ci, _, ki, _ in reqs]).astype(np.int32)
    val = np.concatenate([np.concatenate(
        [np.broadcast_to(cv, (len(kv), fc)), kv], axis=1)
        for _, cv, _, kv in reqs]).astype(np.float32)
    return idx, val


_forward = jax.jit(deepffm.forward, static_argnums=(0, 4))


def oracle(cfg: FFMConfig, model: str, params, idx, val,
           chunk: int = 4096) -> np.ndarray:
    """f32 ``deepffm.forward`` at float32 matmul precision (on TPU the
    default precision would make the oracle the inexact side), ``chunk``
    feature rows per call."""
    with jax.default_matmul_precision("float32"):
        return np.concatenate([
            np.asarray(_forward(cfg, params, idx[i:i + chunk],
                                val[i:i + chunk], model))
            for i in range(0, len(idx), chunk)])


def dequantized_rows(qparams, idx):
    """The engine's int8 model restricted to the rows ``idx`` touches, as
    f32: dequantized embedding rows and LR weights, ``idx`` renumbered into
    them. A forward over it equals a forward over the whole dequantized
    table, which is 3.2 GB at production widths."""
    rows, inv = np.unique(idx, return_inverse=True)
    emb = qparams["ffm"]["emb"]
    emb_rows = Q.dequantize_rows(
        {k: np.asarray(emb[k])[rows] for k in ("codes", "scale", "zero")})
    params = {**qparams,
              "ffm": {**qparams["ffm"], "emb": emb_rows},
              "lr": {"w": ffm.gather_lr_np(qparams["lr"]["w"], rows),
                     "b": qparams["lr"]["b"]}}
    return params, inv.reshape(idx.shape).astype(np.int32)


def compare(name: str, got, want, tol: float, **bound_inputs) -> None:
    dev = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    say(f"compare[{name}]", rows=int(np.size(got)),
        max_abs_dev=f"{dev:.3e}", tolerance=f"{tol:.3e}",
        **{k: f"{v:.3e}" for k, v in bound_inputs.items()})
    check(dev <= tol, f"{name}: deviation {dev} above tolerance {tol}")


def bf16_rows(params):
    """``params`` with its embedding rows rounded to bfloat16."""
    emb = np.asarray(params["ffm"]["emb"])
    return {**params, "ffm": {**params["ffm"], "emb": emb.astype(
        jnp.bfloat16).astype(np.float32)}}


def compare_exact(name: str, got, want, control, **readings) -> None:
    """``got`` within ``EXACT_TOL`` of ``want``, the oracle over the
    engine's own dequantized rows; ``control``, that oracle over the rows
    rounded to bf16, must be farther from ``want`` than the limit."""
    dev = float(np.max(np.abs(np.asarray(got) - want)))
    ctl = float(np.max(np.abs(control - want)))
    say(f"exact[{name}]", rows=int(np.size(got)), max_abs_dev=f"{dev:.3e}",
        limit=f"{EXACT_TOL:.3e}", bf16_control_dev=f"{ctl:.3e}",
        **{k: f"{v:.3e}" for k, v in readings.items()})
    check(dev <= EXACT_TOL, f"{name}: deviation {dev} above {EXACT_TOL}")
    check(ctl > EXACT_TOL, f"{name}: the bf16 control reads {ctl}, inside "
                           f"the limit {EXACT_TOL}: the check is blind")


def value_max(reqs) -> float:
    return float(max(max(np.abs(cv).max(), np.abs(kv).max())
                     for _, cv, _, kv in reqs))


def run(cfg: FFMConfig, *, rounds: int, steps: int, batch: int,
        n_requests: int, n_candidates: int, n_batches: int,
        n_uncached: int = 2) -> None:
    """Every phase after the device check, at ``cfg``."""
    params, manifest, frames = train(cfg, rounds=rounds, steps=steps,
                                     batch=batch)
    batches = make_requests(cfg, n_batches=n_batches, n_requests=n_requests,
                            n_candidates=n_candidates)
    reqs = [r for b in batches for r in b]
    idx, val = feature_rows(cfg, reqs)
    vmax = value_max(reqs)
    emb_absmax = float(jnp.max(jnp.abs(params["ffm"]["emb"])))

    # staged deepffm engine vs the trainer's f32 weights
    staged, got = serve(cfg, "deepffm", params, manifest, frames, batches,
                        fused=False, name="staged")
    qp = staged.params
    pair_bound = dict(emb_absmax=emb_absmax,
                      eps=Q.row_max_error(qp["ffm"]["emb"]), vmax=vmax,
                      lr_eps=Q.block_max_error(qp["lr"]["w"]))
    pair_tol = Q.pair_logit_tolerance(cfg, **pair_bound)
    want = oracle(cfg, "deepffm", params, idx, val)
    compare("staged", got, want, pair_tol, **pair_bound)

    # ... and vs its own dequantized rows, rescored at float32 precision
    rt, rt_idx = dequantized_rows(qp, idx)
    want_q = oracle(cfg, "deepffm", rt, rt_idx, val)
    control = oracle(cfg, "deepffm", bf16_rows(rt), rt_idx, val)
    with jax.default_matmul_precision("float32"):
        got_f32 = np.concatenate([np.concatenate(staged.score_batch(reqs_b))
                                  for reqs_b in batches])
    compare_exact("staged", got_f32, want_q, control,
                  default_precision_dev=float(np.max(np.abs(got - want_q))))

    # the staged engine's uncached forward: Pallas row gather + full
    # interaction matrix over the int8 table, at float32 precision
    t0 = time.perf_counter()
    with jax.default_matmul_precision("float32"):
        unc = np.concatenate([
            np.asarray(staged.score_uncached(*r, use_backend=True))
            for r in reqs[:n_uncached]])
    say("serve[uncached]", requests=n_uncached,
        seconds=f"{time.perf_counter() - t0:.3f}(includes compile)")
    compare("uncached", unc, want[:unc.size], pair_tol, **pair_bound)
    compare_exact("uncached", unc, want_q[:unc.size], control[:unc.size])

    # fused ffm engine vs its own dequantized tables
    fused, got = serve(cfg, "ffm", params, manifest, frames, batches,
                       fused=True, name="fused")
    qp = fused.params
    rt, rt_idx = dequantized_rows(qp, idx)
    lr_max = max(float(np.abs(rt["lr"]["w"]).max()),
                 float(np.abs(np.asarray(rt["lr"]["b"])).max()))
    fused_bound = dict(emb_absmax=emb_absmax,
                       eps=Q.row_max_error(qp["ffm"]["emb"]), vmax=vmax,
                       lr_max=lr_max)
    want_q = oracle(cfg, "ffm", rt, rt_idx, val)
    compare("fused", got, want_q,
            Q.fused_logit_tolerance(cfg, **fused_bound), **fused_bound)
    compare_exact("fused", got, want_q,
                  oracle(cfg, "ffm", bf16_rows(rt), rt_idx, val))
    for eng in (staged, fused):
        eng.update_pipe().close()
        eng.close()


def main() -> None:
    info = device_info()
    say("device", platform=info["platform"], kind=repr(info["kind"]),
        count=info["count"])
    say("compile_cache", dir=setup_compile_cache())
    cfg = PROD_FFM
    say("config", n_fields=cfg.n_fields, context_fields=cfg.context_fields,
        k=cfg.k, hash_space=cfg.hash_space, mlp_hidden=cfg.mlp_hidden)
    say("cut", widths="none (PROD_FFM)",
        training=f"{ROUNDS} rounds x {STEPS} steps x {BATCH} examples",
        traffic=f"{N_BATCHES} batches x {N_REQUESTS} requests x "
                f"{N_CANDIDATES} candidates")
    run(cfg, rounds=ROUNDS, steps=STEPS, batch=BATCH, n_requests=N_REQUESTS,
        n_candidates=N_CANDIDATES, n_batches=N_BATCHES)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
