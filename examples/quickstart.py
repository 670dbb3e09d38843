"""Quickstart: the paper's full production loop in one script.

Train a DeepFFM online -> ship versioned quantized byte-patches to a
long-lived serving engine (hot weight swaps, context cache + Pallas kernel
composed) -> serve candidate requests, microbatched. Run with:

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import transfer
from repro.common.compile_cache import setup_compile_cache
from repro.common.config import FFMConfig
from repro.common.metrics import roc_auc
from repro.core import deepffm
from repro.data.prefetch import Prefetcher
from repro.data.synthetic import CTRStream
from repro.serving.engine import InferenceEngine

setup_compile_cache()
cfg = FFMConfig(n_fields=12, context_fields=8, hash_space=2**14, k=4,
                mlp_hidden=(16, 8))
stream = CTRStream(cfg, seed=7)

# --- trainer ----------------------------------------------------------------
params = deepffm.init_params(cfg, jax.random.PRNGKey(0))
vg = jax.jit(jax.value_and_grad(lambda p, b: deepffm.loss_fn(cfg, p, b)))
acc = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape), params)

sender = transfer.Sender(mode="patch+quant")   # paper §6
# one long-lived serving instance: §5 context cache + Pallas hot loop composed
engine = InferenceEngine(cfg, backend="pallas")

for round_ in range(3):  # three online-training rounds (paper: every ~5 min)
    for batch in Prefetcher(stream.batches(512, 30), depth=4):  # paper §4.1
        loss, grads = vg(params, batch)
        acc = jax.tree_util.tree_map(lambda a, g: a + g * g, acc, grads)
        params = jax.tree_util.tree_map(
            lambda p, g, a: p - 0.1 * g / jnp.sqrt(a + 1e-10), params, grads, acc)
    update = sender.make_update(params)
    # hot swap: weights change in place, the context cache survives
    engine.apply_update(update, sender.manifest, like_params=params)
    print(f"round {round_}: loss={float(loss):.4f} update={len(update):,} bytes "
          f"(weights v{engine.weights_version})")

    ctx_i, ctx_v, cand_i, cand_v = stream.request(n_candidates=16)
    scores = engine.score(ctx_i, ctx_v, cand_i, cand_v)
    print(f"  request: best candidate {int(jnp.argmax(scores))}, "
          f"cache hits={engine.hits} misses={engine.misses}")

# --- serving ----------------------------------------------------------------
test = stream.sample(4096)
probs = np.asarray(deepffm.predict_proba(
    cfg, engine.params, test["idx"], test["val"]))
print(f"served-model AUC: {roc_auc(test['label'], probs):.4f}")

# microbatched requests: one jitted call, power-of-two padding buckets
requests = [stream.request(n_candidates=n) for n in (16, 5, 16, 9)]
for scores in engine.score_batch(requests):
    print(f"batched request: best candidate {int(jnp.argmax(scores))}")
print(f"latency p50={engine.stats.p50_ms:.2f}ms p99={engine.stats.p99_ms:.2f}ms "
      f"({engine.stats.predictions_per_s:.0f} preds/s)")
