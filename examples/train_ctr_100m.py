"""End-to-end driver: train a ~100M-parameter DeepFFM for a few hundred steps.

hash_space 2^20 x 24 fields x k=4 -> 100.7M FFM weights (+ LR + MLP head),
the production-CTR scale the paper operates at. Demonstrates: prefetched data
pipeline, Hogwild multi-thread training, checkpointing with optimizer-state
separation, and the quantized transfer channel.

    PYTHONPATH=src python examples/train_ctr_100m.py [--steps 200] [--hogwild]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store, transfer
from repro.common.compile_cache import setup_compile_cache
from repro.common.config import FFMConfig
from repro.common.metrics import roc_auc
from repro.core import deepffm
from repro.data.prefetch import Prefetcher
from repro.data.synthetic import CTRStream
from repro.train.hogwild import HogwildTrainer


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--hogwild", action="store_true")
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--ckpt", default="/tmp/repro_ctr_100m")
    args = ap.parse_args()

    cfg = FFMConfig(n_fields=24, context_fields=16, hash_space=2**20, k=4,
                    mlp_hidden=(64, 32))
    n_params = cfg.hash_space * cfg.n_fields * cfg.k + cfg.hash_space
    print(f"DeepFFM with {n_params/1e6:.1f}M parameters")
    stream = CTRStream(cfg, seed=0)

    t0 = time.time()
    if args.hogwild:
        trainer = HogwildTrainer(cfg, lr=0.1)
        stats = trainer.train(
            Prefetcher(stream.batches(args.batch, args.steps), depth=8),
            n_threads=args.threads)
        params = trainer.params()
        print(f"hogwild: {stats.examples} examples at "
              f"{stats.examples_per_s:.0f}/s across {args.threads} threads")
    else:
        params = deepffm.init_params(cfg, jax.random.PRNGKey(0))
        vg = jax.jit(jax.value_and_grad(lambda p, b: deepffm.loss_fn(cfg, p, b)))
        acc = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape), params)
        for i, b in enumerate(Prefetcher(stream.batches(args.batch, args.steps), depth=8)):
            loss, g = vg(params, b)
            acc = jax.tree_util.tree_map(lambda a, gg: a + gg * gg, acc, g)
            params = jax.tree_util.tree_map(
                lambda p, gg, a: p - 0.1 * gg / jnp.sqrt(a + 1e-10), params, g, acc)
            if i % 50 == 0:
                print(f"step {i}: loss {float(loss):.4f}")
    print(f"trained in {time.time()-t0:.1f}s")

    test = stream.sample(8192)
    probs = np.asarray(deepffm.predict_proba(cfg, params, test["idx"], test["val"]))
    print(f"test AUC: {roc_auc(test['label'], probs):.4f}")

    # checkpoint (weights and optimizer state in separate files, paper §6)
    store.save(args.ckpt, params)
    print(f"checkpointed to {args.ckpt}")

    # what one online update would cost to ship, per mode
    sender = transfer.Sender(mode="patch+quant")
    sender.make_update(params)
    t0 = time.time()
    drifted = jax.tree_util.tree_map(
        lambda x: x + 1e-5 * (np.random.default_rng(0).random(x.shape) < 0.01), params)
    update = sender.make_update(drifted)
    print(f"patch+quant online update: {len(update):,} bytes "
          f"({len(update)/(n_params*4):.2%} of raw) in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
