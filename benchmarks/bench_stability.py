"""Paper Table 1 + Figure 3: rolling-window AUC stability across algorithms.

Single-pass online training (as FW/VW do) on the synthetic CTR stream with
drift; AUC computed in rolling windows; summary stats per algorithm.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._util import row
from repro.common.config import FFMConfig
from repro.common.metrics import roc_auc, rolling_auc
from repro.core import dcnv2, deepffm
from repro.data.synthetic import CTRStream

CFG = FFMConfig(n_fields=16, context_fields=10, hash_space=2**15, k=6,
                mlp_hidden=(32, 16))
ALGOS = ("linear", "mlp", "ffm", "deepffm", "dcnv2")


LRS = {"linear": 0.3, "mlp": 0.1, "ffm": 0.15, "deepffm": 0.15, "dcnv2": 0.05}


def _fit_online(model: str, n_batches: int = 300, batch: int = 512, lr: float = None,
                window: int = 8192, seed: int = 0):
    """Single-pass online training; returns per-window AUCs + test AUC + time."""
    lr = lr or LRS[model]
    stream = CTRStream(CFG, seed=seed, drift=0.001)
    if model == "dcnv2":
        dcn = CFG.replace(n_cross=3)
        params = dcnv2.init_params(dcn, jax.random.PRNGKey(seed))
        vg = jax.jit(jax.value_and_grad(lambda p, b: dcnv2.loss_fn(dcn, p, b)))
        predict = jax.jit(lambda p, i, v: jax.nn.sigmoid(dcnv2.forward(dcn, p, i, v)))
    else:
        params = deepffm.init_params(CFG, jax.random.PRNGKey(seed), model)
        vg = jax.jit(jax.value_and_grad(
            lambda p, b: deepffm.loss_fn(CFG, p, b, model)))
        predict = jax.jit(
            lambda p, i, v: deepffm.predict_proba(CFG, p, i, v, model))

    acc = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape), params)
    labels, scores = [], []
    t0 = time.perf_counter()
    for b in stream.batches(batch, n_batches):
        # progressive validation (VW-style): score before learning
        scores.append(np.asarray(predict(params, b["idx"], b["val"])))
        labels.append(b["label"])
        _, g = vg(params, b)
        acc = jax.tree_util.tree_map(lambda a, gg: a + gg * gg, acc, g)
        params = jax.tree_util.tree_map(
            lambda p, gg, a: p - lr * gg / jnp.sqrt(a + 1e-10), params, g, acc)
    train_s = time.perf_counter() - t0

    labels = np.concatenate(labels)
    scores = np.concatenate(scores)
    aucs = rolling_auc(labels, scores, window)
    test = stream.sample(8192)
    test_auc = roc_auc(test["label"],
                       np.asarray(predict(params, test["idx"], test["val"])))
    return aucs, test_auc, train_s


def run(quick: bool = False):
    rows = []
    n = 80 if quick else 300
    table = {}
    for algo in ALGOS:
        aucs, test_auc, train_s = _fit_online(algo, n_batches=n)
        table[algo] = dict(avg=aucs.mean(), median=np.median(aucs), max=aucs.max(),
                           std=aucs.std(), min=aucs.min(), test=test_auc)
        rows.append(row(
            f"stability/{algo}", train_s / n * 1e6,
            f"avg={aucs.mean():.4f} median={np.median(aucs):.4f} max={aucs.max():.4f} "
            f"std={aucs.std():.4f} min={aucs.min():.4f} test={test_auc:.4f}",
        ))
    # the paper's qualitative claims, checked. "Stability" in the paper is
    # sensitivity to hyperparameter configuration (VW needs careful tuning;
    # FW-DeepFFM behaves across configs) — so measure test-AUC spread across
    # a small lr grid rather than within-run window variance.
    ok_ffm = table["deepffm"]["test"] >= table["linear"]["test"]
    import numpy as _np

    def _lr_spread(algo):
        base = LRS[algo]
        aucs = [_fit_online(algo, n_batches=max(n // 2, 40), lr=base * m)[1]
                for m in (0.25, 1.0, 4.0)]
        return float(_np.std(aucs)), [round(a, 4) for a in aucs]

    std_lin, aucs_lin = _lr_spread("linear")
    std_dffm, aucs_dffm = _lr_spread("deepffm")
    rows.append(row(
        "stability/claims", 0.0,
        f"deepffm_beats_linear={ok_ffm} "
        f"lr_grid_std linear={std_lin:.4f}{aucs_lin} "
        f"deepffm={std_dffm:.4f}{aucs_dffm} "
        f"deepffm_less_config_sensitive={std_dffm <= std_lin}"))
    return rows


if __name__ == "__main__":
    from benchmarks._util import print_rows

    print_rows(run())
