"""Paper-core behaviour: context cache, sparse updates, hogwild, DeepFFM."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import FFMConfig
from repro.common.metrics import roc_auc
from repro.core import deepffm, dcnv2, ffm, sparse_updates as SU
from repro.data.synthetic import CTRStream
from repro.serving.context_cache import CachedServer
from repro.train.hogwild import HogwildTrainer, make_local_sgd_round

CFG = FFMConfig(n_fields=12, context_fields=8, hash_space=2**14, k=4,
                mlp_hidden=(16, 8))


@pytest.mark.parametrize("model", ["deepffm", "ffm"])
def test_context_cache_equivalence(model):
    key = jax.random.PRNGKey(0)
    params = deepffm.init_params(CFG, key, model)
    params["lr"]["w"] = jax.random.normal(key, params["lr"]["w"].shape) * 0.1
    srv = CachedServer(CFG, params, model)
    stream = CTRStream(CFG, seed=3)
    for _ in range(3):
        ci, cv, ki, kv = stream.request(n_candidates=7)
        a = srv.serve(ci, cv, ki, kv)
        b = srv.serve_uncached(ci, cv, ki, kv)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


def test_context_cache_hit_path_reuses_entry():
    key = jax.random.PRNGKey(1)
    params = deepffm.init_params(CFG, key)
    srv = CachedServer(CFG, params, max_entries=2)
    stream = CTRStream(CFG, seed=4)
    ci, cv, ki, kv = stream.request(5)
    srv.serve(ci, cv, ki, kv)
    srv.serve(ci, cv, ki, kv)
    assert srv.hits == 1 and srv.misses == 1
    # LRU eviction
    for s in range(3):
        ci2, cv2, ki2, kv2 = stream.request(5)
        srv.serve(ci2, cv2, ki2, kv2)
    assert len(srv._cache) <= 2


def test_sparse_update_grads_equal_autodiff():
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 4)
    B, D, H = 32, 16, 24
    p = {"w0": jax.random.normal(ks[0], (D, H)) * 0.5, "b0": jnp.zeros(H),
         "w1": jax.random.normal(ks[1], (H, H)) * 0.5, "b1": jnp.zeros(H),
         "w2": jax.random.normal(ks[2], (H, 1)) * 0.5, "b2": jnp.zeros(1)}
    x = jax.random.normal(ks[3], (B, D))

    def dense(p):
        h = jnp.maximum(x @ p["w0"] + p["b0"], 0)
        h = jnp.maximum(h @ p["w1"] + p["b1"], 0)
        return jnp.sum((h @ p["w2"] + p["b2"]) ** 2)

    def sparse(p):
        return jnp.sum(SU.sparse_mlp_apply(p, x, 2) ** 2)

    gd, gs = jax.grad(dense)(p), jax.grad(sparse)(p)
    for k in p:
        np.testing.assert_allclose(np.asarray(gd[k]), np.asarray(gs[k]),
                                   rtol=1e-5, atol=1e-5)


def test_sparse_update_kernel_path_matches():
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (64, 32))
    w = jax.random.normal(jax.random.PRNGKey(4), (32, 48)) * 0.5
    b = jnp.zeros(48)

    def f(use_kernel):
        return jax.grad(
            lambda w_: jnp.sum(SU.relu_linear(x, w_, b, use_kernel) ** 2)
        )(w)

    np.testing.assert_allclose(np.asarray(f(False)), np.asarray(f(True)),
                               rtol=1e-4, atol=1e-4)


def test_skip_stats_speedup_grows_with_sparsity():
    masks_lo = [jnp.asarray(np.random.default_rng(0).random((64, 256)) < 0.9)]
    masks_hi = [jnp.asarray(np.random.default_rng(0).random((64, 256)) < 0.01)]
    lo = SU.skip_stats(masks_lo)
    hi = SU.skip_stats(masks_hi)
    assert hi["modeled_update_speedup"] > lo["modeled_update_speedup"]


def test_deepffm_beats_linear_on_interaction_data():
    """Paper Table 1's qualitative claim on our synthetic interaction stream."""
    cfg = CFG
    stream = CTRStream(cfg, seed=7)
    train = [stream.sample(512) for _ in range(150)]
    test = stream.sample(4096)

    def fit(model, lr=0.1):
        params = deepffm.init_params(cfg, jax.random.PRNGKey(0), model)
        vg = jax.jit(jax.value_and_grad(
            lambda p, b: deepffm.loss_fn(cfg, p, b, model)))
        acc = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape), params)
        for b in train:
            _, g = vg(params, b)
            acc = jax.tree_util.tree_map(lambda a, gg: a + gg * gg, acc, g)
            params = jax.tree_util.tree_map(
                lambda p, gg, a: p - lr * gg / jnp.sqrt(a + 1e-10), params, g, acc)
        probs = np.asarray(deepffm.predict_proba(
            cfg, params, test["idx"], test["val"], model))
        return roc_auc(test["label"], probs)

    auc_lin = fit("linear")
    auc_dffm = fit("deepffm")
    assert auc_dffm > auc_lin + 0.01, (auc_lin, auc_dffm)


def test_dcnv2_trains():
    cfg = CFG.replace(n_cross=3)
    stream = CTRStream(cfg, seed=8)
    params = dcnv2.init_params(cfg, jax.random.PRNGKey(0))
    vg = jax.jit(jax.value_and_grad(lambda p, b: dcnv2.loss_fn(cfg, p, b)))
    losses = []
    for b in stream.batches(512, 30):
        l, g = vg(params, b)
        params = jax.tree_util.tree_map(lambda p, gg: p - 0.05 * gg, params, g)
        losses.append(float(l))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_hogwild_converges_and_matches_control_quality():
    cfg = CFG
    stream = CTRStream(cfg, seed=9)
    test = stream.sample(4096)

    def auc(tr):
        probs = np.asarray(deepffm.predict_proba(
            cfg, tr.params(), jnp.asarray(test["idx"]), jnp.asarray(test["val"])))
        return roc_auc(test["label"], probs)

    tr1 = HogwildTrainer(cfg, lr=0.05, seed=0)
    tr1.train(stream.batches(256, 100), n_threads=1)
    a1 = auc(tr1)

    # The 4-thread run is racy by design: its quality depends on the thread
    # interleaving, which depends on machine load. One retry absorbs the
    # occasional unlucky schedule without weakening the qualitative claim.
    for attempt in range(2):
        tr4 = HogwildTrainer(cfg, lr=0.05, seed=0)
        tr4.train(CTRStream(cfg, seed=9).batches(256, 100), n_threads=4)
        a4 = auc(tr4)
        if a4 > 0.52 and a4 > a1 - 0.05:
            break
    # paper: "weight degradation due to Hogwild ... does not appear to cause
    # any noticeable drops"
    assert a4 > 0.52 and a4 > a1 - 0.05, (a1, a4)


def test_local_sgd_round_improves_loss():
    cfg = CFG
    stream = CTRStream(cfg, seed=10)
    params = deepffm.init_params(cfg, jax.random.PRNGKey(0))
    acc = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape), params)
    rnd = make_local_sgd_round(cfg, "deepffm", lr=0.05)
    W, K, B = 2, 4, 128
    losses = []
    for _ in range(6):
        bs = [[stream.sample(B) for _ in range(K)] for _ in range(W)]
        stacked = jax.tree_util.tree_map(
            lambda *x: jnp.stack(x),
            *[jax.tree_util.tree_map(lambda *x: jnp.stack(x), *wb) for wb in bs])
        params, acc, loss = rnd(params, acc, stacked)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_ffm_diagmask_pair_count():
    assert CFG.n_pairs == 12 * 11 // 2
    pi, pj = ffm.pair_indices(CFG.n_fields)
    assert (pi < pj).all()
