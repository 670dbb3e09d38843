"""The ``dcnv2`` head served through ``InferenceEngine``'s staged path.

Seeded random weights at a small size: 6 fields (3 of them context), an
embedding width of 5 (odd, so every lane block of the kernel is padded), 2
cross layers, a (16, 8) deep network and 4096 hashed rows. Every engine
answer is held to ``core/dcnv2.forward``, the plain float32 reference, read
on the engine's own tables (for int8 tables: their dequantized rows).

Tolerances, relative to the logits' largest magnitude:

* ``EXACT`` (1e-5) where the engine computes in float32 as the reference
  does (the reference backend, and the candidates' jnp path of float
  tables): only the order of float32 sums differs.
* ``KERNEL`` (1e-4) for ``dcn_cross_q8``: each product is three bfloat16
  passes, accurate to about 2^-16 (1.5e-5) of its inputs, and the errors of
  two cross layers and two deep layers add up.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import FFMConfig
from repro.core import dcnv2
from repro.core import quantization as Q
from repro.kernels.dcn_cross import ops as dcn_ops
from repro.kernels.dcn_cross import ref as dcn_ref
from repro.serving import engine as engine_mod
from repro.serving.engine import InferenceEngine, dcn_candidates_forward
from repro.serving.shard_router import ShardRouter
from repro.train.pipeline import TrainingPipeline

CFG = FFMConfig(n_fields=6, context_fields=3, hash_space=4096, k=5,
                mlp_hidden=(16, 8), n_cross=2)
FC, FCAND = CFG.context_fields, CFG.n_fields - CFG.context_fields
EXACT, KERNEL = 1e-5, 1e-4


def _params(seed=0, cfg=CFG):
    """Float32 weights at a trained model's scale: rows of about 0.3,
    cross layers that move x_l by about half its size, biases that are not
    zero."""
    rng = np.random.default_rng(seed)
    d = dcnv2.width(cfg)
    cross, mlp = {}, {}
    for l in range(cfg.n_cross):
        cross[f"w{l}"] = rng.normal(0, 1 / np.sqrt(d), (d, d))
        cross[f"b{l}"] = rng.normal(0, 0.1, d)
    dims = (d,) + tuple(cfg.mlp_hidden) + (1,)
    for i in range(len(dims) - 1):
        mlp[f"w{i}"] = rng.normal(0, np.sqrt(2 / dims[i]),
                                  (dims[i], dims[i + 1]))
        mlp[f"b{i}"] = rng.normal(0, 0.1, dims[i + 1])
    params = {"emb": rng.normal(0, 0.3, (cfg.hash_space, cfg.k)),
              "cross": cross, "mlp": mlp}
    return jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32), params)


def _request(rng, n, ctx=None, v=CFG.hash_space):
    ci, cv = ctx if ctx is not None else (
        rng.integers(0, v, FC).astype(np.int32),
        rng.uniform(0.5, 2.0, FC).astype(np.float32))
    return (ci, cv, rng.integers(0, v, (n, FCAND)).astype(np.int32),
            rng.uniform(0.5, 2.0, (n, FCAND)).astype(np.float32))


def _reference(params, reqs, cfg=CFG):
    """``dcnv2.forward`` of each request's full feature rows."""
    out = []
    for ci, cv, ki, kv in reqs:
        n = len(ki)
        idx = np.concatenate([np.broadcast_to(ci, (n, FC)), ki], 1)
        val = np.concatenate([np.broadcast_to(cv, (n, FC)), kv], 1)
        out.append(np.asarray(dcnv2.forward(cfg, params, jnp.asarray(idx),
                                            jnp.asarray(val))))
    return out


def _assert_close(got, want, rel):
    scale = max(float(np.max(np.abs(np.concatenate(want)))), 1.0)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=0, atol=rel * scale)


def _engine(quantized=True, backend="pallas", **kw):
    kw.setdefault("params", _params())
    return InferenceEngine(CFG, "dcnv2", backend=backend, quantized=quantized,
                           parallel=kw.pop("parallel", 1), **kw)


@pytest.mark.parametrize("quantized,backend,rel", [
    (False, "reference", EXACT), (False, "pallas", EXACT),
    (True, "reference", EXACT), (True, "pallas", KERNEL)],
    ids=["f32-reference", "f32-pallas", "int8-reference", "int8-kernel"])
def test_engine_matches_the_reference(quantized, backend, rel):
    eng = _engine(quantized, backend)
    rng = np.random.default_rng(1)
    reqs = [_request(rng, n) for n in (1, 13, 40, 8)]
    got = eng.score_batch(reqs)
    # int8 tables: the reference reads the engine's dequantized rows
    assert Q.is_row_quantized(eng.params["emb"]) == quantized
    _assert_close(got, _reference(eng.params, reqs), rel)
    eng.close()


def test_int8_tables_are_rows_of_width_k():
    q = Q.quantize_params_rows(_params())
    assert q["emb"]["codes"].shape == (CFG.hash_space, CFG.k)
    assert q["emb"]["codes"].dtype == np.int8
    assert q["emb"]["scale"].shape == q["emb"]["zero"].shape == (
        CFG.hash_space,)
    # the cross and deep weights stay float32
    assert q["cross"]["w0"].dtype == np.float32


def test_prefix_hits_at_every_checkpoint_depth():
    """A context shared to depth 0..3 with one seen before: the trie
    reuses the deepest cached prefix (stride 1: every depth is a
    checkpoint), and the answers do not move."""
    eng = _engine(prefix_stride=1)
    rng = np.random.default_rng(2)
    first = _request(rng, 11)
    eng.score_batch([first])
    reqs = []
    for depth in range(FC + 1):
        ci, cv = first[0].copy(), first[1].copy()
        ci[depth:] = rng.integers(0, CFG.hash_space, FC - depth)
        reqs.append(_request(rng, 9, (ci, cv)))
    for r in reqs:      # one a call: each looks up what the last inserted
        got = eng.score_batch([r])
        _assert_close(got, _reference(eng.params, [r]), KERNEL)
    hits = eng.prefix_hit_depths
    assert [hits[d] for d in range(1, FC + 1)] == [1] * FC
    assert hits[0] == 2      # the first request and the fresh context
    eng.close()


def test_repeated_candidates_are_scored_once():
    eng = _engine()
    rng = np.random.default_rng(3)
    a, b = _request(rng, 20), _request(rng, 20)
    ctx = (a[0], a[1])
    reqs = [a, (*ctx, a[2][:12], a[3][:12]),     # a's context and ads again
            b, (*ctx, b[2], b[3])]               # b's ads under a's context
    got = eng.score_batch(reqs)
    assert eng.stats.rows_scored == 20 + 20 + 20
    assert eng.stats.candidates == 72
    _assert_close(got, _reference(eng.params, reqs), KERNEL)
    np.testing.assert_array_equal(got[1], got[0][:12])
    eng.close()


def test_slates_across_bucket_edges_compile_nothing_after_warmup():
    cfg = CFG.replace(hash_space=2048)   # its own jit cache entries
    eng = InferenceEngine(cfg, "dcnv2", backend="pallas", quantized=True,
                          params=_params(cfg=cfg), parallel=2)
    eng.warmup(max_requests=4, max_candidates=32)
    warmed = dcn_candidates_forward._cache_size()
    rng = np.random.default_rng(4)
    reqs = [_request(rng, n, v=cfg.hash_space)
            for n in (7, 8, 9, 16, 17, 32)]
    got = eng.score_batch(reqs)
    assert dcn_candidates_forward._cache_size() == warmed
    _assert_close(got, _reference(eng.params, reqs, cfg), KERNEL)
    eng.close()


def test_kernel_weights_are_built_once_per_published_params(monkeypatch):
    built = []
    kernel_weights = dcn_ops.kernel_weights

    def counted(*a, **kw):
        built.append(1)
        return kernel_weights(*a, **kw)

    monkeypatch.setattr(dcn_ops, "kernel_weights", counted)
    eng = _engine()
    assert len(built) == 1                 # at construction
    rng = np.random.default_rng(5)
    for _ in range(2):
        eng.score_batch([_request(rng, 5), _request(rng, 12)])
    assert len(built) == 1
    # one forward call a batch: 2 rows (a power of two) x the 16-slot
    # bucket, which the kernel's candidate tile divides
    assert eng.stats.slots_scored == 2 * 2 * 16
    assert dcn_ops.tile(16) == (16, 16)
    eng.install_params(_params(seed=1))
    assert len(built) == 2
    reqs = [_request(rng, 7)]
    _assert_close(eng.score_batch(reqs), _reference(eng.params, reqs), KERNEL)
    assert len(built) == 2
    ref = _engine(backend="reference")
    ref.score_batch(reqs)
    assert len(built) == 2                 # no kernel on that path
    eng.close()
    ref.close()


def test_resident_twin_scores_as_host_tables(monkeypatch):
    host = _engine()
    rng = np.random.default_rng(6)
    reqs = [_request(rng, n) for n in (10, 3)]
    want = host.score_batch(reqs)
    host.close()
    monkeypatch.setattr(engine_mod, "_HOST_MEMORY_BACKENDS", ())
    eng = _engine()
    got = eng.score_batch(reqs)
    assert eng.stats.table_uploads == 1
    # only the candidate blocks and context states cross per call
    assert eng.stats.host_arg_bytes < Q.quantized_nbytes(eng.params["emb"])
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    eng.close()


def test_context_state_prefix_is_a_slice():
    params = Q.quantize_params_rows(_params())
    rng = np.random.default_rng(7)
    ci = rng.integers(0, CFG.hash_space, FC).astype(np.int32)
    cv = rng.uniform(0.5, 2.0, FC).astype(np.float32)
    empty = dcnv2.empty_state_np(CFG)
    full = dcnv2.extend_state_np(CFG, params["emb"], empty, ci, cv)
    assert full["x0"].shape == (FC, CFG.k)
    for depth in range(FC + 1):
        part = dcnv2.extend_state_np(CFG, params["emb"], empty, ci[:depth],
                                     cv[:depth])
        np.testing.assert_array_equal(dcnv2.slice_state(full, depth)["x0"],
                                      part["x0"])
        again = dcnv2.extend_state_np(CFG, params["emb"], part, ci[depth:],
                                      cv[depth:])
        np.testing.assert_array_equal(again["x0"], full["x0"])


@pytest.mark.parametrize("n", [5, 8, 20])
def test_kernel_matches_its_reference(n):
    """``dcn_cross_q8`` in interpret mode against ``ref.py``: to float32
    sum order where the reference mirrors its three bfloat16 passes, and
    within ``KERNEL`` of the exact products."""
    rng = np.random.default_rng(8 + n)
    params = _params()
    r, k = 3, CFG.k
    x0c = jnp.asarray(rng.normal(0, 0.3, (r, FC * k)), jnp.float32)
    codes = jnp.asarray(rng.integers(-127, 128, (r, n, FCAND, k)), jnp.int8)
    scale = jnp.asarray(rng.uniform(1e-3, 4e-3, (r, n, FCAND)), jnp.float32)
    zero = jnp.asarray(rng.uniform(-0.05, 0.05, (r, n, FCAND)), jnp.float32)
    val = jnp.asarray(rng.uniform(0.5, 2.0, (r, n, FCAND)), jnp.float32)
    cross, mlp = params["cross"], params["mlp"]
    p = (jnp.dot(x0c, cross["w0"][:FC * k], precision="highest")
         + cross["b0"])
    weights = dcn_ops.kernel_weights(cross, mlp, n_ctx=FC, n_cand=FCAND, k=k)
    got = np.asarray(dcn_ops.dcn_cross_q8(x0c, p, codes, scale, zero, val,
                                          weights, block_n=8))
    mirror = np.asarray(dcn_ref.cross_logits(x0c, codes, scale, zero, val,
                                             cross, mlp, mirror=True))
    exact = np.asarray(dcn_ref.cross_logits(x0c, codes, scale, zero, val,
                                            cross, mlp))
    scale_ = max(float(np.abs(exact).max()), 1.0)
    assert got.shape == (r, n)
    np.testing.assert_allclose(got, mirror, rtol=0, atol=EXACT * scale_)
    np.testing.assert_allclose(got, exact, rtol=0, atol=KERNEL * scale_)


def test_fleet_paths_refuse_the_head():
    eng = _engine()
    for call in (lambda: eng.update_pipe(), lambda: eng.apply_update(b""),
                 lambda: eng.submit_update(b"")):
        with pytest.raises(ValueError, match="update pipe"):
            call()
    eng.close()
    with pytest.raises(ValueError, match="ShardRouter"):
        ShardRouter(CFG, "dcnv2", n_shards=2, params=_params())
    with pytest.raises(ValueError, match="fused"):
        _engine(fused=True)
    with pytest.raises(ValueError, match="host pre-gather"):
        _engine(host_gather=True)
    with pytest.raises(ValueError, match="n_cross"):
        InferenceEngine(CFG.replace(n_cross=0), "dcnv2", params=_params())
    with pytest.raises(ValueError, match="'dcnv2'"):
        TrainingPipeline(CFG, "dcnv2")


def test_uncached_oracle_is_the_reference():
    eng = _engine()
    rng = np.random.default_rng(9)
    ci, cv, ki, kv = _request(rng, 6)
    got = np.asarray(eng.score_uncached(ci, cv, ki, kv))
    _assert_close([got], _reference(eng.params, [(ci, cv, ki, kv)]), EXACT)
    eng.close()
