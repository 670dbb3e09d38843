"""Operation, byte and FLOP counts against hand counts at a tiny size, and
the call layout against the engine's own counter."""
import numpy as np

from bench.lib import layout, spec

TINY = {"n_fields": 4, "context_fields": 2, "k": 2, "mlp_hidden": [3],
        "reference": "ffm_family"}
FFM = spec.family(TINY)


def test_model_flops_hand_count():
    # F=4, k=2: P=6 pairs. LR 2*4 = 8; pair dots 6*(2*2) = 24 plus two
    # value products each, 12; head sum 6: ffm = 50.
    assert FFM.flops_per_prediction({**TINY, "head": "ffm"}) == 50
    # deepffm adds MergeNorm over d=7 (7*7 + 1 = 50), the MLP 7->3->1
    # (2*7*3 + 3 = 45, 2*3*1 + 1 = 7) and the shortcut add (1): 153.
    assert FFM.flops_per_prediction({**TINY, "head": "deepffm"}) == 153


def test_config_flops_match_their_sizes():
    bench = spec.load()
    for c in bench["configs"]:
        cfg = spec.config(bench, c["name"])
        family = spec.family(cfg)
        assert cfg["flops_per_prediction"] == family.flops_per_prediction(cfg)
    # PROD_FFM widths: F=24, k=8, P=276, MLP 277->64->32->1
    assert FFM.flops_per_prediction(
        {"n_fields": 24, "k": 8, "head": "ffm", "mlp_hidden": []}) == 5292
    assert FFM.flops_per_prediction(
        {"n_fields": 24, "k": 8, "head": "deepffm",
         "mlp_hidden": [64, 32]}) == 46946


def test_candidate_kernel_hand_count():
    # fc=2 context fields, fa=2 candidate fields, k=2; 3 rows x 5 cands.
    # per row: ctx block 2*2*2 f32 = 32 B, ctx values 2 f32 = 8 B
    # per cand: codes 2*2*2 + 2*2*2 int8 = 16 B, scale/zero 2+2 f32 = 16 B,
    #   values 2+2 f32 = 16 B, xc out 2*2 f32 = 16 B, aa out 16 B: 80 B
    # ops per cand: dequantize 16 elements (mul+add) = 32; ctx-cand dots
    #   4 pairs * 2k = 16 + values 8; cand-cand dots 16 + values 8: 80
    c = spec.kernel("ffm_candidate_matrices_q8").cost(3, 5, TINY)
    assert c == {"float_ops": 15 * 80, "int8_ops": 0,
                 "bytes": 3 * 40 + 15 * 80}


def test_fused_kernel_hand_count():
    # per row: ctx block 2*4*2 f32 = 64 B, values col+row 16 B, ctx pair
    #   matrix out 2*2 f32 = 16 B: 96 B; ctx tail pairs 4*2k = 16 + 8 = 24
    # per cand: base 4 B, codes 8 + 8 B, grids 4*2 f32 = 32 B, values 16 B,
    #   logit 4 B: 72 B
    # float ops per cand: ctx-cand dots 4*2k = 16, affine 3*4 = 12, values
    #   and sum 3*4 = 12, cand-cand affine 10*4 = 40, its sum 3*4 = 12, the
    #   three adds into the logit 3: 95; int8 ops: 4 dots of k products and
    #   adds (16) plus two row sums of k adds per pair (16): 32
    c = spec.kernel("ffm_fused_logits_q8").cost(3, 5, TINY)
    assert c == {"float_ops": 3 * (24 + 5 * 95), "int8_ops": 15 * 32,
                 "bytes": 3 * 96 + 15 * 72}


def test_bucket_and_spans():
    assert [layout.bucket(n, 8) for n in (1, 8, 9, 300)] == [8, 8, 16, 512]
    assert layout.split_spans(7, 4) == [2, 2, 2, 1]
    assert layout.split_spans(3, 4) == [1, 1, 1]
    assert layout.split_spans(5, 1) == [5]


def test_layout_matches_the_engine():
    import jax

    cfg = {"n_fields": 24, "context_fields": 16, "k": 8, "hash_space": 4096,
           "head": "ffm", "reference": "ffm_family",
           "engine": {"backend": "reference", "quantized": True}}
    rng = np.random.default_rng(0)
    ctx = [(rng.integers(0, 4096, 16).astype(np.int32),
            np.ones(16, np.float32)) for _ in range(3)]
    ads = rng.integers(0, 4096, (40, 8)).astype(np.int32)
    reqs = []
    for i, n in enumerate((9, 30, 17, 30, 5)):
        ci, cv = ctx[i % 3]      # 0 and 3, 1 and 4 share a context
        a = ads[rng.integers(0, 40, n)]  # repeated ads within and across
        reqs.append((ci, cv, a, np.ones_like(a, np.float32)))
    family = spec.family(cfg)
    for workers in (1, 4):
        eng = family.build_engine(cfg, family.engine_params(cfg, 3))
        eng.parallel = workers
        before = eng.stats.rows_scored
        eng.score_batch(reqs)
        nb, rows, spans = layout.call_layout(
            reqs, min_bucket=eng.plan.min_bucket, workers=workers)
        assert rows == eng.stats.rows_scored - before
        assert nb == 32 and sum(m for _, m in spans) >= 3  # 3 contexts
        assert all(rb == layout.bucket(m, 1) for rb, m in spans)
        eng.close()
    jax.clear_caches()
