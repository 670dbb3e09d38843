"""The DCN-v2 family (``bench/families/dcnv2_family.py``), its reference and
its kernel's counts, beside the FFM family it must leave as it was."""
import hashlib
import json

import numpy as np
import pytest

from bench.lib import spec, weights
from bench.tests import test_families

BENCH = spec.load()
CFG = spec.config(BENCH, "dcnv2_criteo")
DCN = spec.family(CFG)
FFM = spec.family(spec.config(BENCH, "deepffm_prod"))
TINY = {"n_fields": 4, "context_fields": 2, "k": 3, "n_cross": 2,
        "mlp_hidden": [5], "hash_space": 4096, "head": "dcnv2",
        "reference": "dcnv2_family"}

# SHA-256 of the FFM family's recorded digests (test_families.DIGESTS): the
# FFM tables, heads and reference logits are the ones recorded before
# this family existed
FFM_DIGESTS_SHA = (
    "63487167b035af42a047afc0261736ec2efdaf1b968a9485716071d3c2b307cb")


def test_flops_per_prediction_hand_count():
    # D = 12: x0 12; two cross layers 2 * (2 * 144 + 3 * 12) = 648;
    # deep 12 -> 5 (2 * 60 + 5 = 125) -> 1 (2 * 5 + 1 = 11): 796
    assert DCN.flops_per_prediction(TINY) == 796
    assert CFG["flops_per_prediction"] == DCN.flops_per_prediction(CFG)
    # the Criteo widths: D = 1,521, three cross layers, deep 768-768
    assert CFG["flops_per_prediction"] == (
        1521 + 3 * (2 * 1521 ** 2 + 3 * 1521)
        + (2 * 1521 * 768 + 768) + (2 * 768 * 768 + 768) + (2 * 768 + 1))


def test_cross_kernel_hand_count():
    # D = 12 pads to 128 lanes, context 6 lanes (layer 0 from lane 0), the
    # factor block 4 * 2 columns to 128, the deep width 5 to 128.
    # per slot: spread 2 * 128 * 256 = 65,536; dequantize 3 * 128 = 384;
    #   layer 0 and layer 1 each 3 passes * 2 * 128 * 128 + 3 * 128 = 98,688;
    #   deep 3 * 2 * 128 * 128 + 128 = 98,432; output 2 * 128 + 1 = 257
    # weights: spread 128 * 256 * 2 B; w0 hi + lo 128 * 128 * 4 B; w1 the
    #   same plus b1 128 * 4 B; deep the same; output 128 * 4 + 4 B
    # per row x0c and p, 2 * 128 * 4 B; per slot codes 128 B, factors
    #   128 * 2 B, the logit 4 B
    per_slot = 65536 + 384 + 2 * 98688 + 98432 + 257
    w_bytes = 65536 + 65536 + 66048 + 66048 + 516
    c = spec.kernel("dcn_cross_q8").cost(3, 5, TINY)
    assert c == {"float_ops": 15 * per_slot, "int8_ops": 0,
                 "bytes": w_bytes + 3 * 1024 + 15 * 388}


def test_reference_is_the_program_model():
    """The bench's reference equals ``repro.core.dcnv2.forward`` over the
    same seeded rows and weights."""
    import jax.numpy as jnp

    from repro.common.config import FFMConfig
    from repro.core import dcnv2

    cfg = {**CFG, "hash_space": 4096}
    seed = 2 ** 33 + 7
    idx, val = test_families._sample(cfg)
    want = spec.reference(cfg).logits(cfg, seed, idx, val)
    params = {"emb": DCN.table_rows(cfg, seed, np.arange(4096)),
              **DCN.head_params(cfg, seed)}
    fcfg = FFMConfig(n_fields=39, context_fields=16, hash_space=4096, k=39,
                     mlp_hidden=(768, 768), n_cross=3)
    got = np.asarray(dcnv2.forward(fcfg, params, jnp.asarray(idx),
                                   jnp.asarray(val)))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


@pytest.mark.parametrize("seed", [5, 2 ** 40 + 1])
def test_logits_of_a_trained_rankers_size(seed):
    """Under the cell's traffic the seeded scales give logits of a few
    units."""
    from bench.lib import harness, traffic
    from bench.tests import test_faults

    cfg, mix = test_faults._small("dcnv2.sessions_max")
    reqs = traffic.generate(mix, cfg, seed, 24)
    want = spec.reference(cfg).logits(
        cfg, seed, *harness.feature_rows(reqs, list(range(24))))
    assert 0.5 < float(np.sqrt(np.mean(want ** 2))) < 4.0
    assert float(np.max(np.abs(want))) < 16.0


def test_engine_tables_are_the_reference_rows():
    cfg = {**CFG, "hash_space": 4096}
    seed = 2 ** 32 + 3
    emb = DCN.engine_params(cfg, seed)["emb"]
    assert emb["codes"].shape == (4096, 39) and emb["codes"].dtype == np.int8
    got = (emb["codes"].astype(np.float32) * emb["scale"][:, None]
           + emb["zero"][:, None])
    np.testing.assert_array_equal(
        got, DCN.table_rows(cfg, seed, np.arange(4096)))


def test_head_streams_are_their_own():
    dcn = set(DCN.HEAD_STREAMS.values())
    assert not dcn & set(weights.STREAMS.values())
    ffm_fixed = {v for k, v in FFM.HEAD_STREAMS.items() if k != "mlp"}
    assert not dcn & ffm_fixed
    # the FFM family's MLP takes every id from its base on
    assert max(dcn) < FFM.HEAD_STREAMS["mlp"]


# One operation line of each kernel as the TPU trace names it, and one
# that is no kernel
OPS = {
    "ffm_candidate_matrices_q8":
        '%candidate_interactions_q8.1 = (f32[4,64,16,8]{3,2,1,0}, '
        'f32[4,64,8,8]{3,2,1,0}) custom-call(%copy.11), '
        'custom_call_target="tpu_custom_call"',
    "ffm_fused_logits_q8":
        '%fused_candidate_logits_q8.2 = (f32[4,64]{1,0}, f32[4,16,16]{2,1,0}) '
        'custom-call(%copy.3), custom_call_target="tpu_custom_call"',
    "dcn_cross_q8":
        '%dcn_cross_q8.1 = f32[16,1024,1]{2,1,0:T(8,128)} '
        'custom-call(%broadcast_in_dim.0), custom_call_target="tpu_custom_call"',
    None: '%fusion.6 = s8[16,1024,23,39]{3,2,1,0} fusion(%param_0.1)',
}


@pytest.mark.parametrize("kernel", list(OPS), ids=lambda k: str(k))
def test_trace_names_match_only_their_own_kernel(kernel):
    pats = spec.kernel_patterns()
    assert set(k for k in OPS if k) <= set(pats)
    matched = [k for k, p in pats.items() if all(s in OPS[kernel] for s in p)]
    assert matched == ([kernel] if kernel else [])


def test_ffm_digests_are_untouched():
    text = json.dumps(sorted((f"{c}-{s}", v) for (c, s), v in
                             test_families.DIGESTS.items()))
    assert hashlib.sha256(text.encode()).hexdigest() == FFM_DIGESTS_SHA


def test_one_pass_kernel_is_not_correct(monkeypatch):
    """The kernel at one bfloat16 pass a product, the precision below the
    three passes the configuration states, reads outside the
    configuration's limit through the harness's own comparison (the cell's
    CPU run of ``test_faults``)."""
    import jax

    from bench.tests import test_faults
    from repro.kernels.dcn_cross import dcn_cross

    def one_pass(a, w_hi, w_lo):
        return dcn_cross._mxu(a.astype(dcn_cross.MXU_DTYPE), w_hi)

    monkeypatch.setattr(dcn_cross, "dot3", one_pass)
    jax.clear_caches()        # no program traced with three passes is reused
    try:
        res = test_faults._run("dcnv2.sessions_max")
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    dev = res["checks"]["max_abs_dev"]
    assert not res["correct"] and dev["value"] > dev["limit"], res["checks"]
