"""The main-path Pallas kernels compile for a TPU v5e at production widths.

Each test lowers one kernel for a described (not attached) v5e chip and
compiles it with the TPU compiler, which refuses what the chip would refuse:
blocks that break the tile rule, scoped VMEM over its limit, unsupported
layouts. Interpret-mode parity tests cannot see any of that. Shapes are
``PROD_FFM``'s (24 fields, 16 of them context, k=8, a 2^22-row table) with
R=16 request rows x N=256 candidates, and B=512 examples for the full
interaction matrix.

The topology is described inside a module fixture: the TPU library may be
loaded by one process at a time, so nothing here touches it at import.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.common.config import PROD_FFM
from repro.kernels.ffm_interaction import ffm_interaction as K
from repro.kernels.row_gather.row_gather import gather_dequant_rows_q8

F, FC, KK, V = (PROD_FFM.n_fields, PROD_FFM.context_fields, PROD_FFM.k,
                PROD_FFM.hash_space)
FCAND = F - FC
R, N, B = 16, 256, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # without it the TPU compiler writes its logs outside the checkout
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back without the chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


f32, i8, i32 = jnp.float32, jnp.int8, jnp.int32


def test_interaction_matrix_compiles(one_chip):
    txt = _compile(lambda e, v: K.ffm_interaction_matrix(e, v, interpret=False),
                   one_chip, ((B, F, F, KK), f32), ((B, F), f32))
    assert "tpu_custom_call" in txt


def test_candidate_matrices_compiles(one_chip):
    txt = _compile(
        lambda *a: K.ffm_candidate_matrices(*a, interpret=False), one_chip,
        ((R, FC, FCAND, KK), f32), ((R, FC), f32),
        ((R, N, FCAND, FC, KK), f32), ((R, N, FCAND, FCAND, KK), f32),
        ((R, N, FCAND), f32))
    assert "tpu_custom_call" in txt


def test_candidate_matrices_q8_compiles(one_chip):
    txt = _compile(
        lambda *a: K.ffm_candidate_matrices_q8(*a, interpret=False), one_chip,
        ((R, FC, FCAND, KK), f32), ((R, FC), f32),
        ((R, N, FCAND, FC, KK), i8), ((R, N, FCAND, FCAND, KK), i8),
        ((R, N, FCAND), f32), ((R, N, FCAND), f32), ((R, N, FCAND), f32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("kind", ["q8", "rows"])
def test_fused_logits_compiles(one_chip, kind):
    head = [((R, FC, F, KK), f32), ((R, FC), f32), ((R,), i32), ((R, N), f32)]
    if kind == "q8":
        fn = K.ffm_fused_logits_q8
        cands = [((R, N, FCAND, FC, KK), i8), ((R, N, FCAND, FCAND, KK), i8),
                 ((R, N, FCAND), f32), ((R, N, FCAND), f32)]
    else:
        fn = K.ffm_fused_logits_rows
        cands = [((R, N, FCAND, FC, KK), f32), ((R, N, FCAND, FCAND, KK), f32)]
    txt = _compile(lambda *a: fn(*a, interpret=False), one_chip,
                   *head, *cands, ((R, N, FCAND), f32))
    assert "tpu_custom_call" in txt


def test_row_gather_q8_compiles(one_chip):
    txt = _compile(
        lambda *a: gather_dequant_rows_q8(*a, interpret=False), one_chip,
        ((V, F, KK), i8), ((V,), f32), ((V,), f32), ((R, N, FCAND), i32))
    assert "tpu_custom_call" in txt


def test_dcn_cross_q8_compiles(one_chip):
    """The DCN-v2 cross kernel at the ``dcnv2_criteo`` widths (39 fields of
    width 39, 16 of them context, three cross layers, deep 768-768): its
    split weights fit the kernel's VMEM limit in one buffer each."""
    from repro.kernels.dcn_cross import ops as dcn_ops

    fc, fa, k, d = 16, 23, 39, 39 * 39

    def s(shape, dt=f32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    cross = {f"{n}{l}": s((d, d) if n == "w" else (d,))
             for l in range(3) for n in "wb"}
    dims = (d, 768, 768, 1)
    mlp = {}
    for i in range(3):
        mlp[f"w{i}"], mlp[f"b{i}"] = s(dims[i:i + 2]), s((dims[i + 1],))
    cands = (R, N, fa)
    def call(x0c, p, codes, scale, zero, val, cross, mlp):
        weights = dcn_ops.kernel_weights(cross, mlp, n_ctx=fc, n_cand=fa, k=k)
        return dcn_ops.dcn_cross_q8(x0c, p, codes, scale, zero, val, weights,
                                    interpret=False)

    txt = jax.jit(call).lower(
        s((R, fc * k)), s((R, d)), s(cands + (k,), i8), s(cands), s(cands),
        s(cands), cross, mlp).compile().as_text()
    assert "tpu_custom_call" in txt and "%dcn_cross_q8" in txt
