"""CPU checks of the pieces that put the main path on the chip: the
platform's Pallas interpret flag, the persistent compile-cache directory,
the gather choice on TPU, and ``chip_smoke.py`` — its phases end to end at a
tiny configuration with oracle parity, and its refusal to report success
without a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.common import compile_cache
from repro.common.config import FFMConfig
from repro.kernels import platform
from repro.kernels.row_gather import ops as rg_ops

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", None), ("rocm", None)])
def test_interpret_mode_follows_platform(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(ValueError, match=backend):
            platform.interpret_mode()
    else:
        assert platform.interpret_mode() is want
    # an explicit flag is never overridden
    assert platform.interpret_mode(False) is False
    assert platform.interpret_mode(True) is True


def test_interpret_mode_on_this_host_is_interpreter():
    assert jax.default_backend() == "cpu"
    assert platform.interpret_mode() is True


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)


def test_compile_cache_dir_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.cache_dir()
    assert first == compile_cache.cache_dir() == str(ROOT / ".jax_cache")
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_tpu_gather_choice_ignores_timing_probe(monkeypatch):
    """On TPU the quantized gather is the Pallas kernel at any table size,
    and neither the host-gather policy nor the gather runs the probe."""
    def probe(*a, **k):
        raise AssertionError("timing probe ran")

    chosen = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(rg_ops, "_calibrated", None)
    monkeypatch.setattr(rg_ops, "calibrate_cliff_rows", probe)
    monkeypatch.setattr(rg_ops, "gather_dequant_rows_q8",
                        lambda *a, **k: chosen.append(a[0].shape) or "pallas")
    assert not rg_ops.use_host_gather(2 ** 22)
    for rows in (64, 2 ** 22):
        table = {"codes": jax.ShapeDtypeStruct((rows, 3, 2), "int8"),
                 "scale": None, "zero": None}
        assert rg_ops.gather_dequant_rows(table, None) == "pallas"
    assert chosen == [(64, 3, 2), (2 ** 22, 3, 2)]


def test_chip_smoke_phases_tiny_on_cpu(capsys):
    """Rehearsal at a tiny configuration: train -> frames -> both engines
    ingest and score -> oracle parity, loose and exact (the phases raise on
    a deviation above tolerance, and when the bf16 control falls inside the
    exact limit)."""
    smoke = _load_smoke()
    cfg = FFMConfig(n_fields=6, context_fields=4, hash_space=2 ** 10, k=4,
                    mlp_hidden=(8,))
    smoke.run(cfg, rounds=2, steps=2, batch=16, n_requests=2,
              n_candidates=8, n_batches=2)
    out = capsys.readouterr().out
    for phase in ("train", "serve[staged]", "compare[staged]",
                  "exact[staged]", "compare[uncached]", "exact[uncached]",
                  "serve[fused]", "compare[fused]", "exact[fused]"):
        assert f"\n{phase}: " in "\n" + out, phase
    assert '"ok"' not in out  # only main() reports success


def test_chip_smoke_refuses_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    res = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "no TPU" in res.stderr
