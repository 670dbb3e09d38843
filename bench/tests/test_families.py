"""Model families found by name (``bench.lib.spec.family``).

The FFM family makes the same tables, head and reference logits as the
benchmark made before its head-specific code moved behind the family, bit
for bit; and a family that lives only in new files, with its own engine and
reference, runs through the harness with no edit to an existing file.
"""
import hashlib
import textwrap
import time

import numpy as np
import pytest

from bench.lib import harness, spec, traffic

BENCH = spec.load()

# SHA-256 of (engine params, reference logits of ``_sample``) at
# hash_space 4096, recorded from the benchmark's code before the FFM
# family's params, FLOP count and engine moved into bench/families/
DIGESTS = {
    ("deepffm_prod", 11): (
        "a273416460c42836d2cad030b9e2b53f6b32e26d4172878ca45b525c9128693f",
        "5402da37c36c3e265bedc945e2ed3d3211c3915d115576fd5f4782d8c565accf"),
    ("deepffm_prod", 2 ** 33 + 5): (
        "da53395f2249c1e5258891b2fc6f665e4f70199f7315533a1a94fdb862025fa4",
        "36a1113c6325cc382f4d3ae3ff366d649f5d518900304d4160756b4b494487f7"),
    ("ffm_prod", 11): (
        "2ba4214831fe376d7b8012047facc2e90ef9cc0cb5f8a6fe5862affa1264259b",
        "bff9eb5013c0e9fbb79cf1dcb011ae0408b1b5d48cc59d5e012145c2a6456861"),
    ("ffm_prod", 2 ** 33 + 5): (
        "dd4c82156a574dbe964a4b1918f0ce7baa6678cb79a219b81de75a9d86151d11",
        "e3c5ade3f9fcd4246d6dbefa4b7468c4bcce885c9d4ad229e58e5512942e6674"),
}


def _tree_digest(tree) -> str:
    """SHA-256 over every leaf in key order: its path, dtype, shape and
    bytes (a Python scalar by its repr)."""
    h = hashlib.sha256()

    def walk(path, x):
        if isinstance(x, dict):
            for k in sorted(x):
                walk(f"{path}/{k}", x[k])
            return
        h.update(path.encode())
        if isinstance(x, np.ndarray):
            h.update(f"{x.dtype.str}{x.shape}".encode())
            h.update(np.ascontiguousarray(x).tobytes())
        else:
            h.update(repr(x).encode())

    walk("", tree)
    return h.hexdigest()


def _sample(cfg):
    """48 fixed feature rows, values in quarters."""
    rng = np.random.default_rng(0)
    idx = rng.integers(0, cfg["hash_space"],
                       (48, cfg["n_fields"])).astype(np.int32)
    val = (rng.integers(1, 9, idx.shape) / 4).astype(np.float32)
    return idx, val


@pytest.mark.parametrize("config,seed", sorted(DIGESTS),
                         ids=[f"{c}-{s}" for c, s in sorted(DIGESTS)])
def test_ffm_family_is_bit_identical(config, seed):
    cfg = {**spec.config(BENCH, config), "hash_space": 4096}
    params = spec.family(cfg).engine_params(cfg, seed)
    logits = spec.reference(cfg).logits(cfg, seed, *_sample(cfg))
    want_params, want_logits = DIGESTS[(config, seed)]
    assert _tree_digest(params) == want_params
    got = hashlib.sha256(np.asarray(logits, np.float32).tobytes())
    assert got.hexdigest() == want_logits


# A family of its own: an LR-only head, scored by a numpy engine with what
# the harness reads of an engine, and a reference that recomputes it.
STUB_FAMILY = '''
    """An LR-only head served by a numpy engine."""
    from types import SimpleNamespace

    import numpy as np

    from bench.lib import weights

    HEAD_STREAMS = {"lr_b": 1024}


    def table_rows(cfg, seed, rows, xp=np):
        return weights.table_rows(seed, rows, 1, xp)[1]


    def head_params(cfg, seed):
        return {"lr_b": weights.head_tensor(seed, HEAD_STREAMS["lr_b"], (),
                                            2.0 ** -3)}


    def engine_params(cfg, seed):
        t = weights.make_tables(seed, cfg["hash_space"], 1)
        blk = np.arange(cfg["hash_space"]) // weights.LR_BLOCK
        w = (t["lr_codes"].astype(np.float32) * t["lr_scale"][blk]
             + t["lr_zero"][blk])
        return {"w": w, "b": head_params(cfg, seed)["lr_b"]}


    class Engine:
        parallel = 1
        dedup = True

        def __init__(self, params):
            self.params = params
            self.plan = SimpleNamespace(min_bucket=8)
            self.stats = SimpleNamespace(rows_scored=0, ctx_tail_fields=0,
                                         requests=0)

        def warmup(self, max_requests, max_candidates):
            pass

        def score_batch(self, reqs):
            w, b = self.params["w"], self.params["b"]
            out = []
            for ci, cv, ki, kv in reqs:
                out.append(b + np.sum(w[ci] * cv)
                           + np.sum(w[ki] * kv, axis=1))
                self.stats.rows_scored += len(ki)
                self.stats.ctx_tail_fields += len(ci)
                self.stats.requests += 1
            return out

        def close(self):
            pass


    def build_engine(cfg, params):
        return Engine(params)


    def flops_per_prediction(cfg):
        return 2 * cfg["n_fields"]
'''

STUB_REFERENCE = '''
    """The LR head in float64, from the seed's rows."""
    import numpy as np

    from bench.lib import spec


    def logits(cfg, seed, idx, val):
        family = spec.family(cfg)
        w = family.table_rows(cfg, seed, idx.reshape(-1)).reshape(idx.shape)
        b = family.head_params(cfg, seed)["lr_b"]
        z = float(b) + np.sum(w.astype(np.float64) * val, axis=1)
        return z.astype(np.float32)
'''


def _answer_altered(score_batch):
    def score(reqs):
        out = [np.array(o) for o in score_batch(reqs)]
        out[0][0] += 0.5
        return out
    return score


@pytest.mark.parametrize("fault", [None, _answer_altered],
                         ids=["sound", "answer_altered"])
def test_a_family_in_new_files_runs_through_the_harness(tmp_path,
                                                         monkeypatch, fault):
    for sub, text in (("families", STUB_FAMILY),
                      ("references", STUB_REFERENCE)):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / "lr_stub.py").write_text(textwrap.dedent(text))
    monkeypatch.setattr(spec, "FAMILIES_DIR", str(tmp_path / "families"))
    monkeypatch.setattr(spec, "REFERENCES_DIR", str(tmp_path / "references"))
    cfg = {"name": "lr_stub", "reference": "lr_stub", "n_fields": 24,
           "context_fields": 16, "hash_space": 4096,
           "checks": {"max_abs_dev": 1e-4, "sample_requests": 16,
                      "min_predictions": 64}}
    bench = {"workloads": [{"name": "lr.sessions", "config": "lr_stub",
                            "traffic": "sessions_open", "chips": 1}],
             "end_to_end": [
                 {k: v for k, v in m.items() if k != "workloads"}
                 for m in BENCH["end_to_end"]
                 if m["name"] in ("p90_ms", "setup_s")],
             "per_layer": []}
    mix = {**traffic.load_mix("sessions_open"), "rate_per_s": 30,
           "max_batch": 8, "sessions": 200, "inventory": 2000,
           "candidates": {"median": 24, "sigma": 0.7, "min": 8, "max": 64}}
    res = harness.run_cell(bench, "lr.sessions", 2 ** 32 + 29, 2.0, False,
                           t_process=time.perf_counter(), cfg=cfg, mix=mix,
                           fault=fault)
    assert spec.family(cfg).__file__.startswith(str(tmp_path))
    assert set(res["metrics"]) == {"p90_ms", "setup_s"}
    assert res["checks"]["compared_predictions"]["value"] >= 64
    if fault is None:
        assert res["correct"], res["checks"]
        assert res["checks"]["max_abs_dev"]["value"] < 1e-5
    else:
        assert not res["correct"], res["checks"]
        assert (res["checks"]["max_abs_dev"]["value"]
                > cfg["checks"]["max_abs_dev"])
