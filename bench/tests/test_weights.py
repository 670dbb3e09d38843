"""The tables made on the device, a chunk at a time, are the rows the
reference regenerates from the seed, bit for bit."""
import numpy as np
import pytest

from bench.lib import weights

CFG = {"n_fields": 4, "context_fields": 2, "k": 2, "head": "ffm"}


@pytest.mark.parametrize("hash_space,chunk", [(1000, 256), (4096, 4096)],
                         ids=["ragged_chunks", "one_chunk"])
def test_chunked_tables_match_the_reference_rows(monkeypatch, hash_space,
                                                 chunk):
    monkeypatch.setattr(weights, "CHUNK_ROWS", chunk)
    cfg = {**CFG, "hash_space": hash_space}
    seed = 2 ** 33 + 5
    t = weights.make_tables(cfg, seed)
    codes = t["codes"].reshape(hash_space, 4, 2).astype(np.float32)
    emb = codes * t["scale"][:, None, None] + t["zero"][:, None, None]
    blk = np.arange(hash_space) // weights.LR_BLOCK
    lr = (t["lr_codes"].astype(np.float32) * t["lr_scale"][blk]
          + t["lr_zero"][blk])
    want_emb, want_lr = weights.table_rows(cfg, seed, np.arange(hash_space))
    np.testing.assert_array_equal(emb, want_emb)
    np.testing.assert_array_equal(lr, want_lr)
    assert t["lr_scale"].size == -(-hash_space // weights.LR_BLOCK)
