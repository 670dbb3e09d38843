"""The tables made on the device, a chunk at a time, are the rows the
reference regenerates from the seed, bit for bit."""
import numpy as np
import pytest

from bench.lib import weights

WIDTH = 8   # the FFM family's F * k at F=4, k=2


@pytest.mark.parametrize("hash_space,chunk", [(1000, 256), (4096, 4096)],
                         ids=["ragged_chunks", "one_chunk"])
def test_chunked_tables_match_the_reference_rows(monkeypatch, hash_space,
                                                 chunk):
    monkeypatch.setattr(weights, "CHUNK_ROWS", chunk)
    seed = 2 ** 33 + 5
    t = weights.make_tables(seed, hash_space, WIDTH)
    codes = t["codes"].reshape(hash_space, WIDTH).astype(np.float32)
    emb = codes * t["scale"][:, None] + t["zero"][:, None]
    blk = np.arange(hash_space) // weights.LR_BLOCK
    lr = (t["lr_codes"].astype(np.float32) * t["lr_scale"][blk]
          + t["lr_zero"][blk])
    want_emb, want_lr = weights.table_rows(seed, np.arange(hash_space),
                                           WIDTH)
    np.testing.assert_array_equal(emb, want_emb)
    np.testing.assert_array_equal(lr, want_lr)
    assert t["lr_scale"].size == -(-hash_space // weights.LR_BLOCK)
