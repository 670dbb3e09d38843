"""Seeded int8 tables and f32 head weights, made without the program.

Every value is a function of ``(seed, stream, counter)`` through a 32-bit
integer hash, so the whole table is made on the device by one jitted
program and the reference regenerates just the rows it needs, from the
seed alone.
Every value is exactly representable in float32 (small integers times powers
of two), so no order of computation or fusion can change a single bit.

Layout is the serving engine's int8 form (paper §6): embedding rows
``codes (V, F, k) int8`` with a per-row ``(scale, zero)``; the LR vector as
int8 codes with one ``(scale, zero)`` per block of 64; the head (LR bias,
MergeNorm, MLP) in float32.
"""
from __future__ import annotations

import functools

import numpy as np

LR_BLOCK = 64
CHUNK_ROWS = 2 ** 15   # table rows made per device call

# the last MLP layer's scale on top of its fan-in scale: the MLP adds a
# residual of a trained model's size to the wide logit
MLP_OUT_SCALE = 0.25

# one stream id per leaf, so no two leaves share a counter space
STREAMS = {"emb_codes": 1, "emb_scale": 2, "emb_zero": 3, "lr_codes": 4,
           "lr_scale": 5, "lr_zero": 6, "lr_b": 7, "merge_scale": 8,
           "merge_bias": 9, "mlp": 16}


def _mix(x, xp):
    """lowbias32: a bijective avalanche on uint32 (numpy or jax.numpy)."""
    u = xp.uint32
    x = x ^ (x >> u(16))
    x = x * u(0x7FEB352D)
    x = x ^ (x >> u(15))
    x = x * u(0x846CA68B)
    return x ^ (x >> u(16))


def seed_words(seed: int):
    """A seed of any size up to 64 bits as two uint32 words."""
    seed = int(seed) & 0xFFFFFFFFFFFFFFFF
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def stream_key(lo, hi, stream: int, xp):
    u = xp.uint32
    return _mix(u(lo) ^ _mix(u(hi) + u(stream * 0x9E3779B9 & 0xFFFFFFFF), xp),
                xp)


def bits(lo, hi, stream: int, counter, xp):
    """uint32 hash of each counter in one stream of one seed."""
    if xp is np:  # uint32 wrap-around is the point, not an overflow
        with np.errstate(over="ignore"):
            return _mix(counter.astype(np.uint32)
                        ^ stream_key(lo, hi, stream, np), np)
    return _mix(counter.astype(xp.uint32) ^ stream_key(lo, hi, stream, xp), xp)


# -- value maps: integers and powers of two only ------------------------------

def codes_of(h, xp):
    """int8 codes uniform on [-127, 127]."""
    return ((h % xp.uint32(255)).astype(xp.int32) - 127).astype(xp.int8)


def emb_scale_of(h, xp):
    """Row scale in [2^-10, 2^-9): dequantized values reach about 0.25."""
    return ((h >> xp.uint32(24)).astype(xp.float32) + 256.0) * (2.0 ** -18)


def emb_zero_of(h, xp):
    """Row zero point in [-2^-6, 2^-6)."""
    return ((h >> xp.uint32(22)).astype(xp.float32) - 512.0) * (2.0 ** -15)


def lr_scale_of(h, xp):
    """LR block scale in [2^-9, 2^-8)."""
    return ((h >> xp.uint32(24)).astype(xp.float32) + 256.0) * (2.0 ** -17)


def lr_zero_of(h, xp):
    return ((h >> xp.uint32(22)).astype(xp.float32) - 512.0) * (2.0 ** -14)


def signed_unit(h, xp):
    """[-1, 1) in steps of 2^-15."""
    return ((h >> xp.uint32(16)).astype(xp.float32) - 32768.0) * (2.0 ** -15)


# -- the head (small: made on the host, identical for program and reference) --

def mlp_dims(cfg: dict):
    d_in = cfg["n_fields"] * (cfg["n_fields"] - 1) // 2 + 1
    return (d_in,) + tuple(cfg["mlp_hidden"]) + (1,)


def head_params(cfg: dict, seed: int) -> dict:
    """LR bias and, for the deepffm head, MergeNorm and MLP weights.

    MLP layer i is uniform with a fan-in scale (a power of two near
    ``1/sqrt(fan_in)``); the last layer is further scaled by ``MLP_OUT_SCALE``.
    """
    lo, hi = seed_words(seed)

    def leaf(stream, shape, scale, offset=0.0):
        n = int(np.prod(shape)) if shape else 1
        h = bits(lo, hi, stream, np.arange(n, dtype=np.uint32), np)
        v = signed_unit(h, np) * np.float32(scale) + np.float32(offset)
        return v.reshape(shape).astype(np.float32)

    out = {"lr_b": leaf(STREAMS["lr_b"], (), 2.0 ** -3)}
    if cfg["head"] != "deepffm":
        return out
    dims = mlp_dims(cfg)
    out["merge_scale"] = leaf(STREAMS["merge_scale"], (dims[0],), 2.0 ** -3,
                              1.0)
    out["merge_bias"] = leaf(STREAMS["merge_bias"], (dims[0],), 2.0 ** -3)
    mlp = {}
    for i in range(len(dims) - 1):
        fan = 2.0 ** -round(np.log2(np.sqrt(dims[i])))
        if i == len(dims) - 2:
            fan *= MLP_OUT_SCALE
        mlp[f"w{i}"] = leaf(STREAMS["mlp"] + 2 * i, (dims[i], dims[i + 1]),
                            fan)
        mlp[f"b{i}"] = leaf(STREAMS["mlp"] + 2 * i + 1, (dims[i + 1],),
                            2.0 ** -4)
    out["mlp"] = mlp
    return out


# -- the tables ---------------------------------------------------------------

def table_rows(cfg: dict, seed: int, rows, xp=np):
    """Dequantized embedding rows ``(len(rows), F, k)`` and LR weights
    ``(len(rows),)`` of the seed's tables, in float32: what the reference
    gathers. ``rows`` holds hashed feature indices."""
    lo, hi = seed_words(seed)
    f, k = cfg["n_fields"], cfg["k"]
    rows = xp.asarray(rows).astype(xp.uint32)
    cnt = (rows[:, None] * xp.uint32(f * k)
           + xp.arange(f * k, dtype=xp.uint32)[None, :])
    codes = codes_of(bits(lo, hi, STREAMS["emb_codes"], cnt, xp), xp)
    scale = emb_scale_of(bits(lo, hi, STREAMS["emb_scale"], rows, xp), xp)
    zero = emb_zero_of(bits(lo, hi, STREAMS["emb_zero"], rows, xp), xp)
    emb = (codes.astype(xp.float32).reshape(-1, f, k) * scale[:, None, None]
           + zero[:, None, None])
    blk = rows // xp.uint32(LR_BLOCK)
    lr_code = codes_of(bits(lo, hi, STREAMS["lr_codes"], rows, xp), xp)
    lr = (lr_code.astype(xp.float32)
          * lr_scale_of(bits(lo, hi, STREAMS["lr_scale"], blk, xp), xp)
          + lr_zero_of(bits(lo, hi, STREAMS["lr_zero"], blk, xp), xp))
    return emb, lr


@functools.lru_cache(maxsize=None)
def _chunk_program(f: int, k: int, c: int):
    """The jitted maker of ``c`` table rows from ``row0`` on. The seed and
    ``row0`` are traced arguments, so every seed and chunk reuses one
    compiled program."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(lo, hi, row0):
        rows = row0 + jnp.arange(c, dtype=jnp.uint32)
        cnt = rows[:, None] * jnp.uint32(f * k) \
            + jnp.arange(f * k, dtype=jnp.uint32)[None, :]
        blk = row0 // jnp.uint32(LR_BLOCK) \
            + jnp.arange(c // LR_BLOCK, dtype=jnp.uint32)
        return {
            # flat: a 1-d int8 array leaves the device as one plain copy
            "codes": codes_of(bits(lo, hi, STREAMS["emb_codes"], cnt, jnp),
                              jnp).reshape(-1),
            "scale": emb_scale_of(bits(lo, hi, STREAMS["emb_scale"], rows,
                                       jnp), jnp),
            "zero": emb_zero_of(bits(lo, hi, STREAMS["emb_zero"], rows, jnp),
                                jnp),
            "lr_codes": codes_of(bits(lo, hi, STREAMS["lr_codes"], rows, jnp),
                                 jnp),
            "lr_scale": lr_scale_of(bits(lo, hi, STREAMS["lr_scale"], blk,
                                         jnp), jnp),
            "lr_zero": lr_zero_of(bits(lo, hi, STREAMS["lr_zero"], blk, jnp),
                                  jnp),
        }

    return make


def make_tables(cfg: dict, seed: int) -> dict:
    """The whole int8 table set as host numpy, made on the default device
    ``CHUNK_ROWS`` rows per call and copied out as it comes: the device
    holds two chunks at a time (one being made, one being copied), so the
    set-up leaves no table-sized peak in the device's memory. The embedding
    codes come out flat (``V * F * k``)."""
    import jax.numpy as jnp

    v, f, k = cfg["hash_space"], cfg["n_fields"], cfg["k"]
    n_blk = -(-v // LR_BLOCK)
    c = min(CHUNK_ROWS, n_blk * LR_BLOCK)
    make = _chunk_program(f, k, c)
    out = {"codes": np.empty(v * f * k, np.int8),
           "scale": np.empty(v, np.float32), "zero": np.empty(v, np.float32),
           "lr_codes": np.empty(v, np.int8),
           "lr_scale": np.empty(n_blk, np.float32),
           "lr_zero": np.empty(n_blk, np.float32)}

    def store(row0, chunk):
        n = min(c, v - row0)
        b0, nb = row0 // LR_BLOCK, min(c // LR_BLOCK, n_blk - row0 // LR_BLOCK)
        for key, a in chunk.items():
            a = np.asarray(a)
            if key == "codes":
                out[key][row0 * f * k:(row0 + n) * f * k] = a[:n * f * k]
            elif key in ("lr_scale", "lr_zero"):
                out[key][b0:b0 + nb] = a[:nb]
            else:
                out[key][row0:row0 + n] = a[:n]

    lo, hi = (jnp.uint32(w) for w in seed_words(seed))
    pending = None
    for row0 in range(0, v, c):
        nxt = (row0, make(lo, hi, jnp.uint32(row0)))  # runs while we copy
        if pending is not None:
            store(*pending)
        pending = nxt
    store(*pending)
    return out


def engine_params(cfg: dict, seed: int) -> dict:
    """The serving engine's params pytree for the seed: int8 tables made on
    the device and held as host numpy arrays, the form the engine's update
    pipe publishes (so every scoring call passes them as it would in
    service), plus the float32 head."""
    t = make_tables(cfg, seed)
    t["codes"] = t["codes"].reshape(cfg["hash_space"], cfg["n_fields"],
                                    cfg["k"])
    head = head_params(cfg, seed)
    params = {
        "lr": {"w": {"codes": t["lr_codes"], "scale": t["lr_scale"],
                     "zero": t["lr_zero"], "block": LR_BLOCK},
               "b": head["lr_b"]},
        "ffm": {"emb": {"codes": t["codes"], "scale": t["scale"],
                        "zero": t["zero"]}},
    }
    if cfg["head"] == "deepffm":
        params["merge_scale"] = head["merge_scale"]
        params["merge_bias"] = head["merge_bias"]
        params["mlp"] = head["mlp"]
    return params
