"""The one traffic generator: every mix is a JSON file of its parameters.

A mix file (``bench/traffic/<mix>.json``) may name a ``base`` mix whose
parameters it extends. The keys:

  loop            "open" (arrivals on a schedule) or "max" (the next call
                  goes out as the previous returns, ``max_batch`` requests)
  rate_per_s      open loop: requests per second offered
  pool_per_s      max loop: requests made per second of window, an upper
                  bound on what the system can take
  max_batch       requests handed to one ``score_batch`` call, at most
  sessions        number of user sessions whose contexts recur; 0 makes
                  every context fresh
  session_zipf    Zipf exponent of session popularity
  prefix_share    {depth: share}: share of requests that keep the first
                  ``depth`` context fields of their session's last context
                  and resample the rest
  values_per_field, value_zipf
                  raw values of a field: Zipf over this many values, hashed
                  with ``feature_hash`` into the table's rows
  candidates      {median, sigma, min, max}: lognormal slate lengths
  inventory       number of ads candidates are drawn from; 0 draws every
                  candidate row uniformly from the hash space
  inventory_zipf  Zipf exponent of ad popularity
  numeric_fields  fields whose value is log1p of a lognormal draw; all
                  other fields are categorical, value 1

Every seed gets the same amount of work: the same number of requests, the
same multiset of slate lengths and of prefix depths, and arrival times
spread the same way (a Poisson process conditioned on its count); the seed
chooses their order and the contents.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_DIR = os.path.join(os.path.dirname(HERE), "traffic")

_P1, _P2 = np.uint64(0x9E3779B97F4A7C15), np.uint64(0xBF58476D1CE4E5B9)


def feature_hash(field: np.ndarray, value: np.ndarray,
                 hash_space: int) -> np.ndarray:
    """(field, raw value) -> hashed row, as the program's data path hashes
    (a copy of ``repro.data.synthetic.feature_hash``)."""
    with np.errstate(over="ignore"):
        h = (field.astype(np.uint64) + np.uint64(1)) * _P1 ^ (
            value.astype(np.uint64) + np.uint64(1)) * _P2
        h ^= h >> np.uint64(31)
    return (h % np.uint64(hash_space)).astype(np.int32)


def load_mix(name: str, traffic_dir: str = TRAFFIC_DIR) -> dict:
    with open(os.path.join(traffic_dir, f"{name}.json")) as f:
        mix = json.load(f)
    base = mix.pop("base", None)
    if base is None:
        return mix
    return {**load_mix(base, traffic_dir), **mix}


class Zipf:
    """Finite Zipf over ranks 0..n-1 (rank r has weight (r+1)^-s), sampled
    by inverting its CDF."""

    def __init__(self, n: int, s: float):
        w = np.arange(1, n + 1, dtype=np.float64) ** -float(s)
        self.cdf = np.cumsum(w) / w.sum()

    def sample(self, rng, size) -> np.ndarray:
        r = np.searchsorted(self.cdf, rng.random(size), side="right")
        return np.minimum(r, self.cdf.size - 1)


def slate_lengths(n: int, c: dict) -> np.ndarray:
    """``n`` lengths at the lognormal's quantiles (i + 0.5) / n, clipped:
    the same multiset for every seed."""
    from statistics import NormalDist

    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(c["median"]) + c["sigma"] * z)
    return np.clip(np.rint(x), c["min"], c["max"]).astype(np.int64)


def exact_counts(n: int, shares: dict) -> dict:
    """Split ``n`` into integer counts in proportion to ``shares``
    (largest remainders), so the mix holds for every seed exactly."""
    keys = sorted(shares, key=lambda k: -float(shares[k]))
    raw = {k: n * float(shares[k]) for k in keys}
    out = {k: int(np.floor(raw[k])) for k in keys}
    rest = n - sum(out.values())
    for k in sorted(keys, key=lambda k: out[k] - raw[k])[:rest]:
        out[k] += 1
    return out


@dataclass
class Requests:
    """``n`` requests, built lazily: ``get(i)`` is the
    ``(ctx_idx, ctx_val, cand_idx, cand_val)`` tuple ``score_batch`` takes."""

    ctx_idx: np.ndarray      # (n, Fc) int32
    ctx_val: np.ndarray      # (n, Fc) float32
    depth: np.ndarray        # (n,) prefix depth kept from the session, -1 fresh
    session: np.ndarray      # (n,) the request's session, -1 fresh
    offsets: np.ndarray      # (n + 1,) into ``ads``
    ads: np.ndarray          # candidate ad ids (or rows, no inventory)
    ad_idx: np.ndarray       # (n_ads, Fcand) int32
    ad_val: np.ndarray       # (n_ads, Fcand) float32
    due_s: np.ndarray        # (n,) open loop: due time from window start

    def __len__(self) -> int:
        return self.ctx_idx.shape[0]

    def length(self, i: int) -> int:
        return int(self.offsets[i + 1] - self.offsets[i])

    def get(self, i: int):
        a = self.ads[self.offsets[i]:self.offsets[i + 1]]
        return (self.ctx_idx[i], self.ctx_val[i], self.ad_idx[a],
                self.ad_val[a])


def _field_values(rng, zipf: Zipf, fields: np.ndarray, numeric, shape,
                  hash_space: int):
    """Raw Zipf values of ``fields`` hashed to rows, and their values."""
    raw = zipf.sample(rng, shape)
    idx = feature_hash(np.broadcast_to(fields, shape), raw, hash_space)
    val = np.ones(shape, np.float32)
    for col, f in enumerate(fields):
        if f in numeric:
            val[..., col] = np.log1p(rng.lognormal(0.0, 1.0, shape[:-1]))
    return idx, val


def generate(mix: dict, model: dict, seed: int, n: int) -> Requests:
    """``n`` requests of ``mix`` for a model of ``model``'s field layout
    (``n_fields``, ``context_fields``, ``hash_space``)."""
    rng = np.random.default_rng(seed)
    f, fc, v = model["n_fields"], model["context_fields"], model["hash_space"]
    fcand = f - fc
    numeric = set(mix.get("numeric_fields", ()))
    zv = Zipf(mix["values_per_field"], mix["value_zipf"])
    ctx_fields = np.arange(fc)

    # contexts: sessions whose last context is kept to a prefix depth
    fresh_idx, fresh_val = _field_values(rng, zv, ctx_fields, numeric,
                                         (n, fc), v)
    depth = np.full(n, -1, np.int64)
    s_of = np.full(n, -1, np.int64)
    ctx_idx, ctx_val = fresh_idx, fresh_val
    if mix["sessions"]:
        counts = exact_counts(n, mix["prefix_share"])
        depth = np.concatenate([np.full(c, int(d), np.int64)
                                for d, c in counts.items()])
        rng.shuffle(depth)
        s_of = Zipf(mix["sessions"], mix["session_zipf"]).sample(rng, n)
        last_idx, last_val = _field_values(rng, zv, ctx_fields, numeric,
                                           (mix["sessions"], fc), v)
        ctx_idx, ctx_val = fresh_idx.copy(), fresh_val.copy()
        for i in range(n):
            s, d = s_of[i], depth[i]
            ctx_idx[i, :d] = last_idx[s, :d]
            ctx_val[i, :d] = last_val[s, :d]
            last_idx[s], last_val[s] = ctx_idx[i], ctx_val[i]

    # slates
    lengths = slate_lengths(n, mix["candidates"])
    rng.shuffle(lengths)
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    cand_fields = np.arange(fc, f)
    if mix["inventory"]:
        ad_idx, ad_val = _field_values(rng, zv, cand_fields, numeric,
                                       (mix["inventory"], fcand), v)
        ads = Zipf(mix["inventory"], mix["inventory_zipf"]).sample(
            rng, int(offsets[-1]))
    else:
        total = int(offsets[-1])
        ad_idx = rng.integers(0, v, (total, fcand)).astype(np.int32)
        ad_val = np.ones((total, fcand), np.float32)
        for col, fld in enumerate(cand_fields):
            if fld in numeric:
                ad_val[:, col] = np.log1p(rng.lognormal(0.0, 1.0, total))
        ads = np.arange(total)

    due = np.zeros(n)
    if mix["loop"] == "open":
        due = np.sort(rng.random(n)) * (n / float(mix["rate_per_s"]))
    return Requests(ctx_idx.astype(np.int32), ctx_val.astype(np.float32),
                    depth, s_of, offsets, ads, ad_idx, ad_val, due)


def request_count(mix: dict, seconds: float) -> int:
    """Requests a run of ``seconds`` makes: the open loop's schedule, or the
    max loop's pool."""
    key = "rate_per_s" if mix["loop"] == "open" else "pool_per_s"
    return max(1, int(round(float(mix[key]) * seconds)))
