"""Scatter-gather serving over hash-space-sharded engine shards (§2/§6).

The paper's >300M predictions/s is an aggregate over a fleet of CPU workers,
each resident over a slice of the model. :class:`ShardRouter` is that fleet's
front-end: it owns N :class:`~repro.serving.engine.InferenceEngine` shards,
each holding a **contiguous hash-space range** of the embedding rows and
blocked-int8 LR rows (:class:`repro.launch.topology.ShardTopology` decides
ownership from the ParamSpec rule table), splits every request's feature rows
by owning shard, scores per-shard **partial candidate terms** on a thread
pool, and reduces them into the final logit. Per-shard resident bytes are
~1/N of the single-engine set; per-shard delta ingest arrives through a
fan-out of per-shard :class:`~repro.serving.update_pipe.UpdatePipe` instances
fed by :class:`repro.checkpoint.transfer.ShardedSender` frames.

Partial-sum reduction contract
------------------------------

The FFM logit is additive over pair terms and LR terms, so sharding is exact
— but *bit-stable* sharding needs care, because XLA-CPU float summation is
only deterministic for an identical reduction structure. The router's
contract, asserted by the fleet tests:

* **Every pair term is computed in exactly one place, from fully assembled
  inputs.** A pair (i, j) needs embedding rows from (up to) two shards, so
  no shard can own a full pair sum. Instead each *candidate entry* — one
  (request, candidate, candidate-field) cell — is owned by the shard holding
  its hashed row. The owning shard's worker gathers the row from its local
  table (packed host gather) and computes the entry's ctx-facing partial
  terms with one fixed contraction (``mik,mik->mi`` over a compacted entry
  list, padded to a power-of-two bucket — XLA-CPU keeps that contraction's
  bits invariant to the padded length, measured, which is what makes the
  result independent of how entries distribute over shards).
* **Host scatter in fixed shard order into disjoint positions.** Each
  entry's terms land at positions no other shard writes, so the scatter is
  order-free by construction, and the fixed order makes that auditable.
* **Cross-candidate (aa) pairs reduce at the router** from the scattered
  per-entry dequantized row slices, with the same einsum form and shapes as
  the single engine's fused q8 forward; context (cc) pairs and LR sums come
  from the router-level prefix cache over *assembled* rows (the sharded
  tables present a ``gather_np`` view that concatenates per-shard gathers),
  which is bit-equal to the single engine's host context path because both
  are elementwise-deterministic numpy.

Net effect: router output is **bit-identical for every shard count N**
(including N=1) at every generation, and matches the single-engine oracle to
the quantization tolerance contract (the single engine itself is not
bit-equal to ``deepffm.forward`` — its prefix tails run in numpy, its pair
sums in XLA — so cross-N bit equality is the strongest stable invariant, and
it is the one that matters operationally: resharding a fleet must not move
any score).

Per-shard generation vector
---------------------------

Each shard publishes ``(params, generation)`` atomically on its own update
pipe; the router tracks the **fleet generation vector**
(:meth:`ShardRouter.fleet_generations` — per-shard ``(generation,
weights_version)``, ``None`` for a dead shard) and rebuilds its assembled
view (bumping its own generation, which stamps the prefix cache) whenever
the vector changes. A scoring batch snapshots one assembled view, so it sees
each shard at one coherent generation; while delta frames are in flight the
vector can be *torn* (shards at different trainer versions), which is safe
by the same argument as a single engine's hot swap — every row is internally
consistent, and the mix resolves at ``flush_updates``.

Fault tolerance (PR 9)
----------------------

``ShardRouter(replicas=M)`` runs **M engine replicas per hash-space slice**,
each with its own :class:`~repro.checkpoint.transfer.Receiver`/
:class:`~repro.serving.update_pipe.UpdatePipe` state fed by the *same*
per-slice frame stream (``submit_updates`` tees every frame to every
replica), so siblings at the same generation hold **byte-identical
tables** — which is why failing over or hedging between them cannot move a
score: the per-replica bit-exactness contract is unchanged, replication
just multiplies it. Reads load-balance round-robin across a slice's healthy
replicas; a failed call fails over to an untried sibling; a straggling call
past the hedge threshold (``hedge_ms``, default 3x the router's p99 with a
50 ms floor) is **hedged** to a sibling, first response wins, and the
loser's gather buffer recycles through the shared
:class:`~repro.serving.engine.ScoringPool` free lists when its task
finishes. Per-replica health is a breaker (:class:`ReplicaHealth`:
healthy -> suspect -> dead -> probing) driven by call failures and hedge
outcomes — consecutive strikes back a replica off exponentially, then mark
it dead for the background prober to revive.

:meth:`kill_shard` now means **replica promotion**: the slice's next live
replica takes over and the response stays exact. Only when *every* replica
of a slice is dead does the fleet fall back to degraded-zero-rows — the
slice's rows score as zero contributions, and the response is flagged
(``ServeStats.last_degraded`` / ``degraded_responses``; the router-level
``degraded`` attribute latches) rather than silently wrong-by-omission. The
request path never raises for fleet-health reasons; with
``score_batch(deadline_ms=)`` a slice that cannot answer inside the budget
is likewise given up as zero rows (``deadline_misses``). Deterministic
failure drills plug in through :class:`repro.serving.faults.FaultPlan`
hooks (replica death at round k, latency spikes, call failures) — zero
overhead when unset.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import FIRST_COMPLETED, wait as _futures_wait
from functools import partial
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import FFMConfig
from repro.core import deepffm, ffm
from repro.core import quantization as Q
from repro.kernels.row_gather import ops as rg_ops
from repro.launch.topology import ShardTopology
from repro.serving.engine import (InferenceEngine, ScoringPool,
                                  _finish_candidates, refuse_fleet)
from repro.serving.faults import FaultPlan


# ---------------------------------------------------------------------------
# Assembled-view tables (the router's virtual params)
# ---------------------------------------------------------------------------

class ShardedRows:
    """Row-gatherable view over per-shard embedding tables.

    Quacks like a table for ``ffm.gather_rows_np`` (via ``gather_np``):
    a gather splits its indices by owning shard, gathers locally (packed
    host gather + per-row dequant for int8 parts — the exact numpy ops the
    single engine's context path runs, so assembled rows are bit-equal to
    full-table gathers), and scatters into one f32 block. Dead shards
    (``parts[s] is None``) contribute zero rows.

    ``replica_parts`` (set by ``ShardRouter._refresh_fleet``) snapshots, per
    slice, the ``(replica_index, emb_part)`` pairs of every live replica —
    coherent with ``parts`` because both are captured under the fleet lock —
    so the scatter-gather fan-out can load-balance, fail over, and hedge
    across siblings of one view without re-reading mutable fleet state.
    """

    dtype = np.float32
    replica_parts: Optional[List] = None  # per-slice [(replica, part), ...]

    def __init__(self, parts: Sequence, ranges: Sequence[Tuple[int, int]],
                 row_shape: Tuple[int, ...]):
        self.parts = list(parts)
        self.ranges = list(ranges)
        self.row_shape = tuple(row_shape)
        self._bounds = np.asarray([hi for _, hi in ranges[:-1]], np.int64)

    def owner_of(self, idx: np.ndarray) -> np.ndarray:
        return np.searchsorted(self._bounds, idx, side="right")

    def gather_np(self, idx: np.ndarray) -> np.ndarray:
        idx = np.asarray(idx)
        flat = idx.reshape(-1)
        out = np.zeros((flat.size,) + self.row_shape, np.float32)
        owner = self.owner_of(flat)
        for s, part in enumerate(self.parts):
            m = np.flatnonzero(owner == s)
            if part is None or m.size == 0:
                continue
            local = flat[m] - self.ranges[s][0]
            if Q.is_row_quantized(part):
                out[m] = rg_ops.gather_dequant_np(part, local)
            else:
                out[m] = np.asarray(part)[local]
        return out.reshape(idx.shape + self.row_shape)


class ShardedLR:
    """``gather_np`` view over per-shard blocked-int8 (or f32) LR slices.
    Shard boundaries are LR-block aligned (topology invariant), so each
    local slice's block grids are exactly the full-space grids."""

    dtype = np.float32

    def __init__(self, parts: Sequence, ranges: Sequence[Tuple[int, int]]):
        self.parts = list(parts)
        self.ranges = list(ranges)
        self._bounds = np.asarray([hi for _, hi in ranges[:-1]], np.int64)

    def gather_np(self, idx: np.ndarray) -> np.ndarray:
        idx = np.asarray(idx)
        flat = idx.reshape(-1)
        out = np.zeros(flat.size, np.float32)
        owner = np.searchsorted(self._bounds, flat, side="right")
        for s, part in enumerate(self.parts):
            m = np.flatnonzero(owner == s)
            if part is None or m.size == 0:
                continue
            local = flat[m] - self.ranges[s][0]
            out[m] = ffm.gather_lr_np(part, local).astype(np.float32)
        return out.reshape(idx.shape)


# ---------------------------------------------------------------------------
# Jitted partial / reduce stages
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(0,))
def _shard_partial_q8(cfg: FFMConfig, a_ctx, vc, vm, qc, scale, zero):
    """One shard's compacted candidate-entry partials, int8 rows.

    ``qc`` (M, F, k) int8 codes of the owned candidate rows (padded bucket
    M), ``scale``/``zero`` (M,) their grids, ``a_ctx`` (M, Fc, k) the
    ctx-side facing vectors (``stacked_emb[r, :, f0+j]`` per entry),
    ``vc`` (M, Fc) context values, ``vm`` (M,) candidate values. Returns
    ``terms`` (M, Fc) — the entry's ctx-cand pair terms — and ``aa_rows``
    (M, Fcand, k), the dequantized candidate-facing slice the router
    scatters for the cross-candidate reduce. Dequantization inside this jit
    is bit-identical to the single engine's fused dequant (measured), and
    the ``mik,mik->mi`` contraction's bits are invariant to the padded M —
    the two facts the cross-N bit-stability contract rests on.
    """
    fc = cfg.context_fields
    rows = (qc.astype(jnp.float32) * scale[:, None, None]
            + zero[:, None, None])                        # (M, F, k)
    terms = (jnp.einsum("mik,mik->mi", a_ctx, rows[:, :fc])
             * vc * vm[:, None])
    return terms, rows[:, fc:]


@partial(jax.jit, static_argnums=(0,))
def _shard_partial_rows(cfg: FFMConfig, a_ctx, vc, vm, rows):
    """f32-table twin of :func:`_shard_partial_q8` (pre-gathered rows)."""
    fc = cfg.context_fields
    terms = (jnp.einsum("mik,mik->mi", a_ctx, rows[:, :fc])
             * vc * vm[:, None])
    return terms, rows[:, fc:]


@partial(jax.jit, static_argnums=(0, 1))
def _reduce_forward(cfg: FFMConfig, model: str, head_params, cached,
                    pairs_xc, aa_block, kv_b, lr_cand):
    """Fixed-shard-order reduction: finish the logits from scattered partial
    terms. ``pairs_xc`` (R, N, n_xc) ctx-cand terms (scattered per entry);
    ``aa_block`` (R, N, Fcand, Fcand, k) the candidate rows' cand-facing
    slices. The aa einsum form/shape matches the single engine's
    ``_reference_candidate_pairs`` exactly, so its bits do not depend on the
    shard count that produced the block."""
    f0 = cfg.context_fields
    (pi, pj), _, _, aa = ffm.pair_split(cfg)
    eai = aa_block[:, :, pi[aa] - f0, pj[aa] - f0]
    eaj = aa_block[:, :, pj[aa] - f0, pi[aa] - f0]
    va = kv_b[:, :, pi[aa] - f0] * kv_b[:, :, pj[aa] - f0]
    pairs_aa = jnp.einsum("rnxk,rnxk->rnx", eai, eaj) * va
    return _finish_candidates(cfg, model, head_params, cached,
                              pairs_xc, pairs_aa, lr_cand)


# ---------------------------------------------------------------------------
# Replica health (circuit breaker)
# ---------------------------------------------------------------------------

class ReplicaHealth:
    """Per-replica circuit breaker: ``healthy -> suspect -> dead`` on
    consecutive strikes (call failures and lost hedges), with exponential
    backoff between suspect retries. ``dead`` replicas leave the read
    rotation until the router's background prober revives them
    (``dead -> probing -> healthy`` on a successful probe). One small lock
    per breaker: scorer threads and the prober never observe a half-applied
    transition, and the router's fleet lock stays off the per-call path."""

    HEALTHY, SUSPECT, DEAD, PROBING = "healthy", "suspect", "dead", "probing"

    def __init__(self, max_strikes: int = 3, backoff_s: float = 0.05):
        self.max_strikes = max_strikes
        self.backoff_s = backoff_s
        self.state = self.HEALTHY  # guarded-by: _lock
        self.strikes = 0           # guarded-by: _lock
        self.retry_at = 0.0        # guarded-by: _lock
        self._lock = threading.Lock()

    def record_success(self) -> None:
        with self._lock:
            self.state, self.strikes, self.retry_at = self.HEALTHY, 0, 0.0

    def record_strike(self, now: float) -> None:
        with self._lock:
            self.strikes += 1
            self.state = (self.DEAD if self.strikes >= self.max_strikes
                          else self.SUSPECT)
            self.retry_at = now + self.backoff_s * 2 ** min(self.strikes - 1, 6)

    def begin_probe(self) -> bool:
        with self._lock:
            if self.state != self.DEAD:
                return False
            self.state = self.PROBING
            return True

    def fail_probe(self, now: float) -> None:
        with self._lock:
            self.state = self.DEAD
            self.strikes += 1
            self.retry_at = now + self.backoff_s * 2 ** min(self.strikes - 1, 6)

    def available(self, now: float) -> bool:
        """May this replica take request traffic right now? Healthy always;
        suspect only past its backoff; dead/probing never (the prober owns
        those)."""
        with self._lock:
            if self.state == self.HEALTHY:
                return True
            return self.state == self.SUSPECT and now >= self.retry_at


# ---------------------------------------------------------------------------
# The router
# ---------------------------------------------------------------------------

class ShardRouter(InferenceEngine):
    """Fleet front-end: N hash-space-sharded engines behind one
    :class:`InferenceEngine` surface (see module docstring for the reduction
    and generation contracts).

    The router *is* an engine: ``score``/``score_batch``, the prefix cache,
    cross-request dedup, bucketing, warmup, and stats are inherited and
    operate on the **assembled view** — virtual params whose gather-table
    leaves are :class:`ShardedRows`/:class:`ShardedLR` views over the live
    shards. Only the ``_forward_args`` hook is replaced: candidate entries
    are compacted per owning shard, partial-scored on the fleet's one shared
    :class:`~repro.serving.engine.ScoringPool`, scattered, and reduced (the
    per-shard engines hold the resident tables and ingest update frames;
    their own scoring paths serve direct/debug traffic). Router and shards
    pin ``parallel=1``: the router's parallelism is the shard fan-out
    itself, and nesting span-splitting inside it would only multiply GIL
    contention.

    Fault-tolerance knobs (PR 9, see the module docstring): ``replicas=M``
    runs M engines per slice; ``hedge_ms`` pins the straggler threshold
    (default: 3x observed p99, floored at 50 ms); ``probe_interval_s`` paces
    the background prober that revives breaker-dead replicas; ``faults``
    accepts a :class:`repro.serving.faults.FaultPlan` for deterministic
    failure drills.
    """

    def __init__(self, cfg: FFMConfig, model: str = "deepffm", *,
                 n_shards: int = 2, backend: str = "reference", params=None,
                 quantized: bool = True, cache_entries: int = 4096,
                 min_bucket: int = 8, prefix_stride: Optional[int] = 4,
                 dedup: bool = True,
                 warmup_buckets: Optional[Tuple[int, int]] = None,
                 prefix_depths: Optional[Sequence[int]] = None,
                 max_workers: Optional[int] = None,
                 replicas: int = 1, hedge_ms: Optional[float] = None,
                 probe_interval_s: float = 0.2,
                 faults: Optional[FaultPlan] = None):
        refuse_fleet(model, "ShardRouter")
        self.topology = ShardTopology.build(cfg, model, n_shards,
                                            replicas=replicas)
        # ONE pool for the whole fleet: the router's scatter-gather fan-out
        # submits its per-shard partial (and hedge) tasks here, and every
        # replica engine is constructed around the same pool with parallel=1
        # — N x M engines never spawn N x M thread pools whose host gathers
        # contend on the GIL, and the router's parallelism *is* the shard
        # fan-out (span-splitting the replaced forward would sit inside the
        # compacted-entry-bucket bit contract for no extra concurrency)
        self._pool = ScoringPool(max_workers or n_shards * replicas)
        self._fleet: List[List[Optional[InferenceEngine]]] = [  # guarded-by: _fleet_lock
            [InferenceEngine(self.topology.shard_cfg(s), model,
                             backend=backend, quantized=quantized,
                             cache_entries=64, prefix_stride=None,
                             host_gather=False, parallel=1,
                             scoring_pool=self._pool)
             for _ in range(replicas)]
            for s in range(n_shards)]
        self._active: List[int] = [0] * n_shards  # guarded-by: _fleet_lock
        self._rr: List[int] = [0] * n_shards      # round-robin read cursor
        self._health: List[List[ReplicaHealth]] = [
            [ReplicaHealth() for _ in range(replicas)]
            for _ in range(n_shards)]
        self.faults = faults
        self.hedge_ms = hedge_ms
        self.probe_interval_s = probe_interval_s
        self.degraded = False
        self._fleet_lock = threading.Lock()
        self._fleet_vector: Optional[Tuple] = None  # guarded-by: _fleet_lock
        self._last_primary = None  # last live params; guarded-by: _fleet_lock
        self._call_tl = threading.local()  # per-batch fault-outcome flags
        self._prober: Optional[threading.Thread] = None  # guarded-by: _fleet_lock
        self._prober_stop = threading.Event()
        # entry->pair-position map: xc pairs are (i ctx, j cand); the entry
        # (r, n, j) contributes one term per context field i, landing at the
        # xc position of pair (i, f0+j)
        (pi, pj), _, xc, _ = ffm.pair_split(cfg)
        fc, fcand = cfg.context_fields, cfg.n_fields - cfg.context_fields
        self._xcpos = np.empty((fc, fcand), np.int64)
        self._xcpos[pi[xc], pj[xc] - fc] = np.arange(xc.size)
        # the router's own engine surface operates on the assembled view:
        # never quantize (shards own quantization), never host-gather (the
        # candidate path is replaced wholesale)
        super().__init__(cfg, model, backend=backend, params=None,
                         cache_entries=cache_entries, min_bucket=min_bucket,
                         prefix_stride=prefix_stride, dedup=dedup,
                         quantized=False, prefix_depths=prefix_depths,
                         host_gather=False, parallel=1,
                         scoring_pool=self._pool)
        if params is not None:
            self.install_params(params)
            if warmup_buckets is not None:
                self.warmup(max_requests=warmup_buckets[0],
                            max_candidates=warmup_buckets[1])

    # -- fleet weight management -------------------------------------------
    @property
    def n_shards(self) -> int:
        return len(self._fleet)

    @property
    def replicas(self) -> int:
        return self.topology.replicas

    @property
    def _shards(self) -> List[Optional[InferenceEngine]]:
        """Active-replica view of the fleet: slice ``s``'s serving replica,
        ``None`` when every replica of the slice is gone — the shape the
        pre-replica router exposed, kept as the compatibility surface."""
        return [None if a < 0 else row[a]
                for row, a in zip(self._fleet, self._active)]

    @property
    def shards(self) -> List[Optional[InferenceEngine]]:
        """Live view of the serving shard slots (``None`` = slice dead)."""
        return self._shards

    def fleet_generations(self) -> List[Optional[Tuple[int, int]]]:
        """Per-shard ``(generation, weights_version)`` of the *serving*
        replica; ``None`` for a dead slice — the router-level view of the
        fleet's generation vector."""
        return [None if s is None else (s.generation, s.weights_version)
                for s in self._shards]

    def replica_generations(self) -> List[List[Optional[Tuple[int, int]]]]:
        """Per-slice, per-replica ``(generation, weights_version)``
        (``None`` = killed slot) — the full fleet health/freshness matrix."""
        return [[None if e is None else (e.generation, e.weights_version)
                 for e in row] for row in self._fleet]

    def _fleet_vector_now(self) -> Tuple:
        return tuple(tuple(None if e is None
                           else (e.generation, e.weights_version)
                           for e in row) for row in self._fleet)

    def install_params(self, params) -> None:
        """Shard a full-space f32 pytree across the fleet and republish the
        assembled view. Every live replica of slice ``s`` quantizes the same
        slice (on a quantized fleet) — deterministic, so siblings start
        byte-identical; byte-identical to slicing a full-space quantization,
        per the topology's alignment invariant."""
        for s, row in enumerate(self._fleet):
            local = self.topology.shard_params(params, s)
            for eng in row:
                if eng is not None:
                    eng.install_params(local)
        self._refresh_fleet(force=True)

    def kill_shard(self, shard: int, replica: Optional[int] = None) -> None:
        """Take one replica down — by default the slice's *serving* replica.
        A sibling replica is promoted into the serving slot at the refresh
        (scores stay exact: siblings hold byte-identical tables); only when
        the slice's last replica dies do its rows start scoring as zero
        contributions, with ``degraded`` latched for monitoring. The
        victim's update pipe is killed (non-blocking — wakes any ``flush``
        racing this call rather than deadlocking behind its pending frames).
        Idempotent: killing an already-dead slot is a no-op."""
        with self._fleet_lock:
            r = self._active[shard] if replica is None else replica
            victim = None if r < 0 else self._fleet[shard][r]
            if victim is not None:
                self._fleet[shard][r] = None
        if victim is None:
            if all(e is None for e in self._fleet[shard]):
                self.degraded = True
            return
        pipe = victim._pipe
        if pipe is not None:
            pipe.kill()
        victim.close()
        self._refresh_fleet(force=True)

    def rotate_shard(self, shard: int, **rotate_kw) -> InferenceEngine:
        """Atomic shard rotation of the slice's serving replica: build the
        successor off the request path (:meth:`InferenceEngine.rotate`),
        re-point the replica's update pipe at it under the pipe's ingest
        lock (the receiver's byte chain — and therefore the delta-frame
        sequence — continues unbroken), and swap the serving slot. Returns
        the successor.

        Lock order at the re-point: ``pipe._ingest_lock`` (rank 20) then
        ``succ._pipe_lock`` (rank 30) — the cross-object pair declared in
        ``analysis/lock_order.py``. A ``submit_update`` racing this call
        serializes behind the ingest lock and lands its frame on whichever
        engine the pipe points at when it wins; a racing ``flush`` waits on
        ``_pending_cv`` (rank 50, taken under the ingest lock by the drain
        check) so neither can deadlock against the rotation. The fleet-slot
        swap happens *after* the ingest lock is released: ``_fleet_lock``
        (rank 10) ranks *below* the ingest lock, so taking it inside would
        invert the declared order."""
        r = self._active[shard]
        old = None if r < 0 else self._fleet[shard][r]
        if old is None:
            raise ValueError(f"shard {shard} is dead")
        succ = old.rotate(**rotate_kw)
        pipe = old._pipe
        if pipe is not None:
            with pipe._ingest_lock:       # rank 20: freezes frame ingestion
                pipe._engine = succ
                with succ._pipe_lock:     # rank 30: ingest → pipe is declared
                    succ._pipe = pipe
        with self._fleet_lock:
            self._fleet[shard][r] = succ
        self._refresh_fleet(force=True)
        return succ

    def _refresh_fleet(self, force: bool = False) -> None:
        """Rebuild the assembled view iff the fleet generation vector moved;
        publishing bumps the router generation (stamping the prefix cache).

        Replica promotion happens here too: every slice's serving slot is
        re-pointed at a live replica (preferring one holding params) before
        the view assembles, so a kill — or a publish landing on a sibling —
        revives the slice at the next refresh. Once weights have been
        installed the refresh *never raises* for fleet health: with every
        replica of every slice dead it keeps serving the last-known head
        leaves over all-zero tables (fully degraded), per the PR 9 contract
        that the request path never raises."""
        vector = self._fleet_vector_now()
        with self._fleet_lock:
            if not force and vector == self._fleet_vector:
                return
            for s, row in enumerate(self._fleet):
                a = self._active[s]
                if not (0 <= a < len(row) and row[a] is not None
                        and row[a].params is not None):
                    alive = [r for r, e in enumerate(row) if e is not None]
                    armed = [r for r in alive if row[r].params is not None]
                    self._active[s] = (armed or alive or [-1])[0]
            if any(all(e is None for e in row) for row in self._fleet):
                self.degraded = True
            actives = self._shards
            parts = [None if e is None else e.params for e in actives]
            live = [p for p in parts if p is not None]
            if live:
                primary = live[0]
                self._last_primary = primary
            elif self._last_primary is not None:
                primary = self._last_primary
                self.degraded = True
            else:
                raise RuntimeError("every shard is dead or weightless")
            cfg = self.cfg
            virtual = {k: v for k, v in primary.items()
                       if k not in ("ffm", "lr")}
            emb = ShardedRows(
                [None if p is None else p["ffm"]["emb"] for p in parts],
                self.topology.ranges, (cfg.n_fields, cfg.k))
            # snapshot the live sibling tables coherently with the view:
            # the scatter-gather fan-out reads only this (plus the breaker
            # states) — never the mutable fleet lists — per batch
            emb.replica_parts = [
                [(r, e.params["ffm"]["emb"]) for r, e in enumerate(row)
                 if e is not None and e.params is not None]
                for row in self._fleet]
            virtual["ffm"] = {"emb": emb}
            virtual["lr"] = {
                "w": ShardedLR(
                    [None if p is None else p["lr"]["w"] for p in parts],
                    self.topology.ranges),
                "b": primary["lr"]["b"]}
            self._fleet_vector = vector
            # single-reference publish (same atomicity argument as the
            # engine's _publish); _weights_raw directly — the property
            # getter re-enters _refresh_fleet, and _fleet_lock is held
            self._weights_raw = (virtual, self._weights_raw[1] + 1)
            self.weights_version = max(
                (e.weights_version for e in actives if e is not None),
                default=self.weights_version)

    def _maybe_refresh(self) -> None:
        if self._fleet_vector_now() != self._fleet_vector:
            self._refresh_fleet()

    # the engine's scoring path snapshots `self._weights`; route that read
    # through a lazy fleet-vector check so shard publishes (async update
    # pipes) become visible at the next batch boundary
    @property
    def _weights(self):
        if self._fleet_vector is not None:
            self._maybe_refresh()
        return self._weights_raw

    @_weights.setter
    def _weights(self, value):
        self._weights_raw = value

    # -- update fan-out ------------------------------------------------------
    def configure_fanout(self, manifests: Sequence, like_params) -> None:
        """Install per-shard decode defaults: shard ``s``'s pipe decodes
        against ``manifests[s]`` (local shapes — from
        ``transfer.ShardedSender.manifests``) and the shared ``like_params``
        tree (only structure/dtypes are kept)."""
        missing = [s for s, m in enumerate(manifests) if m is None]
        if missing:
            # a pipe configured with a None manifest rejects every frame
            # asynchronously (logged + dropped on the ingest thread) — the
            # fleet would just silently never advance. The sender publishes
            # manifests at prime()/first make_updates.
            raise ValueError(
                f"no manifest for shard(s) {missing}: prime the ShardedSender "
                "(or run a round) before configure_fanout")
        for row, manifest in zip(self._fleet, manifests):
            for eng in row:
                if eng is not None:
                    eng.update_pipe(manifest=manifest,
                                    like_params=like_params)

    def submit_updates(self, updates: Sequence[Optional[bytes]]) -> int:
        """Fan one training round's per-shard frames out to the fleet's
        update pipes (async; backpressure per pipe), tee'ing each slice's
        frame to **every** live replica — each replica runs its own receiver
        byte chain over the same frame sequence, which is what keeps
        siblings byte-identical (and failover exact). Dead slots' copies are
        dropped; a killed pipe counts as dead. Returns the number of slices
        that accepted the frame on at least one replica."""
        n = 0
        for row, frame in zip(self._fleet, updates):
            if frame is None:
                continue
            ok = False
            for eng in row:
                if eng is None:
                    continue
                try:
                    ok |= bool(eng.submit_update(frame))
                except RuntimeError:  # killed/closed pipe == dead slot
                    continue
            n += ok
        return n

    def flush_updates(self, timeout: Optional[float] = 30.0) -> List[
            Optional[Tuple[int, int]]]:
        """Wait until every live replica has published its pending frames,
        refresh the assembled view, and return the generation vector."""
        for row in self._fleet:
            for eng in row:
                if eng is not None and eng._pipe is not None:
                    eng._pipe.flush(timeout)
        self._maybe_refresh()
        return self.fleet_generations()

    def frame_errors(self) -> List[Optional[str]]:
        """Per-slice NACK latch: the first replica-reported frame error
        (``UpdatePipeStats.last_frame_error``), ``None`` for a clean slice —
        what a fleet supervisor polls to decide a :meth:`resync_shard`."""
        out: List[Optional[str]] = []
        for row in self._fleet:
            err = None
            for eng in row:
                pipe = None if eng is None else eng._pipe
                if pipe is not None and pipe.stats.last_frame_error:
                    err = pipe.stats.last_frame_error
                    break
            out.append(err)
        return out

    def resync_shard(self, shard: int, sender) -> int:
        """Answer a NACK: have the trainer side rebuild the slice's full
        state (``transfer.ShardedSender.resync``) and tee the resync frame
        to every live replica, clearing their NACK latches — one lost or
        mangled delta no longer poisons the slice's XOR chain. Returns the
        number of replicas the frame was accepted on (flush to observe the
        republished tables)."""
        frame = sender.resync(shard)
        n = 0
        for eng in self._fleet[shard]:
            if eng is None:
                continue
            try:
                accepted = bool(eng.submit_update(frame))
            except RuntimeError:
                continue
            if accepted:
                pipe = eng._pipe
                if pipe is not None:
                    pipe.stats.last_frame_error = None
                n += 1
        return n

    # -- resource accounting -------------------------------------------------
    @property
    def resident_weight_bytes(self) -> int:
        """Sum of every live replica's resident bytes (the head leaves
        replicate per engine; the tables split per slice and multiply by the
        replication factor)."""
        return sum(e.resident_weight_bytes
                   for row in self._fleet for e in row if e is not None)

    def shard_resident_bytes(self) -> List[int]:
        return [sum(e.resident_weight_bytes for e in row if e is not None)
                for row in self._fleet]

    # -- scoring: scatter partials / gather the reduction --------------------
    def _forward_args(self, params, stacked, ki_b, kv_b, grids=None,
                      out_codes=None):
        """The router's forward *is* the scatter-gather fan-out, so the
        engine's argument-builder hook returns it wholesale: compaction,
        per-shard partial scoring on the shared pool, disjoint scatter, and
        reduction all happen inside the returned callable. ``grids`` /
        ``out_codes`` are unused — the router's own engine surface never
        host-gathers (the shards hold the resident tables)."""
        return self._scatter_gather_forward, (params, stacked, ki_b, kv_b)

    def _tl_flags(self):
        """This thread's per-batch fault-outcome flags (lazily initialized —
        warmup drives the forward without going through ``score_batch``)."""
        tl = self._call_tl
        if not hasattr(tl, "degraded"):
            tl.degraded = False
            tl.hedged = 0
            tl.failovers = 0
            tl.deadline_missed = False
        return tl

    def _hedge_threshold_s(self) -> float:
        """Straggler threshold before a slice call is hedged to a sibling:
        explicit ``hedge_ms`` if pinned, else 3x the router's observed p99
        floored at 50 ms (cold stats hedge almost never — the floor keeps
        warmup/compile jitter from triggering spurious hedges)."""
        if self.hedge_ms is not None:
            return self.hedge_ms / 1e3
        return max(0.05, 3.0 * self.stats.latency_ms(99.0) / 1e3)

    def score_batch(self, requests: Sequence[Tuple], *,
                    deadline_ms: Optional[float] = None) -> List[np.ndarray]:
        """Engine surface plus the fleet's fault semantics: due fault-plan
        replica kills fire at the batch boundary (deterministic rounds), and
        the batch's degraded/hedge/failover/deadline outcomes fold into
        ``stats`` (``last_degraded`` reflects exactly this response)."""
        if self.faults is not None:
            for s, r in self.faults.next_round():
                self.kill_shard(s, r)
        tl = self._tl_flags()
        tl.degraded = False
        tl.hedged = 0
        tl.failovers = 0
        tl.deadline_missed = False
        out = super().score_batch(requests, deadline_ms=deadline_ms)
        with self._lock:
            st = self.stats
            st.last_degraded = bool(tl.degraded)
            if tl.degraded:
                st.degraded_responses += 1
            if tl.deadline_missed:
                st.deadline_misses += 1
            st.hedged_calls += tl.hedged
            st.failovers += tl.failovers
        return out

    def _scatter_gather_forward(self, params, stacked, ki_b, kv_b):
        cfg = self.cfg
        fc, fcand, k = cfg.context_fields, cfg.n_fields - cfg.context_fields, cfg.k
        rb, nb = ki_b.shape[:2]
        emb_view: ShardedRows = params["ffm"]["emb"]

        lr_cand = (ffm.gather_lr_np(params["lr"]["w"], ki_b)
                   * kv_b).sum(-1).astype(np.float32)

        owner = emb_view.owner_of(ki_b.reshape(-1)).reshape(ki_b.shape)
        stacked_emb = np.asarray(stacked["emb"], np.float32)
        stacked_val = np.asarray(stacked["val"], np.float32)
        tl = self._tl_flags()
        deadline = self._deadline()
        hedge_s = self._hedge_threshold_s()
        replica_rows = emb_view.replica_parts
        if replica_rows is None:  # a view built outside _refresh_fleet
            replica_rows = [[] if p is None else [(0, p)]
                            for p in emb_view.parts]

        def shard_task(s: int, replica: int, part):
            # one replica's partial-sum "call": fault hooks first (latency
            # spike / injected hard failure), then the local gather + fixed
            # contraction over this replica's tables. Siblings hold
            # byte-identical tables, so whichever replica answers, the bits
            # are the same — failover and hedging cannot move a score.
            if self.faults is not None:
                self.faults.on_replica_call(s, replica)
            sel = np.flatnonzero((owner == s).reshape(-1))
            r_m, rem = np.divmod(sel, nb * fcand)
            n_m, j_m = np.divmod(rem, fcand)
            local = ki_b[r_m, n_m, j_m] - emb_view.ranges[s][0]
            a_ctx = stacked_emb[r_m, :, fc + j_m]          # (M, Fc, k)
            vc = stacked_val[r_m]                          # (M, Fc)
            vm = kv_b[r_m, n_m, j_m]                       # (M,)
            m = sel.size
            mb = self.plan.bucket(m, minimum=self.plan.min_bucket)

            def pad(x):
                if x.shape[0] == mb:
                    return x
                return np.concatenate(
                    [x, np.zeros((mb - x.shape[0],) + x.shape[1:], x.dtype)])

            a_ctx, vc, vm = pad(a_ctx), pad(vc), pad(vm)
            # the row gather lands in a pool-recycled buffer; the finally
            # returns it to the free list on *every* exit — including a
            # hedge loser whose result the collector already discarded and
            # a call the fault plan blows up mid-flight — so abandoned
            # calls never strand buffers into the next batch
            quant = Q.is_row_quantized(part)
            table = part["codes"] if quant else np.asarray(part)
            buf = self._pool.acquire((mb,) + table.shape[1:], table.dtype)
            try:
                rg_ops.gather_codes_np(table, local, out=buf[:m])
                buf[m:] = 0
                if quant:
                    sc = pad(np.asarray(part["scale"])[local])
                    ze = pad(np.asarray(part["zero"])[local])
                    terms, aa_rows = _shard_partial_q8(cfg, a_ctx, vc, vm,
                                                       buf, sc, ze)
                else:
                    terms, aa_rows = _shard_partial_rows(
                        cfg, a_ctx, vc, vm, buf.astype(np.float32,
                                                       copy=False))
                terms = np.asarray(jax.block_until_ready(terms))
                aa_rows = np.asarray(jax.block_until_ready(aa_rows))
            finally:
                self._pool.release(buf)
            return (r_m, n_m, j_m, terms[:m], aa_rows[:m])

        # launch one call per owning slice (round-robin over available
        # replicas); collection below hedges/fails over per slice
        now0 = time.monotonic()
        inflight: List[Optional[dict]] = []
        for s in range(len(emb_view.parts)):
            if not np.any(owner == s):
                inflight.append(None)
                continue
            cands = [(r, p) for r, p in replica_rows[s]
                     if self._replica_available(s, r, now0)]
            if not cands:
                cands = list(replica_rows[s])  # all breakered: still try
            if not cands:
                # slice owns entries but has no live replica at all: its
                # rows contribute zeros and the response is flagged
                tl.degraded = True
                inflight.append(None)
                continue
            rot = self._rr[s] % len(cands)
            self._rr[s] += 1
            cands = cands[rot:] + cands[:rot]
            fut = self._pool.submit(shard_task, s, cands[0][0], cands[0][1])
            inflight.append({"s": s, "cands": cands, "next": 1,
                             "pending": {fut: cands[0][0]},
                             "start": time.monotonic(), "hedged": False})

        def collect(st):
            """First-success-wins collection for one slice: failed calls
            fail over to the next untried replica, a straggler past the
            hedge threshold is raced against a sibling (once), and a blown
            deadline abandons the slice (stragglers finish on pool threads
            and recycle their own buffers). Returns the partial result or
            None (slice contributes zeros, response degraded)."""
            s = st["s"]
            while st["pending"]:
                now = time.monotonic()
                timeout = None
                if deadline is not None:
                    timeout = deadline - now
                    if timeout <= 0:
                        tl.deadline_missed = True
                        tl.degraded = True
                        return None
                if not st["hedged"] and st["next"] < len(st["cands"]):
                    until_hedge = st["start"] + hedge_s - now
                    timeout = (until_hedge if timeout is None
                               else min(timeout, until_hedge))
                done, _ = _futures_wait(
                    list(st["pending"]),
                    timeout=None if timeout is None else max(timeout, 0.0),
                    return_when=FIRST_COMPLETED)
                now = time.monotonic()
                for fut in done:
                    replica = st["pending"].pop(fut)
                    if fut.exception() is None:
                        self._health[s][replica].record_success()
                        return fut.result()
                    self._health[s][replica].record_strike(now)
                    self._ensure_prober()
                    if st["next"] < len(st["cands"]):
                        r2, p2 = st["cands"][st["next"]]
                        st["next"] += 1
                        tl.failovers += 1
                        st["pending"][self._pool.submit(
                            shard_task, s, r2, p2)] = r2
                if (not done and not st["hedged"]
                        and st["next"] < len(st["cands"])
                        and now - st["start"] >= hedge_s):
                    # straggler: strike it (breaker food) and race a sibling
                    for straggler in st["pending"].values():
                        self._health[s][straggler].record_strike(now)
                    self._ensure_prober()
                    r2, p2 = st["cands"][st["next"]]
                    st["next"] += 1
                    tl.hedged += 1
                    st["hedged"] = True
                    st["pending"][self._pool.submit(
                        shard_task, s, r2, p2)] = r2
            tl.degraded = True  # every attempted replica failed
            return None

        (pi, pj), _, xc, _ = ffm.pair_split(cfg)
        pairs_xc = np.zeros((rb, nb, xc.size), np.float32)
        aa_block = np.zeros((rb, nb, fcand, fcand, k), np.float32)
        # fixed slice order; every entry's positions are written by exactly
        # one slice, so the scatter targets are disjoint by construction —
        # and whichever *replica* of the slice answered, the written bits
        # are identical (byte-identical sibling tables)
        for st in inflight:
            res = None if st is None else collect(st)
            if res is None:
                continue
            r_m, n_m, j_m, terms, aa_rows = res
            pairs_xc[r_m[:, None], n_m[:, None],
                     self._xcpos[:, j_m].T] = terms
            aa_block[r_m, n_m, j_m] = aa_rows
        return _reduce_forward(cfg, self.model, self._head_params(params),
                               stacked, pairs_xc, aa_block, kv_b, lr_cand)

    # -- replica health / background prober ----------------------------------
    def _replica_available(self, shard: int, replica: int,
                           now: float) -> bool:
        try:
            return self._health[shard][replica].available(now)
        except IndexError:  # pragma: no cover - defensive
            return True

    def _ensure_prober(self) -> None:
        """Lazily start the daemon prober the first time a replica breaker
        opens (idle fleets never pay for the thread)."""
        if self._prober is not None and self._prober.is_alive():
            return
        with self._fleet_lock:
            if ((self._prober is not None and self._prober.is_alive())
                    or self._prober_stop.is_set()):
                return
            self._prober = threading.Thread(
                target=self._probe_loop, name="shard-prober", daemon=True)
            self._prober.start()

    def _probe_loop(self) -> None:
        """Background breaker recovery: periodically probe DEAD replicas
        (through the fault hook, so injected failures keep them dead until
        the plan exhausts) and return survivors to the read rotation."""
        while not self._prober_stop.wait(self.probe_interval_s):
            now = time.monotonic()
            for s, row in enumerate(self._fleet):
                for r, eng in enumerate(row):
                    h = self._health[s][r]
                    if (h.state != ReplicaHealth.DEAD or now < h.retry_at
                            or eng is None or eng.params is None):
                        continue
                    if not h.begin_probe():
                        continue
                    try:
                        if self.faults is not None:
                            self.faults.on_replica_call(s, r)
                        np.asarray(eng.params["lr"]["b"])  # touch the tables
                        h.record_success()
                    except Exception:
                        h.fail_probe(time.monotonic())

    def warmup(self, *, max_requests: int = 8, max_candidates: int = 64) -> int:
        """Pre-compile the router's full shape set: every (row-bucket,
        candidate-bucket) reduce shape via the inherited warmup (which
        drives :meth:`_candidates_forward` on zero dummies — zeros are all
        owned by shard 0, so that warms only the largest entry bucket), plus
        every intermediate compacted-entry bucket of the partial jits, which
        real traffic reaches as soon as ownership splits."""
        calls = super().warmup(max_requests=max_requests,
                               max_candidates=max_candidates)
        cfg = self.cfg
        fc, fcand, k = (cfg.context_fields,
                        cfg.n_fields - cfg.context_fields, cfg.k)
        rb = self.plan.bucket(max_requests, minimum=1)
        nb = self.plan.bucket(max_candidates)
        quantized = any(
            p is not None and Q.is_row_quantized(p["ffm"]["emb"])
            for p in (s.params for s in self._shards if s is not None))
        f32 = any(
            p is not None and not isinstance(p["ffm"]["emb"], dict)
            for p in (s.params for s in self._shards if s is not None))
        for mb in self.plan.buckets_upto(rb * nb * fcand):
            a_ctx = np.zeros((mb, fc, k), np.float32)
            vc = np.zeros((mb, fc), np.float32)
            vm = np.zeros(mb, np.float32)
            if quantized:
                _shard_partial_q8(cfg, a_ctx, vc, vm,
                                  np.zeros((mb, cfg.n_fields, k), np.int8),
                                  np.zeros(mb, np.float32),
                                  np.zeros(mb, np.float32))
            if f32:
                _shard_partial_rows(
                    cfg, a_ctx, vc, vm,
                    np.zeros((mb, cfg.n_fields, k), np.float32))
            calls += 1
        return calls

    def close(self) -> None:
        """Shut down the fleet: stop the prober, kill every replica's update
        pipe (non-blocking; wakes any flusher mid-wait), drop the scoring
        pool references (router + every replica share one), and shut the
        pool down. End-of-life: a closed router no longer scores."""
        self._prober_stop.set()
        prober = self._prober
        if prober is not None:
            prober.join(timeout=5.0)
        with self._lock:
            self._scoring_pool = None
        for row in self._fleet:
            for eng in row:
                if eng is None:
                    continue
                with eng._lock:
                    eng._scoring_pool = None
                if eng._pipe is not None:
                    eng._pipe.kill()
        self._pool.shutdown()

    # -- oracle --------------------------------------------------------------
    def materialized_params(self):
        """Concatenate the live shards' tables back into one full-space
        pytree (dead shards contribute zero rows) — the router's oracle
        weights. Exact on a quantized fleet: per-shard grids are slices of
        the full-space grids, so concatenation reverses the sharding
        byte-for-byte."""
        parts = [None if s is None else s.params for s in self._shards]
        live = [p for p in parts if p is not None]
        if not live:
            raise RuntimeError("every shard is dead or weightless")
        primary = live[0]
        cfg = self.cfg

        def emb_part(p, lo, hi):
            if p is not None:
                return p["ffm"]["emb"]
            n = hi - lo
            like = next(q["ffm"]["emb"] for q in live)
            if Q.is_row_quantized(like):
                return {"codes": np.zeros((n, cfg.n_fields, cfg.k), np.int8),
                        "scale": np.ones(n, np.float32),
                        "zero": np.zeros(n, np.float32)}
            return np.zeros((n, cfg.n_fields, cfg.k), np.float32)

        def lr_part(p, lo, hi):
            if p is not None:
                return p["lr"]["w"]
            n = hi - lo
            like = next(q["lr"]["w"] for q in live)
            if Q.is_block_quantized(like):
                b = int(like["block"])
                return {"codes": np.zeros(n, np.int8),
                        "scale": np.ones(-(-n // b), np.float32),
                        "zero": np.zeros(-(-n // b), np.float32),
                        "block": b}
            return np.zeros(n, np.float32)

        embs = [emb_part(p, lo, hi)
                for p, (lo, hi) in zip(parts, self.topology.ranges)]
        lrs = [lr_part(p, lo, hi)
               for p, (lo, hi) in zip(parts, self.topology.ranges)]
        out = {kk: v for kk, v in primary.items() if kk not in ("ffm", "lr")}
        if all(Q.is_row_quantized(e) for e in embs):
            out["ffm"] = {"emb": {
                key: np.concatenate([e[key] for e in embs])
                for key in ("codes", "scale", "zero")}}
        else:
            out["ffm"] = {"emb": np.concatenate(
                [Q.dequantize_rows(e) if Q.is_row_quantized(e)
                 else np.asarray(e) for e in embs])}
        if all(Q.is_block_quantized(w) for w in lrs):
            out["lr"] = {"w": {
                "codes": np.concatenate([w["codes"] for w in lrs]),
                "scale": np.concatenate([w["scale"] for w in lrs]),
                "zero": np.concatenate([w["zero"] for w in lrs]),
                "block": int(lrs[0]["block"])},
                "b": primary["lr"]["b"]}
        else:
            out["lr"] = {"w": np.concatenate(
                [Q.dequantize_blocks(w) if Q.is_block_quantized(w)
                 else np.asarray(w) for w in lrs]),
                "b": primary["lr"]["b"]}
        return out

    def score_uncached(self, ctx_idx, ctx_val, cand_idx, cand_val,
                       use_backend: bool = False) -> jnp.ndarray:
        """Full-forward oracle against the materialized (concatenated)
        fleet tables — the assembled view's duck-typed leaves cannot cross a
        jit boundary, so the router materializes for its oracle."""
        self._require_params()
        n = cand_idx.shape[0]
        fc = self.cfg.context_fields
        idx = jnp.concatenate(
            [jnp.broadcast_to(jnp.asarray(ctx_idx), (n, fc)),
             jnp.asarray(cand_idx)], axis=1)
        val = jnp.concatenate(
            [jnp.broadcast_to(jnp.asarray(ctx_val), (n, fc)),
             jnp.asarray(cand_val)], axis=1)
        return deepffm.forward(self.cfg, self.materialized_params(), idx, val,
                               self.model)
