"""Unified serving engine — the paper's tricks composed in one scoring path.

The paper's >300M predictions/s comes from one long-lived serving instance in
which the tricks *compound* rather than compete. This module is that
composition point; each component maps to a paper section:

* **§3 (architecture)** — :class:`InferenceEngine` is the persistent scoring
  service on the receiving end of the trainer's update channel.
  :meth:`InferenceEngine.apply_update` swaps weights **in place** under a
  generation counter (no server reconstruction), so the context cache and the
  jit caches survive every quantized-patch round. The (params, generation)
  pair is published atomically, so scoring threads always see one coherent
  weights version even while updates land concurrently. Frame decode /
  dequantize / patch / row-delta work lives in the engine's
  :class:`~repro.serving.update_pipe.UpdatePipe`: ``apply_update`` is a thin
  synchronous wrapper over it, and :meth:`InferenceEngine.submit_update`
  hands the frame to the pipe's background thread so the request path only
  ever pays the final pointer swap.
* **§5 (context cache)** — the cache is a *prefix tree* over ``(idx, val)``
  field tokens (:mod:`repro.serving.prefix_cache`), mirroring the paper's
  radix tree over raw request strings: a lookup reuses the deepest cached
  prefix partial and only the context *tail* is computed, grouped across a
  whole cache-miss burst per cached depth. Tails run **on host**
  (:func:`ffm.extend_context_prefix_np`): the arithmetic is tiny, so numpy
  beats the old vmapped-jit path's stacking/dispatch/transfer overhead and
  can never compile mid-traffic (:func:`compute_context_tails` remains as
  the jitted batch-scale reference). Entries are stamped with the weight
  generation and lazily refreshed after a hot swap.
* **§5 (candidate dedup)** — real multi-request traffic repeats candidates:
  :meth:`InferenceEngine.score_batch` dedups identical ``(context,
  candidate)`` rows across the microbatch, scores each unique row once per
  weight generation, and scatters results back per request.
* **§5 (SIMD hot loop)** — the candidate completion can route its pair
  computation through the Pallas candidate-block kernel
  (``kernels/ffm_interaction``), selected per engine via
  ``backend="reference" | "pallas"``: the kernel consumes *cached* context
  partials instead of bypassing the cache.
* **§6 (weight transfer)** — updates arrive as versioned quantized-patch
  frames (``checkpoint.transfer.unframe``); the engine tracks the trainer's
  version stamp alongside its own generation counter.
* **§6 (quantized serving path)** — ``InferenceEngine(quantized=True)``
  keeps the *whole resident gather set* int8: the embedding tables as
  **int8 rows** with per-row ``(scale, zero)`` grids
  (``quantization.quantize_rows``) and the LR table as **blocked int8**
  (``quantization.quantize_blocks``: ``(V,)`` viewed as ``(V/B, B)`` with a
  per-block grid — per-row grids degenerate for scalar rows). The update
  pipe quantizes on ingest (delta frames requantize only their touched
  rows/blocks), every scoring gather moves ~a quarter of the bytes, and
  dequantization happens in-register — inside the fused Pallas candidate
  kernel (``ffm_candidate_matrices_q8``) on the ``pallas`` backend, or right
  after the gather otherwise — so the f32 tables never exist in memory on
  the request path. Cached context partials stay f32 (they are activations,
  not weights; the prefix cache needs only its existing per-generation
  entry slots). *How the gather executes* is strategy-selected per table
  size and backend (``kernels/row_gather``): generic ``jnp.take`` below
  ~2^17 rows, the scalar-prefetch Pallas gather-and-dequant kernel on
  accelerator backends above it, and on CPU a **host packed pre-gather**
  (``host_gather=``, auto) that feeds already-gathered codes + summed LR
  terms to :func:`batched_candidates_forward_q8` — XLA-CPU's generic gather
  leaves its fast path above that size (the ROADMAP'd int8 gather cliff)
  while the packed numpy gather stays flat. An in-trace engine on an
  accelerator holds its gather tables **device-resident per generation**:
  each published params object is copied to the device once, before its
  swap and off the request path (:meth:`InferenceEngine._device_params`;
  ``ServeStats.table_uploads`` counts these copies, one per install or
  publish), and every forward call takes that copy instead of host numpy —
  the same bytes into the same jitted function, so scores are bit-identical.
  On the CPU backend device memory is host memory, so no copy is kept there
  (it would only double host RAM). **Tolerance contract**: scores
  deviate from the f32 oracle by at most the per-row/per-block
  reconstruction errors ``quantization.row_max_error`` /
  ``quantization.block_max_error`` propagated through the pair and LR sums
  (``quantization.pair_logit_tolerance`` bounds the additive FFM part
  rigorously; the DeepFFM MLP head can amplify further, so parity there is
  asserted against the *roundtrip* oracle — an f32 engine running the
  dequantized tables — which the quantized path matches to float precision).
  Keep f32 (the default) when scores feed downstream consumers that need
  sub-quantization-step calibration or when the model head is too sensitive
  to embedding perturbation; quantize when serving is gather-bandwidth
  bound — the paper's CPU deployment regime.
* **Fused bucket scoring (§5 x §6, roofline-grounded)** —
  ``InferenceEngine(fused=True)``, auto-selected on quantized ``"ffm"``
  engines whose table auto-picks the host pre-gather, collapses the staged
  chain — host context-tail extension (``ffm.extend_context_prefix_np``) ->
  candidate dot matrices -> pair-vector scatter -> additive head — into
  **one Pallas call per padding bucket**
  (:func:`fused_candidates_forward_q8`): context resolution only *gathers*
  rows (``ffm.fused_context_state_np``); the kernel computes the context
  pairs a depth-p cached prefix is still missing in-device, accumulates
  cand-cand pair dots as **int8 x int8 -> int32** (exact) dequantizing only
  the scalar dot result, and emits logits directly — the ``(R, N, n_pairs)``
  pair vector and the candidate dot matrices never exist in memory. The
  kernel also returns each row's ctx pair matrix, from which full-depth
  prefix states are rebuilt and inserted *after* scoring
  (``ffm.prefix_state_from_dots_np``) — cache learning survives the fusion,
  and the inserted states are byte-compatible with the staged path's.
  **Int8-accumulator tolerance contract**: against the staged oracle on the
  *same* quantized tables the deviation is pure f32 reassociation (the int32
  code dots are exact), bounded by ``quantization.fused_logit_tolerance``;
  against the f32 oracle the quantization bound
  ``quantization.pair_logit_tolerance`` dominates exactly as on the staged
  path. The staged path is still selected for: ``deepffm``/MLP heads (the
  fused kernel emits additive-head logits only), engines without the host
  pre-gather (the in-trace gather already avoids the host<->jit crossings
  fusion removes), ``score_uncached`` / ``prewarm_contexts`` (oracle and
  cache-fill mechanisms), and the ``ShardRouter`` (its scatter-gather
  forward composes per-shard partial sums in a fixed order — fusing inside
  shards would break the bit-invariance-across-shard-counts contract).

**Model heads.** ``ffm`` and ``deepffm`` score through FFM pair terms;
``dcnv2`` (:mod:`repro.core.dcnv2`) through the DCN-v2 cross stack. What
differs between them is one :class:`Head` looked up by the model name: the
gather tables, the context state the prefix trie holds (FFM prefix partials,
or DCN-v2's value-scaled context rows ``x0``), its host tail arithmetic and
the jitted staged forward. The trie, dedup, buckets, warmup, spans and the
device twin are shared. The ``dcnv2`` forward computes the context's part
of the first cross layer once per request row and the rest in the Pallas
kernel ``dcn_cross_q8`` (``kernels/dcn_cross``); the update pipe, the shard
router, the fused path and the host pre-gather refuse the head.

**Parallel scoring (multi-core microbatch execution).** The paper's 300M+
predictions/s saturates *every* core of a CPU box; a single-stream
``score_batch`` bounds one. ``InferenceEngine(parallel=N)`` splits each
microbatch's deduped candidate chunks into contiguous per-worker spans,
each padded to its own power-of-two row bucket (a subset of the buckets
:meth:`InferenceEngine.warmup` already compiles, so the compiled shape set
stays closed), and pipelines them through a persistent engine-owned
:class:`ScoringPool`: pool threads run the numpy host pre-gather for span
*k+1* (into recycled double buffers) while the caller thread executes the
GIL-releasing Pallas/jit call for span *k*. **Bit-parity contract**: spans
are dispatched and reassembled in fixed chunk order, every jitted
forward's per-row output is invariant to the row-bucket size, and all
spans score against the batch's one resolved ``(params, generation)``
context snapshot — so the scattered scores are bit-identical to the
single-stream path for every worker count. The auto policy
(:func:`auto_parallel_workers`, ``parallel=None``) turns the pipeline off
on 1-core boxes and otherwise uses one worker per core capped at 4. A
:class:`~repro.serving.shard_router.ShardRouter` threads **one** shared
pool through all its shards (``scoring_pool=``) instead of letting N
shards spawn M pools whose host gathers contend on the GIL; shards and the
router itself pin ``parallel=1`` — the router's parallelism *is* the shard
fan-out.

**Deadlines and the degraded-response contract (PR 9).**
``score_batch(deadline_ms=)`` attaches a per-request wall-clock budget that
the :class:`~repro.serving.shard_router.ShardRouter` plumbs through its
scatter-gather: a shard call that exceeds the straggler threshold is hedged
to a sibling replica (first response wins), and a slice that still has no
answer at the deadline contributes **zero rows** instead of blocking the
response. Any response assembled with at least one such zero-rows slice —
whether from a blown deadline or a slice whose replicas are all dead — is
*degraded*: scores are wrong-by-omission for candidates whose rows lived in
the missing slice (the reduction simply lacks those partial sums; all other
slices' contributions are exact and bit-stable). Degradation is surfaced,
never silent: ``ServeStats.last_degraded`` flags the most recent response,
``degraded_responses`` / ``deadline_misses`` / ``hedged_calls`` /
``failovers`` count the window, and the router's ``degraded`` attribute
latches once any slice has lost its last replica. Single engines (no
router) never degrade: without a deadline they compute to completion, and
with one they still run their single forward to completion — ``deadline_ms``
only gates *fan-out* waits, it never truncates a computation already
running.

Request batching: candidate counts are padded to power-of-two buckets and
multiple requests are stacked into one jitted call
(:meth:`InferenceEngine.score_batch`), so the forward compiles once per
bucket instead of once per request shape — and because the prefix cache's
checkpoint depths close the set of tail shapes too, the *entire* compiled
shape set is enumerable up front: :meth:`InferenceEngine.warmup` pre-compiles
it at construction so no request ever pays compile latency. Latency is
tracked per request with p50/p95/p99 percentiles in :class:`ServeStats`.
Cross-request candidate dedup packs the microbatch's ``(group, idx, val)``
rows into one contiguous int32 matrix and dedups with ``np.unique`` on a
void view — no per-row Python hashing on the hot path.

**Tracing.** Each ``score_batch`` call takes a batch id and opens
``jax.profiler.TraceAnnotation`` spans for its phases, every one carrying
that id as ``call``: ``serve.score_batch`` (the whole call),
``serve.resolve`` (context tokens, trie lookups and inserts),
``serve.tails`` (host tail arithmetic, one span per miss group),
``serve.dedup`` (packed dedup, chunk layout, candidate blocks and grids),
``serve.prepare`` (padding, stacking and the host pre-gather of one chunk
span; on a pool thread when the batch is split), ``serve.pool_wait`` (the
caller waiting for a prepare), ``serve.launch`` (the jitted call, which
copies its host arguments to the device), ``serve.device_wait`` (waiting for
the device and copying the results back) and ``serve.finish`` (fused cache
inserts, scatter-back, stats). The profiler records them only while
``jax.profiler.start_trace`` runs; their wall seconds always add up in
``ServeStats.phase_s`` (:class:`CallPhases`). ``ServeStats.host_arg_bytes``
and ``slots_scored`` count the host bytes handed to the jitted forwards and
the padded slots they computed.

**Machine-checked invariants (PR 10).** The concurrency and purity
contracts this module leans on — the lock partial order (`_pipe_lock` and
`_lock` sit *under* the pipe's `_ingest_lock`; see
``repro.analysis.lock_order``), the ``# guarded-by:`` attribute
annotations, numpy-keyed hot paths, trace purity of the jitted forwards —
are enforced by the invariant linter (``python -m repro.analysis``) and the
runtime lock-order witness on the concurrency suites. See
``src/repro/analysis/README.md`` and "Static invariants (PR 10)" in
ROADMAP.md.
"""
from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
from collections import Counter, deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.common.config import FFMConfig
from repro.core import dcnv2, deepffm, ffm
from repro.core import quantization as Q
from repro.serving.prefix_cache import (PrefixCache, context_from_tokens,
                                        context_tokens)
from repro.serving.update_pipe import UpdatePipe


# ---------------------------------------------------------------------------
# Stats
# ---------------------------------------------------------------------------

@dataclass
class ServeStats:
    """Serving counters + a bounded window of per-request latencies.

    ``candidates`` counts *requested* rows; ``rows_scored`` counts rows that
    actually went through the forward after cross-request dedup (pre-padding).
    ``ctx_partials_full`` counts contexts computed from scratch (no cached
    prefix) and ``ctx_tail_fields`` the total context fields actually
    computed — the prefix cache shrinks both relative to an exact-match
    cache on prefix-sharing traffic. ``host_arg_bytes`` counts the bytes of
    host numpy arrays handed to the jitted forwards (a device-resident
    ``jax.Array`` argument counts 0), ``slots_scored`` the padded
    ``rows x candidates`` slots those forwards computed, and ``phase_s`` the
    wall seconds spent in each ``serve.*`` span (see :class:`CallPhases`).
    ``table_uploads`` counts the device-resident twins of published weights
    an in-trace engine on an accelerator built (see
    :meth:`InferenceEngine._device_params`) and ``table_upload_bytes`` the
    host bytes they copied: one per install, publish or construction with
    params, never one per call. CPU engines build none (their device memory
    is host memory), so both stay 0 there.
    """

    requests: int = 0
    candidates: int = 0
    rows_scored: int = 0
    seconds: float = 0.0
    updates_applied: int = 0
    update_bytes: int = 0
    ctx_partials_full: int = 0
    ctx_tail_fields: int = 0
    host_arg_bytes: int = 0
    slots_scored: int = 0
    table_uploads: int = 0
    table_upload_bytes: int = 0
    phase_s: Dict[str, float] = field(default_factory=dict)
    # fault-tolerance counters (PR 9) — populated by the ShardRouter:
    degraded_responses: int = 0  # responses with >=1 zero-rows slice
    deadline_misses: int = 0     # responses that gave a slice up at deadline
    hedged_calls: int = 0        # shard calls re-issued to a sibling replica
    failovers: int = 0           # shard calls recovered on a sibling after failure
    last_degraded: bool = False  # the most recent response's degraded flag
    latency_window: int = 4096
    _latencies_s: Optional[deque] = field(default=None, repr=False)

    def __post_init__(self):
        # deque(maxlen=...) keeps the window mutation a single C-level call:
        # concurrent scorer threads recording without the engine lock (e.g.
        # bench drivers) can no longer interleave an extend with the windowed
        # delete and drop or double-count entries
        self._latencies_s = deque(maxlen=self.latency_window)

    def record(self, seconds: float, candidates: int, requests: int = 1) -> None:
        self.requests += requests
        self.candidates += candidates
        self.seconds += seconds
        # every request in a microbatch completes when the batch does, so the
        # batch wall time is each request's latency; maxlen evicts the oldest
        self._latencies_s.extend([seconds] * requests)

    def merge(self, other: "ServeStats") -> None:
        """Fold another accumulator into this one. The parallel scoring path
        accumulates a batch's counters (including per-worker contributions)
        into a private :class:`ServeStats` outside any lock and merges it here
        **once per caller-visible batch** under the engine lock — chunk
        sub-dispatches never touch the shared object, so splitting a batch
        across workers adds no lock traffic and, critically, no extra
        ``record`` calls: latency percentiles count requests, not padded
        engine-internal chunks."""
        self.requests += other.requests
        self.candidates += other.candidates
        self.rows_scored += other.rows_scored
        self.seconds += other.seconds
        self.updates_applied += other.updates_applied
        self.update_bytes += other.update_bytes
        self.ctx_partials_full += other.ctx_partials_full
        self.ctx_tail_fields += other.ctx_tail_fields
        self.host_arg_bytes += other.host_arg_bytes
        self.slots_scored += other.slots_scored
        self.table_uploads += other.table_uploads
        self.table_upload_bytes += other.table_upload_bytes
        self.add_phases(other.phase_s)
        self.degraded_responses += other.degraded_responses
        self.deadline_misses += other.deadline_misses
        self.hedged_calls += other.hedged_calls
        self.failovers += other.failovers
        self.last_degraded = self.last_degraded or other.last_degraded
        self._latencies_s.extend(other._latencies_s)

    def add_phases(self, seconds: Dict[str, float]) -> None:
        for name, s in seconds.items():
            self.phase_s[name] = self.phase_s.get(name, 0.0) + s

    @property
    def dedup_saved(self) -> int:
        """Candidate rows the cross-request dedup avoided scoring."""
        return self.candidates - self.rows_scored

    @property
    def predictions_per_s(self) -> float:
        return self.candidates / max(self.seconds, 1e-9)

    def latency_ms(self, pct: float) -> float:
        snap = list(self._latencies_s)  # atomic snapshot vs concurrent records
        if not snap:
            return 0.0
        return float(np.percentile(np.asarray(snap), pct) * 1e3)

    @property
    def p50_ms(self) -> float:
        return self.latency_ms(50.0)

    @property
    def p95_ms(self) -> float:
        return self.latency_ms(95.0)

    @property
    def p99_ms(self) -> float:
        return self.latency_ms(99.0)


class CallPhases:
    """The phases of one ``score_batch`` call. Each :meth:`span` is a
    ``jax.profiler.TraceAnnotation`` carrying the call's batch id as
    ``call`` (recorded only while a profiler trace runs), and its wall time
    adds to the call's :meth:`totals`, from any thread: the engine folds
    those into ``ServeStats.phase_s`` once the call returns."""

    def __init__(self, call: int):
        self.call = call
        self._seconds: Dict[str, float] = {}  # guarded-by: _lock
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(name, call=self.call):
                yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self._seconds[name] = self._seconds.get(name, 0.0) + dt

    def totals(self) -> Dict[str, float]:
        """Seconds per span name, summed over this call's threads."""
        with self._lock:
            return dict(self._seconds)


def _span(phases: Optional[CallPhases], name: str):
    """``phases.span(name)``; no span for work outside a ``score_batch``
    call (``phases`` None: prewarm, a bare ``ScoringPool.run``)."""
    return contextlib.nullcontext() if phases is None else phases.span(name)


def host_arg_nbytes(args) -> int:
    """Bytes of the host numpy arrays among a forward call's arguments: what
    crosses to the device when the call runs. A ``jax.Array`` already on the
    device counts 0."""
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(args)
               if isinstance(x, (np.ndarray, np.generic)))


# backends whose device memory is host memory: a device-resident copy of the
# gather tables there would only double host RAM
_HOST_MEMORY_BACKENDS = ("cpu",)


def _tables_resident(host_gather: bool, params) -> bool:
    """Whether an engine keeps a device-resident twin of ``params`` (see
    :meth:`InferenceEngine._device_params`): only where its forward gathers
    in-trace (the tables are then arguments of every forward call), the
    backend's device memory is not host memory, and the tables are arrays
    rather than sharded views that gather on host (``gather_np``)."""
    if (host_gather or params is None
            or jax.default_backend() in _HOST_MEMORY_BACKENDS):
        return False
    return not any(hasattr(t, "gather_np")
                   for t in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Parallel scoring pool
# ---------------------------------------------------------------------------

def auto_parallel_workers(cpu_count: Optional[int] = None) -> int:
    """Auto policy for the engine's ``parallel=`` knob: 1 (off) on a
    single-core box — splitting a burst there only adds dispatch overhead
    with no second core to overlap on — otherwise one worker per core capped
    at 4 (the chunk counts real microbatches produce rarely reward more, and
    XLA's own intra-op threads want the remaining cores)."""
    n = (os.cpu_count() if cpu_count is None else cpu_count) or 1
    return 1 if n < 2 else min(int(n), 4)


class ScoringPool:
    """Persistent worker pool + buffer recycler for the parallel pipeline.

    One pool per engine (created lazily on the first split batch, reused for
    every burst; a :class:`~repro.serving.shard_router.ShardRouter` instead
    constructs its shards around one shared pool so N shards do not each spin
    up M threads). Two jobs:

    * :meth:`run` pipelines a burst's chunk spans: *prepare* callables (the
      numpy host pre-gather + padding for span *k+1*) execute on pool threads
      while the caller thread runs the *dispatch* (the Pallas/jit call) for
      span *k* — the jit execution releases the GIL inside XLA, so host
      ``np.take`` work genuinely overlaps kernel time. The look-ahead window
      is ``workers + 1`` spans so prepares never run unboundedly ahead of the
      buffers backing them. Dispatches always happen on the caller thread in
      fixed span order — that ordering is half of the engine's bit-parity
      contract (the other half is bucket-aligned span padding).
    * :meth:`acquire`/:meth:`release` recycle packed gather buffers
      (:func:`repro.kernels.row_gather.ops.gather_codes_np` ``out=``): the
      free list keeps at most two buffers per worker per shape — the
      double-buffer depth the pipeline needs — so a steady burst stops
      allocating fresh multi-MB code blocks per chunk.
    """

    def __init__(self, workers: int):
        self.workers = max(1, int(workers))
        self._ex = ThreadPoolExecutor(max_workers=self.workers,
                                      thread_name_prefix="scoring-pool")
        self._buffers: Dict[tuple, list] = {}  # guarded-by: _buf_lock
        self._buf_lock = threading.Lock()
        # secondary failures discarded by run()'s drain (the first error
        # re-raises) — latched so an aborted burst can't hide errors entirely
        self.drain_errors = 0
        self.last_drain_error: Optional[BaseException] = None

    def acquire(self, shape: tuple, dtype) -> np.ndarray:
        """A recycled gather buffer of this shape/dtype (fresh if none free)."""
        key = (tuple(shape), np.dtype(dtype).str)
        with self._buf_lock:
            free = self._buffers.get(key)
            if free:
                return free.pop()
        return np.empty(shape, dtype)

    def release(self, buf: np.ndarray) -> None:
        """Return a buffer to the free list once its dispatch has completed
        (``block_until_ready`` has run, so XLA holds no alias into it).
        Extras beyond the double-buffer depth fall back to the allocator."""
        key = (tuple(buf.shape), buf.dtype.str)
        with self._buf_lock:
            free = self._buffers.setdefault(key, [])
            if len(free) < 2 * self.workers:
                free.append(buf)

    def submit(self, fn, *args):
        """Raw executor submit — the ShardRouter's scatter-gather fan-out."""
        return self._ex.submit(fn, *args)

    def run(self, prepares: Sequence, dispatch, cleanup=None,
            phases: Optional[CallPhases] = None) -> list:
        """Pipeline ``prepares`` (pool threads, bounded look-ahead) against
        ``dispatch`` (caller thread, fixed order); returns dispatch results
        in prepare order. Each wait for a prepare is a ``serve.pool_wait``
        span of the caller's ``phases``.

        Exception safety: if any prepare or dispatch raises, the remaining
        in-flight prepares are *drained* — each completed result is handed to
        ``cleanup`` (best-effort; e.g. returning an acquired gather buffer to
        the free list) — and the first error re-raises to the caller. Without
        the drain, an aborted burst would strand its recycled buffers and
        leave orphaned futures running into the next batch; with it, the pool
        stays fully usable for the next batch."""
        window = self.workers + 1
        pending: deque = deque()
        out = []

        def next_prepared():
            with _span(phases, "serve.pool_wait"):
                return pending.popleft().result()

        try:
            for prep in prepares:
                pending.append(self._ex.submit(prep))
                if len(pending) >= window:
                    out.append(dispatch(next_prepared()))
            while pending:
                out.append(dispatch(next_prepared()))
        except BaseException:
            while pending:
                fut = pending.popleft()
                try:
                    res = fut.result()
                except Exception as e:
                    # the first error already propagates; count the rest
                    self.drain_errors += 1
                    self.last_drain_error = e
                    continue
                if cleanup is not None:
                    try:
                        cleanup(res)
                    except Exception as e:
                        self.drain_errors += 1
                        self.last_drain_error = e
            raise
        return out

    def shutdown(self) -> None:
        self._ex.shutdown(wait=True)


# ---------------------------------------------------------------------------
# Scoring plan
# ---------------------------------------------------------------------------

BACKENDS = ("reference", "pallas")


class ScoringPlan:
    """Precomputed request-independent scoring choices: the validated
    context/candidate field split, the power-of-two candidate padding buckets,
    and the backend. Built once per engine; shape/index logic, never weights.
    (The DiagMask pair split itself is derived where it is used, via
    ``ffm.pair_split`` at jit trace time.)
    """

    def __init__(self, cfg: FFMConfig, model: str = "deepffm",
                 backend: str = "reference", min_bucket: int = 8,
                 fused: bool = False):
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
        if not 1 <= cfg.context_fields < cfg.n_fields:
            raise ValueError("context cache needs 1 <= context_fields < n_fields")
        if fused and model != "ffm":
            # the fused kernel emits additive-head logits; MergeNorm/MLP heads
            # need the full pair vector and stay on the staged path
            raise ValueError(f"fused scoring requires model='ffm', got {model!r}")
        self.cfg, self.model, self.backend = cfg, model, backend
        self.fused = bool(fused)
        self.min_bucket = max(1, min_bucket)

    def bucket(self, n: int, minimum: Optional[int] = None) -> int:
        """Smallest power-of-two >= n (floored at ``min_bucket``)."""
        b = max(1, self.min_bucket if minimum is None else minimum)
        while b < n:
            b *= 2
        return b

    def buckets_upto(self, n: int, minimum: Optional[int] = None) -> List[int]:
        """All buckets the engine can emit for sizes in [1, n] — the closed
        shape set :meth:`InferenceEngine.warmup` pre-compiles."""
        out, b = [], self.bucket(1, minimum)
        top = self.bucket(n, minimum)
        while b <= top:
            out.append(b)
            b *= 2
        return out


# ---------------------------------------------------------------------------
# Jitted scoring path
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(0,))
def compute_context(cfg: FFMConfig, params, ctx_idx, ctx_val):
    """Context-only pass (§5). ctx_idx/val: (Fc,). Returns the cacheable
    partial in *prefix state* format (see ``ffm.extend_context_prefix``):
    ``emb`` (Fc, F, k), ``val`` (Fc,), ``pairs`` (j-major ctx-ctx
    interactions), ``lr_terms`` (Fc,). Any prefix depth of the state is a
    pure slice of it. The emb table may be int8 row-quantized
    (``ffm.gather_rows`` dequantizes the gathered rows); the partial itself
    is always an f32 activation."""
    emb = params["ffm"]["emb"]
    prefix = ffm.empty_context_prefix(cfg, ffm.table_dtype(emb))
    return ffm.extend_context_prefix(cfg, emb, params["lr"]["w"], prefix,
                                     ctx_idx, ctx_val)


@partial(jax.jit, static_argnums=(0,))
def compute_context_tails(cfg: FFMConfig, params, prefix, tail_idx, tail_val):
    """Batched context-tail pass over one cache-miss group (§5, prefix cache).

    All members share one cached-prefix depth p; ``prefix`` leaves carry a
    leading group axis M (emb (M, p, F, k), val (M, p), pairs (M, p(p-1)/2),
    lr_terms (M, p)); tail_idx/val: (M, Fc-p). Returns the stacked full-depth
    prefix states — one vmapped call per miss burst instead of one
    ``compute_context`` per request.
    """
    def one(pe, pv, pp, pl, ti, tv):
        return ffm.extend_context_prefix(
            cfg, params["ffm"]["emb"], params["lr"]["w"],
            {"emb": pe, "val": pv, "pairs": pp, "lr_terms": pl}, ti, tv)

    return jax.vmap(one)(prefix["emb"], prefix["val"], prefix["pairs"],
                         prefix["lr_terms"], tail_idx, tail_val)


def _reference_candidate_pairs(cfg: FFMConfig, emb_ctx, val_ctx, ec, cand_val):
    """ctx-cand / cand-cand pair columns from gathered f32 candidate rows —
    the jnp reference math both candidate forwards share."""
    f0 = cfg.context_fields
    (pi, pj), _, xc, aa = ffm.pair_split(cfg)
    # ctx-cand: pair (i ctx, j cand): dot(emb_ctx[i, j], ec[j-f0, i]) * v_i * v_j
    exi = emb_ctx[:, pi[xc], pj[xc]]                  # (R, n_xc, k) ctx side
    exj = ec[:, :, pj[xc] - f0, pi[xc]]               # (R, N, n_xc, k) cand side
    vx = (val_ctx[:, pi[xc]][:, None, :]
          * cand_val[:, :, pj[xc] - f0])
    pairs_xc = jnp.einsum("rxk,rnxk->rnx", exi, exj) * vx

    # cand-cand
    eai = ec[:, :, pi[aa] - f0, pj[aa]]               # (R, N, n_aa, k)
    eaj = ec[:, :, pj[aa] - f0, pi[aa]]
    va = cand_val[:, :, pi[aa] - f0] * cand_val[:, :, pj[aa] - f0]
    pairs_aa = jnp.einsum("rnxk,rnxk->rnx", eai, eaj) * va
    return pairs_xc, pairs_aa


def _finish_candidates(cfg: FFMConfig, model: str, params, cached,
                       pairs_xc, pairs_aa, lr_cand):
    """Assemble the canonical pair vector and run the model head — the tail
    both candidate forwards share. ``lr_cand``: (R, N) candidate LR sums."""
    r, n = lr_cand.shape
    _, cc, xc, aa = ffm.pair_split(cfg)
    pairs_cc = cached["pairs"][:, ffm.prefix_to_cc_perm(cfg)]
    lr_ctx = jnp.sum(cached["lr_terms"], axis=-1)

    vec = jnp.zeros((r, n, cfg.n_pairs), pairs_aa.dtype)
    vec = vec.at[:, :, cc].set(
        jnp.broadcast_to(pairs_cc[:, None, :], (r, n, cc.size)))
    vec = vec.at[:, :, xc].set(pairs_xc)
    vec = vec.at[:, :, aa].set(pairs_aa)

    lr_out = lr_ctx[:, None] + lr_cand + params["lr"]["b"]
    logits = deepffm.head_from_parts(
        cfg, params, lr_out.reshape(-1), vec.reshape(r * n, cfg.n_pairs), model)
    return logits.reshape(r, n)


@partial(jax.jit, static_argnums=(0, 1, 2))
def batched_candidates_forward(cfg: FFMConfig, model: str, backend: str,
                               params, cached, cand_idx, cand_val):
    """Candidate completion for a stack of R request rows.

    ``cached`` leaves carry a leading row axis R (stacked prefix states from
    :func:`compute_context` / :func:`compute_context_tails`); cand_idx/val:
    (R, N, F-Fc). Returns logits (R, N). Pair computation routes through the
    Pallas candidate kernel when ``backend == "pallas"``. All table gathers
    (emb rows, LR weights) happen in-trace here — engines whose quantized
    table crosses the XLA-CPU gather cliff pre-gather on host instead and
    call :func:`batched_candidates_forward_q8`.
    """
    emb = params["ffm"]["emb"]
    emb_ctx, val_ctx = cached["emb"], cached["val"]

    if backend == "pallas":
        from repro.kernels.ffm_interaction import ops as ffm_ops

        if isinstance(emb, dict):  # int8 rows: gather codes, dequant in-kernel
            qc = jnp.take(emb["codes"], cand_idx, axis=0)
            s = jnp.take(emb["scale"], cand_idx)
            z = jnp.take(emb["zero"], cand_idx)
            pairs_xc, pairs_aa = ffm_ops.candidate_interactions_q8(
                cfg, emb_ctx, val_ctx, qc, s, z, cand_val)
        else:
            ec = jnp.take(emb, cand_idx, axis=0)  # (R, N, Fcand, F, k)
            pairs_xc, pairs_aa = ffm_ops.candidate_interactions(
                cfg, emb_ctx, val_ctx, ec, cand_val)
    else:
        # gather_rows dequantizes right after the gather when emb is int8
        ec = ffm.gather_rows(emb, cand_idx)               # (R, N, Fcand, F, k)
        pairs_xc, pairs_aa = _reference_candidate_pairs(
            cfg, emb_ctx, val_ctx, ec, cand_val)

    lr_cand = jnp.sum(ffm.gather_lr(params["lr"]["w"], cand_idx) * cand_val,
                      axis=-1)
    return _finish_candidates(cfg, model, params, cached,
                              pairs_xc, pairs_aa, lr_cand)


@partial(jax.jit, static_argnums=(0, 1, 2))
def batched_candidates_forward_q8(cfg: FFMConfig, model: str, backend: str,
                                  head_params, cached, qc, scale, zero,
                                  cand_val, lr_cand):
    """Candidate completion over *pre-gathered* int8 candidate codes.

    The above-the-cliff twin of :func:`batched_candidates_forward` (§6 x the
    gather subsystem): the engine gathers candidate rows on host — packed
    numpy gather, immune to the XLA-CPU generic-gather slow path past ~2^17
    table rows — and ships only the gathered block into the jit: ``qc``
    (R, N, Fcand, F, k) int8 codes, ``scale``/``zero`` (R, N, Fcand) per-row
    grids, ``lr_cand`` (R, N) already-summed candidate LR terms (the LR
    lookups ride the same host gather). ``head_params`` carries only the
    head leaves (LR bias, MergeNorm, MLP) — the resident tables never cross
    the jit boundary here, so the call moves 1 byte per candidate element
    plus two scalars per row, exactly like the in-kernel gather path.
    """
    emb_ctx, val_ctx = cached["emb"], cached["val"]
    if backend == "pallas":
        from repro.kernels.ffm_interaction import ops as ffm_ops

        pairs_xc, pairs_aa = ffm_ops.candidate_interactions_q8(
            cfg, emb_ctx, val_ctx, qc, scale, zero, cand_val)
    else:
        ec = (qc.astype(jnp.float32) * scale[..., None, None]
              + zero[..., None, None])
        pairs_xc, pairs_aa = _reference_candidate_pairs(
            cfg, emb_ctx, val_ctx, ec, cand_val)
    return _finish_candidates(cfg, model, head_params, cached,
                              pairs_xc, pairs_aa, lr_cand)


@partial(jax.jit, static_argnums=(0, 1, 2))
def batched_candidates_forward_rows(cfg: FFMConfig, model: str, backend: str,
                                    head_params, cached, ec, cand_val,
                                    lr_cand):
    """Candidate completion over *pre-gathered f32* candidate rows.

    The f32 twin of :func:`batched_candidates_forward_q8`: the PR 5 sweep
    shows f32 ``jnp.take`` hits the same XLA-CPU generic-gather wall as the
    int8 rows (0.9 -> 3.9 ms at 2^19), so f32 engines above the measured
    cliff pre-gather on host too (packed numpy gather moves the same bytes
    either way) and ship the already-gathered ``ec`` (R, N, Fcand, F, k)
    block plus the summed ``lr_cand`` terms. ``head_params`` again carries
    only the head leaves — the resident table never crosses the jit boundary.
    """
    emb_ctx, val_ctx = cached["emb"], cached["val"]
    if backend == "pallas":
        from repro.kernels.ffm_interaction import ops as ffm_ops

        pairs_xc, pairs_aa = ffm_ops.candidate_interactions(
            cfg, emb_ctx, val_ctx, ec, cand_val)
    else:
        pairs_xc, pairs_aa = _reference_candidate_pairs(
            cfg, emb_ctx, val_ctx, ec, cand_val)
    return _finish_candidates(cfg, model, head_params, cached,
                              pairs_xc, pairs_aa, lr_cand)


@partial(jax.jit, static_argnums=(0,))
def fused_candidates_forward_q8(cfg: FFMConfig, lr_b, cached, qc, scale, zero,
                                cand_val, lr_cand):
    """One-call fused scoring over pre-gathered int8 candidate codes.

    The roofline-motivated collapse of :func:`batched_candidates_forward_q8`
    + :func:`_finish_candidates` into a single Pallas dispatch per padding
    bucket (``"ffm"`` model only — the head is the additive LR + pair sum).
    ``cached`` is the *fused* context state (leaves stacked over R rows):
    ``emb`` (R, Fc, F, k) full-depth embeddings, ``val`` (R, Fc), ``depth``
    (R,) cached prefix depths, ``pair_sum`` (R,) summed cached ctx pairs,
    ``lr_terms`` (R, Fc). The missing ctx pairs (j >= depth) compute inside
    the kernel; cand-cand dots accumulate int8 x int8 -> int32 and
    dequantize only at the scalar result. Returns ``(logits (R, N),
    ctx_dots (R, Fc, Fc))`` — the second output rebuilds insertable
    full-depth prefix states (``ffm.prefix_state_from_dots_np``).
    """
    from repro.kernels.ffm_interaction import ops as ffm_ops

    base = (jnp.sum(cached["lr_terms"], axis=-1)
            + cached["pair_sum"])[:, None] + lr_cand + lr_b
    return ffm_ops.fused_candidate_logits_q8(
        cfg, cached["emb"], cached["val"], cached["depth"], base,
        qc, scale, zero, cand_val)


@partial(jax.jit, static_argnums=(0,))
def fused_candidates_forward_rows(cfg: FFMConfig, lr_b, cached, ec, cand_val,
                                  lr_cand):
    """f32 twin of :func:`fused_candidates_forward_q8` (pre-gathered f32
    rows ``ec`` (R, N, Fcand, F, k) instead of codes + grids)."""
    from repro.kernels.ffm_interaction import ops as ffm_ops

    base = (jnp.sum(cached["lr_terms"], axis=-1)
            + cached["pair_sum"])[:, None] + lr_cand + lr_b
    return ffm_ops.fused_candidate_logits_rows(
        cfg, cached["emb"], cached["val"], cached["depth"], base,
        ec, cand_val)


def candidates_forward(cfg: FFMConfig, model: str, params, cached,
                       cand_idx, cand_val):
    """Single-request compatibility wrapper (reference backend). ``cached`` is
    one :func:`compute_context` state; cand_idx/val: (N, F-Fc) -> logits (N,)."""
    lifted = jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], cached)
    return batched_candidates_forward(
        cfg, model, "reference", params, lifted,
        jnp.asarray(cand_idx)[None], jnp.asarray(cand_val)[None])[0]


@partial(jax.jit, static_argnums=(0, 1, 2))
def dcn_candidates_forward(cfg: FFMConfig, model: str, backend: str, params,
                           cached, cand_idx, cand_val):
    """The ``dcnv2`` head's staged forward (:func:`dcnv2.candidates_forward`)
    under :func:`batched_candidates_forward`'s signature: ``params`` the
    head's serving form (:func:`dcnv2.serving_params`), ``cached["x0"]``
    (R, Fc, k) stacked context states, cand_idx/val (R, N, F-Fc) -> logits
    (R, N). Its gathers are in-trace, from the resident twin on an
    accelerator."""
    return dcnv2.candidates_forward(cfg, params, cached["x0"], cand_idx,
                                    cand_val)


# ---------------------------------------------------------------------------
# Model heads
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Head:
    """What differs between the engine's model heads. Everything else on
    the scoring path is shared: the prefix trie, dedup, buckets, warmup,
    spans, the device twin.

    ``tables(params)`` is ``(emb, lr)``, the gather tables (``lr`` None for
    a head without one). The host context state the prefix cache holds:
    ``empty_state(cfg, params)``, ``extend_state(cfg, emb, lr, state, idx,
    val)`` (the tail arithmetic, on host numpy views of the tables) and
    ``slice_state(state, depth)``, which must be a pure slice;
    ``dummy_state(cfg, params, rows)`` stacks zeros of it for warmup.
    ``forward(cfg, model, backend, params, cached, cand_idx, cand_val)`` is
    the jitted staged forward, which takes ``serving_params(cfg, backend,
    params)``: what depends on the published params alone, built once per
    params object (:meth:`InferenceEngine._device_params`), or ``params``
    itself. ``oracle(cfg, params, idx, val, model, interactions_fn)`` is the
    uncached forward (:meth:`InferenceEngine.score_uncached`). ``fleet``
    says whether the update pipe, the shard router and the host pre-gather
    take the head."""

    tables: Callable
    empty_state: Callable
    extend_state: Callable
    slice_state: Callable
    dummy_state: Callable
    forward: Callable
    serving_params: Callable
    oracle: Callable
    fleet: bool


def _ffm_dummy_state(cfg: FFMConfig, params, rb: int):
    fc = cfg.context_fields
    return {
        "emb": np.zeros((rb, fc, cfg.n_fields, cfg.k),
                        ffm.table_dtype(params["ffm"]["emb"])),
        "val": np.zeros((rb, fc), np.float32),
        "pairs": np.zeros((rb, ffm.prefix_pair_count(fc)), np.float32),
        "lr_terms": np.zeros((rb, fc), np.float32),
    }


FFM_HEAD = Head(
    tables=lambda params: (params["ffm"]["emb"], params["lr"]["w"]),
    empty_state=lambda cfg, params: ffm.empty_context_prefix_np(
        cfg, ffm.table_dtype(params["ffm"]["emb"])),
    extend_state=ffm.extend_context_prefix_np,
    slice_state=ffm.slice_context_prefix,
    dummy_state=_ffm_dummy_state,
    forward=batched_candidates_forward,
    serving_params=lambda cfg, backend, params: params,
    oracle=lambda cfg, params, idx, val, model, interactions_fn:
        deepffm.forward(cfg, params, idx, val, model,
                        interactions_fn=interactions_fn),
    fleet=True)


DCN_HEAD = Head(
    tables=lambda params: (params["emb"], None),
    empty_state=lambda cfg, params: dcnv2.empty_state_np(cfg),
    extend_state=lambda cfg, emb, lr, state, idx, val:
        dcnv2.extend_state_np(cfg, emb, state, idx, val),
    slice_state=dcnv2.slice_state,
    dummy_state=lambda cfg, params, rb: {
        "x0": np.zeros((rb, cfg.context_fields, cfg.k), np.float32)},
    forward=dcn_candidates_forward,
    serving_params=dcnv2.serving_params,
    oracle=lambda cfg, params, idx, val, model, interactions_fn:
        dcnv2.forward(cfg, params, idx, val),
    fleet=False)

HEADS = {"dcnv2": DCN_HEAD}


def head_of(model: str) -> Head:
    """The head serving ``model``: ``dcnv2``, else the FFM family's."""
    return HEADS.get(model, FFM_HEAD)


def refuse_fleet(model: str, what: str) -> None:
    """A clear error where ``what`` cannot serve ``model``'s head."""
    if not head_of(model).fleet:
        raise ValueError(f"{what} does not serve the {model!r} head: it "
                         f"scores on one InferenceEngine's staged path only")


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class InferenceEngine:
    """Single scoring path for the serving stack: prefix-sharing context cache
    x cross-request candidate dedup x Pallas kernel x cache-preserving hot
    weight swaps x bucketed request batching.

    Constructor knobs beyond the PR 1 surface:

    * ``prefix_stride`` — spacing of the prefix cache's checkpoint depths.
      ``None`` stores only full-depth entries (exact-match caching, the PR 1
      behaviour); smaller strides share more prefix work per miss.
    * ``dedup`` — score each unique ``(context, candidate)`` row once per
      microbatch and scatter results back per request.
    * ``warmup_buckets`` — ``(max_requests, max_candidates)``; when given
      (and params are installed) every padding-bucket/tail shape combination
      is pre-compiled at construction via :meth:`warmup`.
    * ``quantized`` — serve from int8 row-quantized embedding tables (§6):
      installed/ingested f32 params are row-quantized
      (``quantization.quantize_params_rows``; the update pipe requantizes
      only a delta frame's touched rows) and scoring dequantizes gathered
      rows in-register. One-flag switch; the f32 default is the oracle. See
      the module docstring for the tolerance contract.
    * ``prefix_depths`` — explicit checkpoint-depth set for the prefix
      cache, overriding ``prefix_stride``; feed it from
      :meth:`suggest_checkpoint_depths` of a running engine to adapt the
      depth set to observed traffic.
    * ``host_gather`` — pre-gather candidate rows/LR terms on host (packed
      numpy gather) and score through :func:`batched_candidates_forward_q8`
      (int8 tables) or :func:`batched_candidates_forward_rows` (f32 tables),
      dodging the XLA-CPU gather cliff — both dtypes hit it; on the CPU
      backend the threshold is probed per process at engine startup
      (``row_gather.ops.cliff_rows``, constant fallback via
      ``REPRO_CLIFF_CALIBRATE=0``). ``None`` (default) auto-selects by
      table size and backend (``row_gather.ops.use_host_gather``).
    * ``fused`` — score each padding bucket in one fused Pallas call
      (:func:`fused_candidates_forward_q8` / ``_rows``): ctx-tail pairs +
      candidate pair terms + additive head, int8 pair arithmetic on
      quantized tables (``"ffm"`` model only — see the module docstring for
      the tolerance contract and when the staged path remains selected).
      ``True`` forces ``host_gather`` on (the fused forwards consume
      pre-gathered blocks); ``None`` (default) turns it on exactly when the
      engine is a quantized ``"ffm"`` server whose table *auto*-picked the
      host pre-gather — the regime the roofline report shows is bound by
      staged-path memory traffic. Engines with explicitly pinned
      ``host_gather`` keep the staged path unless ``fused=True`` is asked
      for, so bit-exactness expectations against in-trace engines survive.
    * ``parallel`` — worker count for the parallel scoring pipeline (see the
      module docstring's "Parallel scoring" section). ``None`` (default)
      auto-resolves via :func:`auto_parallel_workers`: off (1) on 1-core
      boxes, else one worker per core capped at 4. Any value keeps output
      bit-identical to the single-stream path; ``scoring_pool`` optionally
      injects a shared :class:`ScoringPool` (the ShardRouter threads one
      pool through all its shards).
    """

    def __init__(self, cfg: FFMConfig, model: str = "deepffm", *,
                 backend: str = "reference", params=None,
                 cache_entries: int = 4096, min_bucket: int = 8,
                 prefix_stride: Optional[int] = 4, dedup: bool = True,
                 warmup_buckets: Optional[Tuple[int, int]] = None,
                 quantized: bool = False,
                 prefix_depths: Optional[Sequence[int]] = None,
                 host_gather: Optional[bool] = None,
                 fused: Optional[bool] = None,
                 parallel: Optional[int] = None,
                 scoring_pool: Optional[ScoringPool] = None):
        from repro.kernels.row_gather import ops as rg_ops

        self._head = head_of(model)
        if not self._head.fleet:
            if host_gather:
                refuse_fleet(model, "the host pre-gather (host_gather=True)")
            if cfg.n_cross < 1:
                raise ValueError(f"the {model!r} head needs n_cross >= 1")
            host_gather = False  # its rows are always gathered in-trace
        host_auto = host_gather is None
        resolved_host = (rg_ops.use_host_gather(cfg.hash_space)
                         if host_auto else bool(host_gather))
        if fused is None:
            fused = (model == "ffm" and quantized and resolved_host
                     and host_auto)
        elif fused:
            resolved_host = True  # fused forwards consume pre-gathered blocks
        self.plan = ScoringPlan(cfg, model, backend=backend,
                                min_bucket=min_bucket, fused=bool(fused))
        self.cache_entries = cache_entries
        self.dedup = dedup
        self.quantized = quantized
        self.host_gather = resolved_host
        self.weights_version = 0     # trainer's stamp from the update frame
        self._weights: Tuple[Optional[Dict], int] = (  # guarded-by: _lock
            self._maybe_quantize(params), 0)
        self._cache = PrefixCache(  # guarded-by(calls): _lock
            cfg.context_fields, cache_entries,
            stride=prefix_stride, depths=prefix_depths,
            slice_state=self._head.slice_state)
        self._lock = threading.Lock()  # cache structure + counters + weights
        self.hits = 0    # guarded-by: _lock
        self.misses = 0  # guarded-by: _lock
        self.stats = ServeStats()  # guarded-by: _lock
        # batch ids: every span of one score_batch call carries the same one
        self._call_ids = itertools.count()
        self.parallel = (auto_parallel_workers() if parallel is None
                         else max(1, int(parallel)))
        self._scoring_pool = scoring_pool  # guarded-by: _lock
        self._owns_pool = scoring_pool is None
        self._pipe: Optional[UpdatePipe] = None  # guarded-by: _pipe_lock
        self._pipe_lock = threading.Lock()
        # per-request deadline (score_batch(deadline_ms=)): an absolute
        # time.monotonic() budget, thread-local because concurrent scorer
        # threads carry independent budgets through the same engine
        self._deadline_tl = threading.local()
        self._device_params(self.params)  # upload before the first request
        if warmup_buckets is not None and params is not None:
            self.warmup(max_requests=warmup_buckets[0],
                        max_candidates=warmup_buckets[1])

    # -- configuration passthroughs ----------------------------------------
    @property
    def cfg(self) -> FFMConfig:
        return self.plan.cfg

    @property
    def model(self) -> str:
        return self.plan.model

    @property
    def backend(self) -> str:
        return self.plan.backend

    @property
    def fused(self) -> bool:
        return self.plan.fused

    @property
    def params(self):
        return self._weights[0]

    @property
    def generation(self) -> int:
        return self._weights[1]

    @property
    def cache_hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    @property
    def prefix_hit_depths(self) -> Counter:
        """Histogram of cached-prefix depth matched per context lookup
        (depth == context_fields is a full hit, 0 a cold miss)."""
        return self._cache.hit_depths

    @property
    def resident_weight_bytes(self) -> int:
        """Bytes of the currently published weight pytree — ~4x smaller with
        ``quantized=True`` (emb: int8 codes + two f32 scalars per row; LR:
        int8 codes + two f32 scalars per block)."""
        params = self.params
        return 0 if params is None else Q.quantized_nbytes(params)

    def suggest_checkpoint_depths(self, max_depths: int = 4,
                                  min_share: float = 0.05) -> List[int]:
        """Checkpoint depths adapted to observed traffic (ROADMAP follow-on).

        Ranks the intermediate depths of the prefix-hit histogram (collected
        per lookup into :attr:`prefix_hit_depths` alongside ``ServeStats``)
        by how many lookups actually reused a partial there, keeps those
        carrying at least ``min_share`` of the intermediate hits (at most
        ``max_depths`` of them), and always includes the full depth. Pass the
        result as ``prefix_depths=`` to the next engine (the depth set closes
        the compiled tail-shape set, so it is fixed per engine — adapting it
        live would trigger mid-traffic compiles): checkpoints traffic never
        reuses stop costing cache inserts and warmup compiles, while the
        depths real prefix overlap concentrates on survive.
        """
        fc = self.cfg.context_fields
        with self._lock:  # scorer threads insert histogram keys under it
            hist = dict(self._cache.hit_depths)
            current = self._cache.checkpoint_depths()
        inter = {d: c for d, c in hist.items() if 0 < d < fc and c > 0}
        total = sum(inter.values())
        if not total:  # no observed intermediate reuse: keep the current set
            return current
        ranked = sorted(inter.items(), key=lambda dc: (-dc[1], dc[0]))
        keep = [d for d, c in ranked if c / total >= min_share][:max_depths]
        return sorted(set(keep) | {fc})

    # -- weight management (§3 / §6) ---------------------------------------
    def _maybe_quantize(self, params, prev=None, touched_rows=None):
        """Row-quantize the embedding tables of an f32 pytree when this
        engine serves quantized; no-op otherwise (or when ``params`` already
        carries quantized tables)."""
        if not self.quantized or params is None:
            return params
        return Q.quantize_params_rows(params, prev=prev,
                                      touched_rows=touched_rows)

    def install_params(self, params) -> None:
        """Directly swap the weight pytree in place (tests / local serving).
        The (params, generation) pair is published atomically, so concurrent
        scorers see either the old or the new version, never a mix. On a
        quantized engine f32 params are row-quantized here (full-table —
        only the update pipe knows touched rows)."""
        params = self._maybe_quantize(params)
        self._device_params(params)  # upload before the swap
        with self._lock:  # serialize the generation bump against _publish
            self._weights = (params, self._weights[1] + 1)

    def _publish(self, params, version: int, nbytes: int) -> int:
        """Atomically install a fully materialized params pytree (the update
        pipe's publish step — the only weight work under the request lock).
        The quantize fallback and the device twin's upload run *before* the
        lock (the quantize is a no-op for the update pipe, which ships
        already-quantized tables), on the pipe's thread."""
        params = self._maybe_quantize(params)
        self._device_params(params)
        with self._lock:
            self._weights = (params, self._weights[1] + 1)
            self.weights_version = version
            self.stats.updates_applied += 1
            self.stats.update_bytes += nbytes
            return self._weights[1]

    def update_pipe(self, manifest=None, like_params=None) -> UpdatePipe:
        """The engine's (lazily created) trainer-update ingestion pipe."""
        refuse_fleet(self.model, "the update pipe")
        with self._pipe_lock:
            pipe, created = self._pipe, False
            if pipe is None:
                pipe = self._pipe = UpdatePipe(self, manifest=manifest,
                                               like_params=like_params)
                created = True
        # reconfigure outside _pipe_lock: configure serializes behind the
        # pipe's _ingest_lock, which ranks *below* _pipe_lock in the
        # declared order (rotate_shard takes ingest -> pipe)
        if not created and (manifest is not None or like_params is not None):
            pipe.configure(manifest, like_params)
        return pipe

    def apply_update(self, update: bytes, manifest=None, like_params=None) -> None:
        """Ingest one trainer update (full file, patch, or row delta) and
        hot-swap weights — a thin synchronous wrapper over the update pipe.

        Cache-preserving: the prefix tree keeps its entries; lookups compare
        each entry's generation stamp and lazily recompute stale partials, so
        the trie structure, stats, and jit caches all survive the swap.
        Decode/dequant/patch work happens *outside* the request lock; only
        the final (params, generation) pointer swap takes it.
        """
        self.update_pipe().ingest(update, manifest=manifest,
                                  like_params=like_params)

    def submit_update(self, update: bytes, manifest=None,
                      like_params=None) -> bool:
        """Asynchronous :meth:`apply_update`: enqueue the frame for the update
        pipe's background thread and return once it is queued — *not* once it
        is applied. A full pipe queue applies backpressure (blocks the caller
        until a slot frees) rather than dropping, because dropped frames
        would desync the Sender's patch/delta chain. The new generation
        becomes visible to scorers at the pipe's publish; ``update_pipe().
        flush()`` waits for it."""
        pipe = self.update_pipe(manifest, like_params)
        return pipe.submit(update, block=True)

    def prewarm_contexts(self, params=None, generation: Optional[int] = None,
                         chunk: int = 8, pause_s: float = 0.0) -> int:
        """Recompute every cached context partial against ``(params,
        generation)`` — by default the *next* generation — and install the
        results, ``chunk`` contexts per vmap group.

        The update pipe calls this from its deprioritized ingest thread with
        the freshly decoded standby params *before* publishing them: the
        atomic swap then flips both the weights and an already-warm cache, so
        post-swap requests get full-depth hits instead of paying the stale
        recompute on the request path. Cache nodes hold per-generation entry
        slots (two newest), so current-generation scorers keep their hits
        while the next generation warms. ``chunk`` must not exceed the warmed
        request bucket so a prewarm can never trigger a new jit compilation
        mid-traffic; ``pause_s`` sleeps between chunks (cooperative
        throttling on the ingest thread). Returns the number of contexts
        recomputed."""
        if params is None:
            params = self.params
        if params is None:
            return 0
        if generation is None:
            generation = self.generation + 1
        if self._warmed_requests is not None:
            # never exceed the warmed group bucket: a prewarm-triggered jit
            # compile mid-traffic would be the stall this path exists to avoid
            chunk = min(chunk, self._warmed_requests)
        with self._lock:
            keys = self._cache.keys()
        ctxs = [(key, *context_from_tokens(key)) for key in keys]
        for i in range(0, len(ctxs), max(1, chunk)):
            # record_stats=False: prewarm churn must not pollute the
            # request-path hit-depth histogram or partial/tail counters
            self._resolve_contexts(ctxs[i:i + max(1, chunk)], params,
                                   generation, record_stats=False)
            if pause_s:
                time.sleep(pause_s)
        return len(ctxs)

    # -- context cache (§5, prefix tree) ------------------------------------
    _host_tables: Tuple = ()  # up to 2 of (params, emb_view, lr_view)

    def _host_weights(self, params):
        """Host-numpy views of the gather tables for the context-tail path
        (zero-copy on the CPU backend), cached per params object. Two slots —
        the published generation and the standby one the pipe prewarms — so
        concurrent prewarm and scoring never thrash the cache. A benign race:
        concurrent fills compute the same views."""
        for entry in self._host_tables:
            if entry[0] is params:
                return entry[1], entry[2]

        def host_view(t):
            if t is None or hasattr(t, "gather_np"):  # none, or already host
                return t
            if isinstance(t, dict):
                return {k: np.asarray(v) for k, v in t.items()}
            return np.asarray(t)

        emb, lr = (host_view(t) for t in self._head.tables(params))
        self._host_tables = ((params, emb, lr),) + self._host_tables[:1]
        return emb, lr

    _device_tables: Tuple = ()  # up to 2 of (params, device twin)

    def _device_params(self, params):
        """``params`` in the head's serving form (``Head.serving_params``)
        with every numpy leaf copied to the device — the twin the in-trace
        forward (:func:`batched_candidates_forward`) takes, so the gather
        tables cross to the device once per published generation instead
        of inside every forward call. Non-array leaves (the LR table's
        Python-int ``block``) stay as they are. Returns the serving form as
        it is where :func:`_tables_resident` says no twin is kept (host
        pre-gather, CPU backend, sharded views): for the FFM heads that is
        ``params`` itself.

        Cached per params object in two slots, like :meth:`_host_weights`:
        the published generation and the one being published. Construction,
        :meth:`install_params` and :meth:`_publish` fill the slot before the
        swap and :meth:`rotate` hands its twin to the successor, so the
        request path only finds; a miss there uploads too. Every upload
        counts in ``ServeStats.table_uploads`` / ``table_upload_bytes``."""
        for entry in self._device_tables:
            if entry[0] is params:
                return entry[1]
        served = (params if params is None else
                  self._head.serving_params(self.cfg, self.backend, params))
        resident = _tables_resident(self.host_gather, served)
        if not resident and served is params:
            return params
        twin = served
        if resident:
            nbytes = host_arg_nbytes(served)
            twin = jax.block_until_ready(jax.tree_util.tree_map(
                lambda x: (jax.device_put(x)
                           if isinstance(x, (np.ndarray, np.generic)) else x),
                served))
            with self._lock:
                self.stats.table_uploads += 1
                self.stats.table_upload_bytes += nbytes
        self._device_tables = ((params, twin),) + self._device_tables[:1]
        return twin

    def _head_params(self, params):
        """``params`` minus the resident gather tables — what the pre-gather
        scoring path ships into the jit (the tables stay host-side)."""
        out = {k: v for k, v in params.items() if k != "ffm"}
        out["lr"] = {"b": params["lr"]["b"]}
        return out

    def _resolve_contexts(self, ctxs: List[Tuple[Tuple[bytes, ...],
                                                 np.ndarray, np.ndarray]],
                          params, generation: int,
                          record_stats: bool = True,
                          phases: Optional[CallPhases] = None
                          ) -> Tuple[List[Dict], List[bool]]:
        """Full-depth prefix states for each unique (tokens, idx, val) context,
        plus a full-depth-hit flag per context.

        Prefix-tree lookups find the deepest cached partial per context; the
        remaining tails are computed on host per miss group, one group per
        distinct cached depth (a closed set — see ``PrefixCache``).

        Resolution runs in rounds so prefix sharing works *within* a miss
        burst too: when several uncached contexts share a checkpoint prefix,
        one representative per distinct prefix is computed (and inserted)
        first, and the rest re-look-up in the next round to reuse it — the
        sequential walk a radix tree would do, restructured to keep the tail
        computation batched. The tail arithmetic of each miss group is a
        ``serve.tails`` span of ``phases`` (the trie work between them stays
        in the caller's ``serve.resolve``).
        """
        fc = self.cfg.context_fields
        with self._lock:
            checkpoints = [d for d in self._cache.checkpoint_depths()
                           if d < fc]
        states: List[Optional[Dict]] = [None] * len(ctxs)
        full_hit: List[bool] = [False] * len(ctxs)
        head = self._head

        pending = list(range(len(ctxs)))
        first_round = True
        while pending:
            with self._lock:
                looked = {i: self._cache.lookup(ctxs[i][0], generation)
                          for i in pending}
            claimed: set = set()
            miss_groups: Dict[int, List[int]] = {}
            deferred: List[int] = []
            for i in pending:
                depth, state = looked[i]
                if depth == fc:
                    # only possible in the first round: contexts are unique
                    # within a burst, so later rounds never find a full match
                    states[i] = state
                    full_hit[i] = first_round
                    if record_stats:
                        with self._lock:
                            self._cache.hit_depths[fc] += 1
                    continue
                above = [(d, ctxs[i][0][:d]) for d in checkpoints if d > depth]
                if any(c in claimed for c in above):
                    deferred.append(i)  # another context computes this prefix
                else:
                    claimed.update(above)
                    miss_groups.setdefault(depth, []).append(i)
            first_round = False

            # tails are computed on host (the head's extend_state): the
            # arithmetic is tiny (members x tail fields x F x k), so the old
            # vmapped-jit path paid more in group stacking, padded buckets,
            # dispatch, and device->host result transfers than the math —
            # the PR 2 overlap-traffic regression. Host tails also never
            # compile, so prewarm/resolution cannot stall mid-traffic.
            emb_h, lr_h = self._host_weights(params)
            empty = head.empty_state(self.cfg, params)
            for depth, members in sorted(miss_groups.items()):
                t = fc - depth
                fresh = []
                with _span(phases, "serve.tails"):
                    for i in members:
                        base = (head.slice_state(looked[i][1], depth)
                                if looked[i][1] is not None else empty)
                        fresh.append(head.extend_state(
                            self.cfg, emb_h, lr_h, base,
                            ctxs[i][1][depth:], ctxs[i][2][depth:]))
                with self._lock:
                    if record_stats:
                        self.stats.ctx_partials_full += sum(
                            1 for i in members if looked[i][0] == 0)
                        self.stats.ctx_tail_fields += t * len(members)
                    for i, state in zip(members, fresh):
                        if record_stats:
                            self._cache.hit_depths[depth] += 1
                        states[i] = state
                        self._cache.insert(ctxs[i][0], generation, state)
            pending = deferred
        return states, full_hit

    def _resolve_contexts_fused(self, ctxs: List[Tuple[Tuple[bytes, ...],
                                                       np.ndarray, np.ndarray]],
                                params, generation: int,
                                record_stats: bool = True,
                                phases: Optional[CallPhases] = None):
        """Gather-only context resolution for the fused scoring path.

        Returns ``(states, insert_info, full_hit)``: per context a stackable
        fused state (``ffm.fused_context_state_np`` — full-depth rows + LR
        terms + cached depth and pair sum, *no* host pair arithmetic), plus
        for each cache miss the ``(depth, prefix_pairs)`` needed to rebuild
        and insert the full-depth state after the kernel returns its ctx
        pair matrix (:meth:`_insert_fused_misses`).

        Unlike the staged resolver this runs a single round: the tail pairs
        don't exist until the fused kernel runs, so contexts in one burst
        can't chain off each other's fresh inserts — each extends
        independently from its deepest *already-cached* prefix. The cache
        still learns (inserts land post-scoring), so steady-state traffic
        converges to the same hit depths. The pass over the contexts, where
        the misses' rows are gathered, is one ``serve.tails`` span of
        ``phases``.
        """
        fc = self.cfg.context_fields
        states: List[Optional[Dict]] = [None] * len(ctxs)
        insert_info: List[Optional[Tuple]] = [None] * len(ctxs)
        full_hit: List[bool] = [False] * len(ctxs)
        with self._lock:
            looked = [self._cache.lookup(c[0], generation) for c in ctxs]
        emb_h, lr_h = self._host_weights(params)
        empty = ffm.empty_context_prefix_np(
            self.cfg, ffm.table_dtype(params["ffm"]["emb"]))
        n_full = tails = 0
        with _span(phases, "serve.tails"):
            for i, (toks, ci, cv) in enumerate(ctxs):
                depth, state = looked[i]
                if depth == fc:
                    full_hit[i] = True
                    states[i] = {
                        "emb": state["emb"], "val": state["val"],
                        "depth": np.int32(fc),
                        "pair_sum": np.float32(
                            np.asarray(state["pairs"]).sum()),
                        "lr_terms": state["lr_terms"],
                    }
                    continue
                base = (ffm.slice_context_prefix(state, depth)
                        if state is not None else empty)
                states[i] = ffm.fused_context_state_np(
                    self.cfg, emb_h, lr_h, base, ci[depth:], cv[depth:])
                insert_info[i] = (depth, np.array(base["pairs"], np.float32,
                                                  copy=True))
                n_full += depth == 0
                tails += fc - depth
        if record_stats:
            with self._lock:
                for (depth, _), info in zip(looked, insert_info):
                    self._cache.hit_depths[fc if info is None else depth] += 1
                self.stats.ctx_partials_full += n_full
                self.stats.ctx_tail_fields += tails
        return states, insert_info, full_hit

    def _insert_fused_misses(self, u_ctxs, states, insert_info, chunk_group,
                             u_of_group, ctx_dots, generation: int) -> None:
        """Post-scoring cache insertion for the fused path: rebuild each
        missed context's full-depth prefix state from the kernel's returned
        ctx pair matrix and insert it. ``chunk_group`` maps forward rows to
        groups; on a no-dedup engine ``u_of_group`` maps groups back to
        unique contexts. A context whose requests all carried empty slates
        never entered the forward and stays uninserted (no pair matrix to
        read back — the staged resolver will fill it on its next miss)."""
        if all(info is None for info in insert_info):
            return
        first_chunk: Dict[int, int] = {}
        for c, g in enumerate(chunk_group):
            u = int(g) if self.dedup else int(u_of_group[g])
            first_chunk.setdefault(u, c)
        inserts = []
        for u, info in enumerate(insert_info):
            if info is None or u not in first_chunk:
                continue
            depth, prefix_pairs = info
            inserts.append((u, ffm.prefix_state_from_dots_np(
                self.cfg, states[u], prefix_pairs,
                ctx_dots[first_chunk[u]])))
        with self._lock:
            for u, full in inserts:
                self._cache.insert(u_ctxs[u][0], generation, full)

    # -- scoring ------------------------------------------------------------
    def _require_params(self):
        if self.params is None:
            raise RuntimeError("no weights yet — apply_update first")

    def score(self, ctx_idx, ctx_val, cand_idx, cand_val, *,
              deadline_ms: Optional[float] = None) -> np.ndarray:
        """Score one request's candidates against its context. Returns logits (N,)."""
        return self.score_batch([(ctx_idx, ctx_val, cand_idx, cand_val)],
                                deadline_ms=deadline_ms)[0]

    def _deadline(self) -> Optional[float]:
        """The in-flight request's absolute ``time.monotonic()`` budget on
        this thread (None = unbounded) — set by ``score_batch(deadline_ms=)``
        and consumed by the ShardRouter's scatter-gather waits."""
        return getattr(self._deadline_tl, "until", None)

    def score_batch(self, requests: Sequence[Tuple], *,
                    deadline_ms: Optional[float] = None) -> List[np.ndarray]:
        """Microbatch several (ctx_idx, ctx_val, cand_idx, cand_val) requests.

        Contexts are resolved through the prefix cache (tails batched per miss
        group); identical ``(context, candidate)`` rows across the microbatch
        are scored once and scattered back (``dedup=True``). The scored rows
        are padded to one power-of-two candidate bucket and a power-of-two row
        axis, so the whole batch is a single jitted call with a small, closed
        set of compiled shapes. Scores are computed against exactly one
        atomically published (params, generation) snapshot.

        ``deadline_ms`` attaches a wall-clock budget to this batch (see the
        module docstring's degraded-response contract): a plain engine's
        single forward always runs to completion, but a fan-out engine
        (ShardRouter) bounds its scatter-gather waits by it and zero-fills
        slices that cannot answer in time, flagging the response degraded.
        """
        if deadline_ms is None:
            return self._score_batch(requests)
        self._deadline_tl.until = time.monotonic() + deadline_ms / 1e3
        try:
            return self._score_batch(requests)
        finally:
            self._deadline_tl.until = None

    def _score_batch(self, requests: Sequence[Tuple]) -> List[np.ndarray]:
        self._require_params()
        if not requests:
            return []
        phases = CallPhases(next(self._call_ids))
        with phases.span("serve.score_batch"):
            results = self._score_requests(requests, phases)
        with self._lock:
            self.stats.add_phases(phases.totals())
        return results

    def _score_requests(self, requests: Sequence[Tuple],
                        phases: CallPhases) -> List[np.ndarray]:
        """The body of :meth:`score_batch`; its phases are the spans
        ``serve.resolve``, ``serve.tails``, ``serve.dedup``,
        ``serve.prepare``, ``serve.pool_wait``, ``serve.launch``,
        ``serve.device_wait`` and ``serve.finish`` of ``phases``."""
        t0 = time.perf_counter()
        params, generation = self._weights

        fcand = self.cfg.n_fields - self.cfg.context_fields

        def slate(a, dtype):
            # normalize empty slates (any shape) to (0, Fcand) so empty and
            # non-empty requests concatenate in one microbatch; anything
            # non-empty must already be (N, Fcand) — a silent reshape would
            # misread e.g. full feature rows as extra candidates
            a = np.asarray(a, dtype)
            if a.size == 0:
                return a.reshape(0, fcand)
            if a.ndim != 2 or a.shape[1] != fcand:
                raise ValueError(
                    f"candidate slate must be (N, {fcand}), got {a.shape}")
            return a

        reqs = [(np.asarray(ci, np.int32), np.asarray(cv, np.float32),
                 slate(ki, np.int32), slate(kv, np.float32))
                for ci, cv, ki, kv in requests]

        with phases.span("serve.resolve"):
            # unique contexts across the microbatch
            u_of: List[int] = []
            u_index: Dict[Tuple[bytes, ...], int] = {}
            u_ctxs: List[Tuple[Tuple[bytes, ...], np.ndarray,
                               np.ndarray]] = []
            for ci, cv, ki, kv in reqs:
                toks = context_tokens(ci, cv)
                u = u_index.get(toks)
                if u is None:
                    u = u_index[toks] = len(u_ctxs)
                    u_ctxs.append((toks, ci, cv))
                u_of.append(u)

            if self.fused:
                states, insert_info, full_hit = (
                    self._resolve_contexts_fused(u_ctxs, params, generation,
                                                 phases=phases))
            else:
                states, full_hit = self._resolve_contexts(
                    u_ctxs, params, generation, phases=phases)
            # hit/miss bookkeeping matches the flat cache: first request of
            # an uncached context is the miss, every other request this batch
            # (and every full-depth match) is a hit
            seen_full = dict(enumerate(full_hit))
            with self._lock:
                for u in u_of:
                    if seen_full[u]:
                        self.hits += 1
                    else:
                        self.misses += 1
                        seen_full[u] = True

        with phases.span("serve.dedup"):
            # candidate rows: dedup identical (context, candidate) pairs
            # across requests, or keep one row-group per request
            if self.dedup:
                group_of_req = u_of
                n_groups = len(u_ctxs)
                group_state = states
            else:
                group_of_req = list(range(len(reqs)))
                n_groups = len(reqs)
                group_state = [states[u] for u in u_of]
            counts = np.asarray([r[2].shape[0] for r in reqs], np.int64)
            total = int(counts.sum())
            if total == 0:  # every request carried an empty slate
                with self._lock:
                    self.stats.record(time.perf_counter() - t0, 0,
                                      requests=len(reqs))
                return [np.zeros((0,), np.float32) for _ in reqs]
            group_of_row = np.repeat(np.asarray(group_of_req, np.int64),
                                     counts)
            ki_all = np.concatenate([r[2] for r in reqs])      # (total, Fcand)
            kv_all = np.concatenate([r[3] for r in reqs])
            if self.dedup:
                # packed-array dedup: one contiguous (group | idx | val-bits)
                # int32 matrix viewed as void rows for np.unique — identical
                # semantics to per-row byte keys, no Python-level row loop
                mat = np.empty((total, 1 + 2 * fcand), np.int32)
                mat[:, 0] = group_of_row
                mat[:, 1:1 + fcand] = ki_all
                mat[:, 1 + fcand:] = kv_all.view(np.int32)
                packed = np.ascontiguousarray(mat).view(
                    np.dtype((np.void, mat.itemsize * mat.shape[1])))[:, 0]
                _, first, inverse = np.unique(packed, return_index=True,
                                              return_inverse=True)
            else:
                first = inverse = np.arange(total)
            u_group = group_of_row[first]
            n_rows = int(first.size)

            # a dedup group unions candidates from several requests and can
            # exceed the per-request bucket; chunk groups to the request-level
            # bucket so padded work never exceeds the no-dedup layout and the
            # compiled shape set stays the closed per-request one (see warmup)
            nb = self.plan.bucket(int(counts.max()))
            order = np.argsort(u_group, kind="stable")
            gcounts = np.bincount(u_group, minlength=n_groups)
            gstarts = np.concatenate([[0], np.cumsum(gcounts)[:-1]])
            pos = np.empty(n_rows, np.int64)  # rank of each row in its group
            pos[order] = np.arange(n_rows) - np.repeat(gstarts, gcounts)
            chunks_per_g = -(-gcounts // nb)
            chunk_base = np.concatenate([[0], np.cumsum(chunks_per_g)[:-1]])
            n_chunks = int(chunks_per_g.sum())
            row_of_u = chunk_base[u_group] + pos // nb
            slot_of_u = pos % nb

            # unpadded (n_chunks, nb, Fcand) candidate blocks, built once;
            # the span scorer pads each contiguous chunk span to its own
            # power-of-two row bucket (a single span of every chunk
            # reproduces the padded single-stream call exactly)
            ki_c = np.zeros((n_chunks, nb, fcand), np.int32)
            kv_c = np.zeros((n_chunks, nb, fcand), np.float32)
            ki_c[row_of_u, slot_of_u] = ki_all[first]
            kv_c[row_of_u, slot_of_u] = kv_all[first]
            grids_c = self._compact_grids(params, ki_all[first], row_of_u,
                                          slot_of_u, n_chunks, nb, fcand)

            chunk_group = np.repeat(np.arange(n_groups), chunks_per_g)
            chunk_state = [group_state[g] for g in chunk_group]
        batch_stats = ServeStats()  # the forwards' counters land here too
        out, ctx_dots = self._score_spans(params, chunk_state, ki_c, kv_c,
                                          grids_c, self._plan_spans(n_chunks),
                                          batch_stats, phases)
        with phases.span("serve.finish"):
            if self.fused:
                self._insert_fused_misses(u_ctxs, states, insert_info,
                                          chunk_group, u_of, ctx_dots,
                                          generation)
            # plain numpy scatter-back (no per-request device gathers)
            flat = out[row_of_u[inverse], slot_of_u[inverse]]
            offs = np.concatenate([[0], np.cumsum(counts)])
            results = [flat[offs[i]:offs[i + 1]] for i in range(len(reqs))]
            # per-batch stats accumulate outside the lock and merge in one
            # shot: one record per caller-visible batch no matter how many
            # chunk spans the parallel pipeline dispatched (see
            # ServeStats.merge)
            batch_stats.rows_scored = n_rows
            batch_stats.record(time.perf_counter() - t0, total,
                               requests=len(reqs))
            with self._lock:
                self.stats.merge(batch_stats)
        return results

    # -- parallel scoring pipeline ------------------------------------------
    def _get_pool(self) -> ScoringPool:
        """The engine's scoring pool, created lazily on the first split batch
        (or injected shared via ``scoring_pool=``)."""
        if self._scoring_pool is None:
            with self._lock:
                if self._scoring_pool is None:
                    self._scoring_pool = ScoringPool(self.parallel)
        return self._scoring_pool

    def close(self) -> None:
        """Shut down the engine-owned scoring pool (a shared injected pool is
        its owner's to close). Idempotent; the engine keeps serving — a later
        split batch just lazily recreates the pool."""
        pool, self._scoring_pool = self._scoring_pool, None
        if pool is not None and self._owns_pool:
            pool.shutdown()
        self._owns_pool = True

    def _plan_spans(self, n_chunks: int) -> List[Tuple[int, int]]:
        """Split ``[0, n_chunks)`` into contiguous near-equal per-worker
        spans. Each span pads to ``plan.bucket(span_len)`` — a power-of-two
        no larger than the full batch's row bucket, so the compiled shape
        set stays the closed one :meth:`warmup` enumerates."""
        w = self.parallel
        if w <= 1 or n_chunks <= 1:
            return [(0, n_chunks)]
        w = min(w, n_chunks)
        base, rem = divmod(n_chunks, w)
        spans, lo = [], 0
        for i in range(w):
            hi = lo + base + (1 if i < rem else 0)
            spans.append((lo, hi))
            lo = hi
        return spans

    def _compact_grids(self, params, ki_u, row_of_u, slot_of_u,
                       n_chunks: int, nb: int, fcand: int):
        """(scale, zero) dequant grids for the padded block, gathered **once
        per unique deduped candidate row** and broadcast by the same
        ``(row, slot)`` scatter the codes use — the staged/fused q8 forwards
        previously re-gathered the f32 grids per padded row
        (``scale[ki_b]``), the measured per-prediction byte waste ROADMAP
        open item 2 names. Padded slots keep grid zeros (their dequantized
        rows become exact zeros; per-slot logits are independent and padded
        outputs are never read). ``None`` on engines whose forward takes no
        host-side grids."""
        if not self.host_gather:
            return None
        if not Q.is_row_quantized(params["ffm"]["emb"]):
            return None
        emb_h, _ = self._host_weights(params)
        s_c = np.zeros((n_chunks, nb, fcand), np.float32)
        z_c = np.zeros((n_chunks, nb, fcand), np.float32)
        s_c[row_of_u, slot_of_u] = emb_h["scale"][ki_u]
        z_c[row_of_u, slot_of_u] = emb_h["zero"][ki_u]
        return s_c, z_c

    def _score_spans(self, params, chunk_state, ki_c, kv_c, grids_c, spans,
                     stats: ServeStats,
                     phases: Optional[CallPhases] = None):
        """Score contiguous chunk spans and reassemble ``(logits (n_chunks,
        nb), ctx_dots | None)`` in fixed chunk order — the parallel pipeline's
        core. One span runs inline (exactly the single-stream path). Several
        spans run through the :class:`ScoringPool`: the host pre-gather for
        span *k+1* (on pool threads, into recycled double buffers) overlaps
        the GIL-releasing jit/Pallas dispatch for span *k* (on this thread).
        Because every span is padded to its own bucket, dispatched in order,
        and sliced back to its true length, the reassembled block is
        bit-identical for every worker count: per-row outputs of all the
        jitted forwards are invariant to the row-bucket size, and all spans
        share this batch's one resolved context snapshot.

        Each forward call adds its host argument bytes and padded slots to
        ``stats``; its phases are the ``serve.prepare``, ``serve.launch``
        and ``serve.device_wait`` spans of ``phases``.
        """
        n_chunks = ki_c.shape[0]
        pool = self._get_pool() if len(spans) > 1 else None
        codes_tbl = None
        if pool is not None and self.host_gather:
            emb = params["ffm"]["emb"]
            emb_h, _ = self._host_weights(params)
            if Q.is_row_quantized(emb):
                codes_tbl = emb_h["codes"]
            elif not isinstance(emb, dict):
                codes_tbl = emb_h

        def pad_rows(x, rb_s, m):
            if rb_s == m:
                return x
            return np.concatenate(
                [x, np.zeros((rb_s - m,) + x.shape[1:], x.dtype)])

        def prepare(lo, hi):
            with _span(phases, "serve.prepare"):
                return build(lo, hi)

        def build(lo, hi):
            m = hi - lo
            rb_s = self.plan.bucket(m, minimum=1)
            ki_b = pad_rows(ki_c[lo:hi], rb_s, m)
            kv_b = pad_rows(kv_c[lo:hi], rb_s, m)
            stacked = jax.tree_util.tree_map(
                lambda *xs: np.stack(xs), *chunk_state[lo:hi])
            if rb_s > m:
                stacked = jax.tree_util.tree_map(
                    lambda x: pad_rows(x, rb_s, m), stacked)
            grids = None
            if grids_c is not None:
                grids = (pad_rows(grids_c[0][lo:hi], rb_s, m),
                         pad_rows(grids_c[1][lo:hi], rb_s, m))
            out_codes = None
            if codes_tbl is not None:
                out_codes = pool.acquire(
                    ki_b.shape + codes_tbl.shape[1:], codes_tbl.dtype)
            fn_args = self._forward_args(params, stacked, ki_b, kv_b,
                                         grids=grids, out_codes=out_codes)
            return fn_args, m, out_codes, rb_s * ki_b.shape[1]

        def dispatch(prepared):
            (fn, args), m, buf, slots = prepared
            stats.host_arg_bytes += host_arg_nbytes(args)
            stats.slots_scored += slots
            try:
                with _span(phases, "serve.launch"):
                    fwd = fn(*args)
                with _span(phases, "serve.device_wait"):
                    fwd = jax.block_until_ready(fwd)
                    if self.fused:
                        out_s, dots_s = fwd
                        return np.asarray(out_s)[:m], np.asarray(dots_s)[:m]
                    return np.asarray(fwd)[:m], None
            finally:
                if buf is not None:
                    # on success the computation has completed (no XLA alias);
                    # on error nothing holds the buffer either — either way it
                    # must return to the free list or the burst leaks it
                    pool.release(buf)

        def span_cleanup(prepared):
            # drain path (ScoringPool.run): a prepared-but-never-dispatched
            # span still owns its acquired gather buffer
            buf = prepared[2]
            if buf is not None:
                pool.release(buf)

        if pool is None:
            lo, hi = spans[0]
            parts = [dispatch(prepare(lo, hi))]
        else:
            parts = pool.run([partial(prepare, lo, hi) for lo, hi in spans],
                             dispatch, cleanup=span_cleanup, phases=phases)
        if len(parts) == 1:
            out, dots = parts[0]
        else:
            out = np.concatenate([p[0] for p in parts])
            dots = (np.concatenate([p[1] for p in parts])
                    if self.fused else None)
        assert out.shape[0] == n_chunks
        return out, dots

    def _forward_args(self, params, stacked, ki_b, kv_b, grids=None,
                      out_codes=None):
        """Pick the jitted forward for one padded candidate block and build
        its argument tuple — the host pre-gather (candidate codes/rows + LR
        sums via packed numpy gather, immune to the XLA gather cliff)
        happens here. Shared by :meth:`_candidates_forward` (calls it) and
        :meth:`lower_candidates_forward` (lowers it), so the lowered program
        is exactly the deployed forward.

        ``grids`` is the compact-gathered padded ``(scale, zero)`` pair
        :meth:`score_batch` builds once per unique deduped row
        (:meth:`_compact_grids`); ``None`` falls back to the per-padded-row
        table gather (warmup dummies, ``score_uncached``). ``out_codes`` is
        an optional caller-provided destination for the packed code/row
        gather — the scoring pool's recycled double buffer. The in-trace
        forward takes the whole tables as arguments: their device twin
        (:meth:`_device_params`) where the engine keeps one."""
        if self.host_gather:
            from repro.kernels.row_gather import ops as rg_ops

            emb = params["ffm"]["emb"]
            emb_h, lr_h = self._host_weights(params)
            lr_cand = (ffm.gather_lr_np(lr_h, ki_b)
                       * kv_b).sum(-1).astype(np.float32)
            if Q.is_row_quantized(emb):
                if grids is None:
                    grids = (emb_h["scale"][ki_b], emb_h["zero"][ki_b])
                s, z = grids
                qc = rg_ops.gather_codes_np(emb_h["codes"], ki_b,
                                            out=out_codes)
                if self.fused:
                    lr_b = np.float32(
                        np.asarray(params["lr"]["b"], np.float32))
                    return fused_candidates_forward_q8, (
                        self.cfg, lr_b, stacked, qc, s, z, kv_b, lr_cand)
                return batched_candidates_forward_q8, (
                    self.cfg, self.model, self.backend,
                    self._head_params(params), stacked, qc, s, z, kv_b,
                    lr_cand)
            if self.fused:
                lr_b = np.float32(np.asarray(params["lr"]["b"], np.float32))
                ec = rg_ops.gather_codes_np(emb_h, ki_b, out=out_codes)
                return fused_candidates_forward_rows, (
                    self.cfg, lr_b, stacked,
                    np.asarray(ec, np.float32), kv_b, lr_cand)
            if not isinstance(emb, dict):
                # f32 table above the cliff: same packed pre-gather, whole
                # rows instead of codes (the gather moves identical bytes;
                # only the in-jit dequant disappears)
                ec = rg_ops.gather_codes_np(emb_h, ki_b, out=out_codes)
                return batched_candidates_forward_rows, (
                    self.cfg, self.model, self.backend,
                    self._head_params(params), stacked,
                    ec.astype(np.float32, copy=False), kv_b, lr_cand)
        return self._head.forward, (
            self.cfg, self.model, self.backend, self._device_params(params),
            stacked, ki_b, kv_b)

    def _candidates_forward(self, params, stacked, ki_b, kv_b, grids=None):
        """Route one padded candidate block through the right jitted forward
        (see :meth:`_forward_args`). Fused engines return ``(logits,
        ctx_dots)``; staged ones return logits."""
        fn, args = self._forward_args(params, stacked, ki_b, kv_b,
                                      grids=grids)
        return fn(*args)

    def _warmup_dummies(self, rb: int, nb: int):
        """Numpy dummy (cached-state, cand-idx, cand-val) arguments for one
        (row-bucket, candidate-bucket) shape — what :meth:`warmup` calls and
        :meth:`lower_candidates_forward` lowers."""
        cfg = self.cfg
        fc, fcand = cfg.context_fields, cfg.n_fields - cfg.context_fields
        if self.fused:
            emb_dt = ffm.table_dtype(self.params["ffm"]["emb"])
            cached = {
                "emb": np.zeros((rb, fc, cfg.n_fields, cfg.k), emb_dt),
                "val": np.zeros((rb, fc), np.float32),
                "depth": np.zeros((rb,), np.int32),
                "pair_sum": np.zeros((rb,), np.float32),
                "lr_terms": np.zeros((rb, fc), np.float32),
            }
        else:
            cached = self._head.dummy_state(cfg, self.params, rb)
        return (cached, np.zeros((rb, nb, fcand), np.int32),
                np.zeros((rb, nb, fcand), np.float32))

    def lower_candidates_forward(self, rb: int, nb: int):
        """Lower (trace, don't run) the deployed candidate forward at one
        (row-bucket, candidate-bucket) shape and return the jax ``Lowered``
        (its ``args_info`` gives the arguments' shapes and dtypes). Uses the
        same argument builder as the hot path, so the lowered program is
        byte-for-byte the one requests run, not a stub."""
        self._require_params()
        params, _ = self._weights
        cached, ki_b, kv_b = self._warmup_dummies(rb, nb)
        fn, args = self._forward_args(params, cached, ki_b, kv_b)
        return fn.lower(*args)

    _warmed_requests: Optional[int] = None  # set by warmup(); clamps prewarm
    _warmed_buckets: Optional[Tuple[int, int]] = None  # rotate() re-warms these

    def warmup(self, *, max_requests: int = 8, max_candidates: int = 64) -> int:
        """Pre-compile every jitted shape the engine can emit for microbatches
        of up to ``max_requests`` requests with up to ``max_candidates``
        candidates each: all (row-bucket, candidate-bucket) combinations of
        :func:`batched_candidates_forward`. (Context tails run on host —
        :func:`ffm.extend_context_prefix_np` — and never compile.) Returns
        the number of warmup calls issued. Uses the installed params, so it
        must run after weights are available (the constructor's
        ``warmup_buckets`` runs it when params are passed in)."""
        self._require_params()
        self._warmed_requests = max_requests
        self._warmed_buckets = (max_requests, max_candidates)
        params, _ = self._weights
        rbs = self.plan.buckets_upto(max_requests, minimum=1)
        calls = 0
        # numpy dummies, matching the hot path: jax's jit cache keys on the
        # argument container type, so warming with device arrays would leave
        # the numpy-argument entries cold. The tables go through
        # _forward_args as on the hot path, so an engine that keeps a device
        # twin warms the twin's entries. On a fused engine the dummies are
        # fused context states (depth/pair_sum instead of the pair vector) —
        # the fused forward's compiled shape set is covered the same way.
        for rb in rbs:
            for nb in self.plan.buckets_upto(max_candidates):
                self._candidates_forward(params,
                                         *self._warmup_dummies(rb, nb))
                calls += 1
        return calls

    def rotate(self, *, max_depths: int = 4, min_share: float = 0.05,
               warmup_buckets: Optional[Tuple[int, int]] = None
               ) -> "InferenceEngine":
        """Build a fully warmed successor engine adapted to observed traffic
        — the auto-rotation primitive (ROADMAP carried item; the shard
        rotation building block).

        The prefix cache's checkpoint-depth set is fixed per engine (it
        closes the compiled tail-shape set), so adapting it means a *new*
        engine: the successor takes :meth:`suggest_checkpoint_depths` of this
        engine's traffic histogram, shares the currently published params by
        reference (already-quantized tables are adopted, not re-quantized),
        carries the generation counter and trainer version stamp forward,
        and pre-compiles the same warmup bucket set this engine ran
        (``warmup_buckets`` overrides; nothing is warmed when neither is
        known). All of that happens off the request path — this engine keeps
        serving throughout. The caller then performs the atomic swap by
        publishing the returned engine into its serving slot
        (:meth:`repro.serving.shard_router.ShardRouter.rotate_shard` is
        exactly that swap, including re-pointing the shard's update pipe so
        the delta-frame chain continues unbroken).
        """
        self._require_params()
        depths = self.suggest_checkpoint_depths(max_depths=max_depths,
                                                min_share=min_share)
        succ = InferenceEngine(
            self.cfg, self.model, backend=self.backend,
            cache_entries=self.cache_entries,
            min_bucket=self.plan.min_bucket, dedup=self.dedup,
            quantized=self.quantized, prefix_depths=depths,
            host_gather=self.host_gather, fused=self.fused,
            parallel=self.parallel)
        succ.weights_version = self.weights_version
        # adopt the published pytree by reference (already-quantized tables
        # must not re-walk the quantizer) and keep the generation counter
        # monotonic across the swap: scorers comparing generations must
        # never see it move backwards. The successor is still private, but
        # it gets published to other threads later — write under its lock
        # so the adoption happens-before any post-publish read.
        params, generation = self._weights
        with succ._lock:
            succ._weights = (params, generation)
        # the device twin too: the successor uploads nothing
        succ._device_tables = tuple(e for e in self._device_tables
                                    if e[0] is params)
        buckets = warmup_buckets or self._warmed_buckets
        if buckets is not None:
            succ.warmup(max_requests=buckets[0], max_candidates=buckets[1])
        return succ

    def score_uncached(self, ctx_idx, ctx_val, cand_idx, cand_val,
                       use_backend: bool = False) -> jnp.ndarray:
        """Baseline: full forward per candidate (context recomputed each time).

        ``use_backend=True`` routes the full forward's interaction hot loop
        through this engine's Pallas kernel; the default stays on the
        reference path so it can serve as the equivalence oracle. On a
        quantized engine this scores against the *quantized* tables
        (``ffm.gather_rows`` dequantizes per gather) — the roundtrip oracle
        for the quantized cached path, not the f32 one.
        """
        self._require_params()
        n = cand_idx.shape[0]
        fc = self.cfg.context_fields
        idx = jnp.concatenate(
            [jnp.broadcast_to(jnp.asarray(ctx_idx), (n, fc)),
             jnp.asarray(cand_idx)], axis=1)
        val = jnp.concatenate(
            [jnp.broadcast_to(jnp.asarray(ctx_val), (n, fc)),
             jnp.asarray(cand_val)], axis=1)
        interactions_fn = None
        if use_backend and self.backend == "pallas":
            from repro.kernels.ffm_interaction import ops as ffm_ops

            interactions_fn = ffm_ops.interactions
        return self._head.oracle(self.cfg, self.params, idx, val, self.model,
                                 interactions_fn)
