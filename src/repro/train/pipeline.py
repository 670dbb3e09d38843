"""Unified online-training pipeline — the trainer half of the paper, in one
place (§3 online rounds, §4.2 Hogwild, §4.3 sparse updates, §6 transfer).

One :class:`TrainingPipeline` round closes the train->serve loop end to end:

  prefetched ingest (§4.1) -> one **jitted AdaGrad round step** (buffer
  donation + ``lax.scan`` over microbatches, §4.3 sparse backward on by
  default) -> touched-row tracking -> versioned update frame (row **delta**
  in steady state, §6) -> the serving engine's async update pipe.

The gradient/update math is the single :func:`make_round_step` built from
``optim.adagrad``; the three execution strategies are backends of the same
:class:`TrainerBackend` protocol:

* ``jit``       — the sequential reference: whole round is one jitted scan.
* ``hogwild``   — §4.2 faithful CPU mechanism (threads over shared buffers),
  now sharing ``optim.adagrad`` instead of a duplicated update rule.
* ``local_sgd`` — the TPU-native Hogwild analogue (vmap workers + merge).

Every round produces a :class:`RoundReport` carrying progressive-validation
AUC (scores taken from the same forward the gradient uses — strictly
pre-update, VW-style), the §4.3 ``skip_stats``, and the update framing that
went over the wire.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Iterable, List, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store, transfer
from repro.common.config import FFMConfig
from repro.common.metrics import roc_auc
from repro.core import deepffm, ffm, sparse_updates
from repro.data.prefetch import Prefetcher
from repro.optim import make_optimizer
from repro.optim.optimizers import Optimizer

BACKENDS = ("jit", "hogwild", "local_sgd")

_KIND_NAMES = {transfer.KIND_FULL: "full", transfer.KIND_PATCH: "patch",
               transfer.KIND_DELTA: "delta"}


@dataclass
class RoundReport:
    """One online round, as reported to the deployment's control plane."""

    round: int               # == the update frame's version stamp
    examples: int
    seconds: float
    mean_loss: float
    progressive_auc: float
    update_bytes: int
    examples_per_s: float = 0.0
    skip_stats: Dict[str, float] = field(default_factory=dict)
    touched_rows: int = 0    # unique embedding/LR rows updated this round
    update_kind: str = "full"  # full | patch | delta


@dataclass
class RoundMetrics:
    """What a backend hands back from one round of updates."""

    examples: int = 0
    losses: List[float] = field(default_factory=list)
    labels: List[np.ndarray] = field(default_factory=list)
    scores: List[np.ndarray] = field(default_factory=list)
    # per hidden layer: (n_updates, H) column-alive booleans (§4.3)
    col_alive: List[np.ndarray] = field(default_factory=list)


def emb_leaf_path(model: str) -> Optional[str]:
    """Manifest path of the row-sparse embedding table, if the model has one."""
    return {"ffm": "ffm/emb", "deepffm": "ffm/emb", "mlp": "emb"}.get(model)


def touched_paths(batches: Iterable[Dict[str, Any]], model: str
                  ) -> Tuple[Dict[str, np.ndarray], int]:
    """Row-sparse leaves -> unique rows updated by ``batches`` (§6 deltas).

    Exact by construction: a hashed feature index receives gradient only when
    it occurs in a batch, and both the LR table and the FFM embedding table
    are indexed by the same feature hashes. (A superset — e.g. a feature with
    value 0 — only costs bytes, never correctness.)
    """
    idxs = [np.asarray(b["idx"]).ravel() for b in batches]
    if not idxs:
        return {}, 0
    rows = np.unique(np.concatenate(idxs)).astype(np.int64)
    touched = {"lr/w": rows}
    emb = emb_leaf_path(model)
    if emb is not None:
        touched[emb] = rows
    return touched, int(rows.size)


# ---------------------------------------------------------------------------
# Device row storage of the embedding table
# ---------------------------------------------------------------------------

LANES = 128


def device_row_width(cfg: FFMConfig) -> int:
    """Width of one embedding row in the jit trainer's device table:
    ``n_fields * k``, rounded up to a multiple of 128 lanes on TPU.

    On TPU a ``(V, F, k)`` table (or a ``(V, F*k)`` one whose width is not a
    lane multiple) is stored with ``V`` as the minor axis, and every row
    scatter first copies it into a row-major layout with the minor axis
    padded to 128 lanes — 16 GB per table at production widths (2^22 x 24 x
    8), beyond one v5e chip for the weights and their AdaGrad accumulator.
    A ``(V, W)`` table with ``W`` a lane multiple is row-major as stored, so
    the round step gathers and scatters it in place. Other platforms store
    any table row-major: there the rows are ``n_fields * k`` wide, unpadded.
    """
    lanes = LANES if jax.default_backend() == "tpu" else 1
    return -(-cfg.n_fields * cfg.k // lanes) * lanes


def _get_emb(tree, model: str):
    return tree["emb"] if model == "mlp" else tree["ffm"]["emb"]


def _set_emb(tree, model: str, emb):
    if model == "mlp":
        return {**tree, "emb": emb}
    return {**tree, "ffm": {**tree["ffm"], "emb": emb}}


@partial(jax.jit, static_argnums=(0, 1))
def to_device_rows(cfg: FFMConfig, model: str, tree):
    """``(V, F, k)`` embedding leaf of a params/accumulator tree ->
    zero-padded ``(V, device_row_width)`` rows."""
    emb = _get_emb(tree, model)
    flat = emb.reshape(emb.shape[0], -1)
    pad = device_row_width(cfg) - flat.shape[1]
    return _set_emb(tree, model, jnp.pad(flat, ((0, 0), (0, pad))))


@partial(jax.jit, static_argnums=(0, 1))
def from_device_rows(cfg: FFMConfig, model: str, tree):
    """Inverse of :func:`to_device_rows`: the model's ``(V, F, k)`` view."""
    emb = _get_emb(tree, model)
    rows = emb[:, :cfg.n_fields * cfg.k]
    return _set_emb(tree, model,
                    rows.reshape(emb.shape[0], cfg.n_fields, cfg.k))


# ---------------------------------------------------------------------------
# The shared jitted round step
# ---------------------------------------------------------------------------

def make_round_step(cfg: FFMConfig, model: str, opt: Optimizer, *,
                    sparse_backward: bool = True, donate: bool = True):
    """One round = one jitted call: ``lax.scan`` over a stacked microbatch
    axis, AdaGrad from ``optim.adagrad``, params/opt-state buffers donated.

    This is the *dense* reference step (full-space gradient and update per
    microbatch, like the seed loop); :func:`make_sparse_round_step` is the
    production variant whose per-batch cost scales with the batch, not the
    model. Kept for equivalence testing and models/optimizers that need
    full-space updates.

    Returns ``round_fn(params, opt_state, step, batches) ->
    (params, opt_state, step, outs)`` where ``batches`` leaves carry a
    leading microbatch axis M and ``outs`` holds per-update losses (M,),
    pre-update scores (M, B), and per-layer column-alive masks (M, H).
    """

    def micro(carry, batch):
        params, opt_state, step = carry
        (loss, aux), grads = jax.value_and_grad(
            lambda p: deepffm.loss_and_aux(cfg, p, batch, model,
                                           sparse_backward=sparse_backward),
            has_aux=True)(params)
        new_params, new_state = opt.update(grads, opt_state, params, step)
        outs = {
            "loss": loss,
            # progressive validation: these logits were computed against the
            # pre-update params (the very forward the gradient came from)
            "scores": jax.nn.sigmoid(aux["logits"]),
            "col_alive": [jnp.any(m, axis=0) for m in aux["masks"]],
        }
        return (new_params, new_state, step + 1), outs

    def round_fn(params, opt_state, step, batches):
        (params, opt_state, step), outs = jax.lax.scan(
            micro, (params, opt_state, step), batches)
        return params, opt_state, step, outs

    if donate:
        return jax.jit(round_fn, donate_argnums=(0, 1))
    return jax.jit(round_fn)


def make_sparse_round_step(cfg: FFMConfig, model: str, opt: Optimizer, *,
                           sparse_backward: bool = True, donate: bool = True):
    """The jitted **row-sparse** AdaGrad round step — the §4.3/Juan-et-al.
    online-learning regime made structural.

    A CTR batch touches at most ``B*F`` of the ``hash_space`` embedding/LR
    rows, yet autodiff of ``jnp.take`` materializes a dense full-table
    gradient and the dense update streams every parameter per microbatch —
    O(model) memory traffic that dwarfs the actual math (it is why the seed
    loop and the dense scan step run at the same speed). This step instead:

    1. differentiates the *gathered* rows (``emb[idx]``, ``lr_w[idx]``) plus
       the dense head leaves — the backward never touches the tables;
    2. reduces duplicate occurrences exactly (``jnp.unique`` with a static
       ``B*F`` size + ``segment_sum`` — AdaGrad must square the *summed*
       row gradient, so per-occurrence application would be wrong);
    3. applies ``optim.adagrad``'s update to the touched row slices and
       scatters them back with ``.at[rows].set(..., mode="drop")`` — with
       donated buffers XLA performs the scatter in place, so per-batch cost
       is O(batch), not O(model).

    Untouched rows see a zero gradient under the dense rule (acc and params
    both unchanged), so this is *exactly* the dense step restricted to the
    touched rows — equivalence-tested against :func:`make_round_step`.
    Same signature/returns as :func:`make_round_step`, except that the
    embedding tables of ``params`` and the accumulator are in the
    :func:`to_device_rows` form ``(V, W)`` (padding columns, if any, get no
    gradient and stay zero).
    """
    emb_path = emb_leaf_path(model)
    fk = cfg.n_fields * cfg.k

    def take_rows(table, rows, **kw):
        """(n, F, k) model rows of a (V, W) device-row table."""
        got = jnp.take(table, rows, axis=0, **kw)[:, :fk]
        return got.reshape(rows.shape[0], cfg.n_fields, cfg.k)

    def put_rows(table, rows, new):
        new = jnp.pad(new.reshape(new.shape[0], fk),
                      ((0, 0), (0, table.shape[1] - fk)))
        return table.at[rows].set(new, mode="drop")

    def micro(carry, batch):
        params, opt_state, step = carry
        idx, val = batch["idx"], batch["val"]
        b, f = idx.shape
        flat = idx.reshape(-1)

        # the differentiated leaves: gathered rows + the dense head
        var = {"lr_rows": jnp.take(params["lr"]["w"], flat).reshape(b, f),
               "dense": {"lr_b": params["lr"]["b"]}}
        if emb_path is not None:
            var["emb_rows"] = take_rows(_get_emb(params, model), flat
                                        ).reshape(b, f, cfg.n_fields, cfg.k)
        if model in ("mlp", "deepffm"):
            var["dense"]["mlp"] = params["mlp"]
        if model == "deepffm":
            var["dense"]["merge_scale"] = params["merge_scale"]
            var["dense"]["merge_bias"] = params["merge_bias"]

        def local_loss(v):
            lr_out = jnp.sum(v["lr_rows"] * val, axis=-1) + v["dense"]["lr_b"]
            if model == "linear":
                logits, masks = lr_out, []
            elif model == "mlp":
                pooled = (jnp.mean(v["emb_rows"], axis=2)
                          * val[..., None]).reshape(b, -1)
                mlp_out, masks = deepffm.mlp_apply(
                    cfg, v["dense"]["mlp"], pooled, return_masks=True,
                    sparse_backward=sparse_backward)
                logits = lr_out + mlp_out
            else:
                e = v["emb_rows"]
                dots = jnp.einsum("bijk,bjik->bij", e, e)
                vv = val[:, :, None] * val[:, None, :]
                pi, pj = ffm.pair_indices(cfg.n_fields)
                vec = (dots * vv)[:, pi, pj]
                logits, masks = deepffm.head_from_parts(
                    cfg, v["dense"], lr_out, vec, model, with_masks=True,
                    sparse_backward=sparse_backward)
            return ffm.bce_loss(logits, batch["label"]), \
                {"logits": logits, "masks": masks}

        (loss, aux), g = jax.value_and_grad(local_loss, has_aux=True)(var)

        # exact row gradients: occurrences of the same hashed row sum first
        rows = jnp.unique(flat, size=b * f, fill_value=cfg.hash_space)
        inv = jnp.searchsorted(rows, flat)
        p_rows = {"lr_w": jnp.take(params["lr"]["w"], rows, mode="clip")}
        a_rows = {"lr_w": jnp.take(opt_state["acc"]["lr"]["w"], rows,
                                   mode="clip")}
        g_rows = {"lr_w": jax.ops.segment_sum(g["lr_rows"].reshape(-1), inv,
                                              num_segments=b * f)}
        if emb_path is not None:
            p_rows["emb"] = take_rows(_get_emb(params, model), rows,
                                      mode="clip")
            a_rows["emb"] = take_rows(_get_emb(opt_state["acc"], model), rows,
                                      mode="clip")
            g_rows["emb"] = jax.ops.segment_sum(
                g["emb_rows"].reshape(b * f, cfg.n_fields, cfg.k), inv,
                num_segments=b * f)

        # one optim.adagrad application over {touched rows} + {dense head}
        upd_p = {"rows": p_rows, "dense": var["dense"]}
        upd_a = {"rows": a_rows,
                 "dense": _dense_subtree(opt_state["acc"], model)}
        upd_g = {"rows": g_rows, "dense": g["dense"]}
        new_p, new_state = opt.update(upd_g, {"acc": upd_a}, upd_p, step)
        new_a = new_state["acc"]

        # scatter the touched rows back in place (donated buffers); the
        # padding slots carry the out-of-range fill row and are dropped
        lr_w = params["lr"]["w"].at[rows].set(new_p["rows"]["lr_w"],
                                              mode="drop")
        acc_lr_w = opt_state["acc"]["lr"]["w"].at[rows].set(
            new_a["rows"]["lr_w"], mode="drop")
        params = {**params, "lr": {"w": lr_w, "b": new_p["dense"]["lr_b"]}}
        acc = _set_dense_subtree(opt_state["acc"], model, new_a["dense"])
        acc = {**acc, "lr": {**acc["lr"], "w": acc_lr_w}}
        params = _set_dense_subtree(params, model, new_p["dense"])
        if emb_path is not None:
            params = _set_emb(params, model, put_rows(
                _get_emb(params, model), rows, new_p["rows"]["emb"]))
            acc = _set_emb(acc, model, put_rows(
                _get_emb(acc, model), rows, new_a["rows"]["emb"]))

        outs = {
            "loss": loss,
            "scores": jax.nn.sigmoid(aux["logits"]),
            "col_alive": [jnp.any(m, axis=0) for m in aux["masks"]],
        }
        return (params, {"acc": acc}, step + 1), outs

    def round_fn(params, opt_state, step, batches):
        (params, opt_state, step), outs = jax.lax.scan(
            micro, (params, opt_state, step), batches)
        return params, opt_state, step, outs

    if donate:
        return jax.jit(round_fn, donate_argnums=(0, 1))
    return jax.jit(round_fn)


def _dense_subtree(params, model: str) -> Dict[str, Any]:
    """The non-row-sparse leaves of a params/acc tree, as the flat dict the
    sparse step differentiates (`lr_b` + head leaves)."""
    dense = {"lr_b": params["lr"]["b"]}
    if model in ("mlp", "deepffm"):
        dense["mlp"] = params["mlp"]
    if model == "deepffm":
        dense["merge_scale"] = params["merge_scale"]
        dense["merge_bias"] = params["merge_bias"]
    return dense


def _set_dense_subtree(params, model: str, dense: Dict[str, Any]):
    """Write an updated dense subtree back into the full tree."""
    out = {**params, "lr": {**params["lr"], "b": dense["lr_b"]}}
    if model in ("mlp", "deepffm"):
        out["mlp"] = dense["mlp"]
    if model == "deepffm":
        out["merge_scale"] = dense["merge_scale"]
        out["merge_bias"] = dense["merge_bias"]
    return out


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------

class TrainerBackend(Protocol):
    """One round of updates over a list of batches. Implementations must
    return the new weights, the new optimizer state (``{"acc": ...}`` for
    AdaGrad), and the round's :class:`RoundMetrics`."""

    def run(self, params, opt_state, batches: List[Dict[str, Any]]
            ) -> Tuple[Any, Any, RoundMetrics]:
        ...


class JitBackend:
    """Sequential reference backend: the whole round is jitted scan calls.

    Batches are stacked along a leading microbatch axis per contiguous run of
    identical shapes (a uniform stream compiles exactly once per round
    length); the stacked scan replaces the seed's per-batch Python loop of
    ``tree_map`` updates and its separate jitted predict call. With
    ``row_sparse=True`` (default) the scan body is
    :func:`make_sparse_round_step`, whose update cost scales with the batch
    instead of the embedding table.
    """

    def __init__(self, cfg: FFMConfig, model: str, opt: Optimizer, *,
                 sparse_backward: bool = True, donate: bool = True,
                 row_sparse: bool = True):
        maker = make_sparse_round_step if row_sparse else make_round_step
        self._round = maker(cfg, model, opt, sparse_backward=sparse_backward,
                            donate=donate)
        self._step = jnp.zeros((), jnp.int32)

    @staticmethod
    def _shape_key(b: Dict[str, Any]) -> Tuple:
        return tuple((k, np.asarray(v).shape) for k, v in sorted(b.items()))

    def run(self, params, opt_state, batches):
        m = RoundMetrics()
        i = 0
        while i < len(batches):
            j = i + 1
            key = self._shape_key(batches[i])
            while j < len(batches) and self._shape_key(batches[j]) == key:
                j += 1
            group = batches[i:j]
            stacked = {k: np.stack([np.asarray(b[k]) for b in group])
                       for k in group[0]}
            params, opt_state, self._step, outs = self._round(
                params, opt_state, self._step, stacked)
            m.losses.extend(np.asarray(outs["loss"]).tolist())
            m.scores.append(np.asarray(outs["scores"]).reshape(-1))
            m.labels.append(stacked["label"].reshape(-1))
            alive = [np.asarray(a) for a in outs["col_alive"]]
            if not m.col_alive:
                m.col_alive = alive
            else:
                m.col_alive = [np.concatenate([c, a])
                               for c, a in zip(m.col_alive, alive)]
            m.examples += int(stacked["label"].size)
            i = j
        return params, opt_state, m


class HogwildBackend:
    """§4.2 faithful CPU Hogwild as a pipeline backend (threads over shared
    numpy buffers, racy by design). Wraps :class:`~repro.train.hogwild.
    HogwildTrainer`, which now draws its update rule from ``optim.adagrad``.
    """

    def __init__(self, cfg: FFMConfig, model: str, *, lr: float,
                 power_t: float, n_threads: int = 4,
                 sparse_backward: bool = True):
        from repro.train import hogwild

        self._hogwild = hogwild
        self.cfg, self.model = cfg, model
        self.lr, self.power_t = lr, power_t
        self.n_threads = n_threads
        self.sparse_backward = sparse_backward
        self._trainer = None

    def run(self, params, opt_state, batches):
        if self._trainer is None:
            self._trainer = self._hogwild.HogwildTrainer(
                self.cfg, self.model, lr=self.lr, power_t=self.power_t,
                params=params, sparse_backward=self.sparse_backward)
        stats = self._trainer.train(batches, n_threads=self.n_threads)
        m = RoundMetrics(examples=stats.examples, losses=list(stats.losses),
                         labels=list(stats.labels), scores=list(stats.scores))
        if stats.col_alive:
            m.col_alive = [np.stack(layer) for layer in stats.col_alive]
        return self._trainer.params(), self._trainer.opt_state(), m


class LocalSGDBackend:
    """TPU-native Hogwild analogue: W vmapped workers each take k
    unsynchronized AdaGrad steps from the same starting point, then merge by
    averaging — one merge per round (see ``train.hogwild``).

    ``workers`` must be a power of two: averaging W bit-identical untouched
    embedding rows is then exact in float arithmetic, which the row-delta
    update frames rely on (untouched rows must stay byte-stable).
    """

    def __init__(self, cfg: FFMConfig, model: str, *, lr: float,
                 power_t: float, workers: int = 2,
                 sparse_backward: bool = True):
        from repro.train import hogwild

        if workers < 1 or workers & (workers - 1):
            raise ValueError(f"local_sgd workers must be a power of two, "
                             f"got {workers}")
        self.workers = workers
        self._round = hogwild.make_local_sgd_round(
            cfg, model, lr=lr, power_t=power_t, with_aux=True,
            sparse_backward=sparse_backward)

    def run(self, params, opt_state, batches):
        m = RoundMetrics()
        w = self.workers
        key = JitBackend._shape_key(batches[0]) if batches else None
        usable = [b for b in batches if JitBackend._shape_key(b) == key]
        k = len(usable) // w
        if k < 1:
            raise ValueError(
                f"local_sgd round needs >= {w} same-shape batches, got "
                f"{len(usable)} matching the first batch's shape "
                f"(of {len(batches)} total)")
        usable = usable[: w * k]
        stacked = {
            kk: np.stack([np.stack([np.asarray(b[kk])
                                    for b in usable[wi * k:(wi + 1) * k]])
                          for wi in range(w)])
            for kk in usable[0]
        }
        acc = opt_state["acc"]
        params, acc, loss, aux = self._round(params, acc, stacked)
        m.losses.append(float(loss))
        m.scores.append(np.asarray(aux["scores"]).reshape(-1))
        m.labels.append(stacked["label"].reshape(-1))
        m.col_alive = [np.asarray(a).reshape(-1, a.shape[-1])
                       for a in aux["col_alive"]]
        m.examples = int(stacked["label"].size)
        return params, {"acc": acc}, m


# ---------------------------------------------------------------------------
# The pipeline
# ---------------------------------------------------------------------------

class TrainingPipeline:
    """The paper's §3 online-training job: rounds in, update frames out.

    ``run_round`` consumes one round's batches (through the §4.1 prefetcher),
    applies them with the selected backend, and emits the versioned update
    blob for the serving layer — a ``KIND_DELTA`` row-delta frame in steady
    state when ``delta_updates`` is on (the trainer knows exactly which
    embedding/LR rows it touched), falling back to full/patch framing on the
    first round or on layout/grid changes.

    With ``donate=True`` (default, jit backend) each round donates the
    previous params/opt-state buffers to XLA: ``self.opt_state`` is replaced
    in place, and any *externally retained* reference to a prior round's
    arrays is invalidated (jax raises on use). Hold the fresh attributes,
    not old snapshots — or pass ``donate=False``.

    The row-sparse jit backend keeps the embedding table and its
    accumulator in :func:`to_device_rows` form (``opt_state`` too);
    :attr:`params` and :attr:`acc` return the model's ``(V, F, k)`` view,
    which is what the update frames carry.
    """

    def __init__(self, cfg: FFMConfig, model: str = "deepffm",
                 backend: str = "jit", *, lr: float = 0.1,
                 power_t: float = 0.5, transfer_mode: str = "patch+quant",
                 delta_updates: bool = True, seed: int = 0,
                 prefetch_depth: int = 8, sparse_backward: bool = True,
                 hogwild_threads: int = 4, local_sgd_workers: int = 2,
                 donate: bool = True, row_sparse: bool = True,
                 shard_ranges=None):
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
        self.cfg, self.model, self.lr = cfg, model, lr
        self.backend_name = backend
        self.prefetch_depth = prefetch_depth
        self.delta_updates = delta_updates
        params = deepffm.init_params(cfg, jax.random.PRNGKey(seed), model)
        self._device_rows = (backend == "jit" and row_sparse
                             and emb_leaf_path(model) is not None)
        if self._device_rows:
            params = to_device_rows(cfg, model, params)
        self._params = params
        self.opt = make_optimizer("adagrad", lr=lr, power_t=power_t)
        self.opt_state = self.opt.init(params)
        # ``shard_ranges`` (a fleet topology's contiguous row ranges) flips
        # the update channel to fan-out: run_round emits one frame per shard
        # (transfer.ShardedSender) instead of one full-space frame; the
        # row-sharded paths come from the model's declarative specs
        if shard_ranges is not None:
            row_paths = sorted({"lr/w"} |
                               ({emb_leaf_path(model)}
                                if emb_leaf_path(model) else set()))
            self.sender = transfer.ShardedSender(
                ranges=shard_ranges, row_paths=row_paths, mode=transfer_mode)
            # publish the wire layout now, so sender.manifests can configure
            # the fleet's decode pipes before the first round runs
            self.sender.prime(self.params)
        else:
            self.sender = transfer.Sender(mode=transfer_mode)
        self.reports: List[RoundReport] = []
        if backend == "jit":
            self.backend: TrainerBackend = JitBackend(
                cfg, model, self.opt, sparse_backward=sparse_backward,
                donate=donate, row_sparse=row_sparse)
        elif backend == "hogwild":
            self.backend = HogwildBackend(
                cfg, model, lr=lr, power_t=power_t,
                n_threads=hogwild_threads, sparse_backward=sparse_backward)
        else:
            self.backend = LocalSGDBackend(
                cfg, model, lr=lr, power_t=power_t,
                workers=local_sgd_workers, sparse_backward=sparse_backward)

    def _model_view(self, tree):
        if self._device_rows:
            return from_device_rows(self.cfg, self.model, tree)
        return tree

    @property
    def params(self):
        """The current weights, embedding table as ``(V, F, k)``."""
        return self._model_view(self._params)

    @property
    def acc(self):
        """AdaGrad accumulator (legacy ``OnlineTrainer`` surface)."""
        return self._model_view(self.opt_state["acc"])

    def run_round(self, batches: Iterable[Dict[str, Any]]):
        """One online round; returns the versioned update blob for serving —
        one ``bytes`` frame, or the per-shard ``List[bytes]`` (shard order)
        when the pipeline was built with ``shard_ranges``."""
        t0 = time.perf_counter()
        batch_list = list(Prefetcher(batches, depth=self.prefetch_depth))
        self._params, self.opt_state, m = self.backend.run(
            self._params, self.opt_state, batch_list)
        touched, n_rows = (touched_paths(batch_list, self.model)
                           if self.delta_updates else (None, 0))
        # report.round and the frame's version stamp are the same number: the
        # serving engine tracks it as weights_version
        version = len(self.reports) + 1
        params = self.params
        if isinstance(self.sender, transfer.ShardedSender):
            # fan-out channel: one frame per shard, same version stamp on
            # all; run_round returns the List[bytes] in shard order
            update = self.sender.make_updates(params, version=version,
                                              touched=touched or None)
            # a fault-injected sender may drop or mangle a shard's frame on
            # the wire; the round still reports the surviving frames' bytes
            # and the kind of the first frame that decodes
            shipped = [u for u in update if u is not None]
            update_bytes = sum(len(u) for u in shipped)
            kind = "dropped"
            for u in shipped:
                try:
                    kind = _KIND_NAMES[transfer.unframe(u).kind]
                    break
                except transfer.FrameError:
                    kind = "corrupt"
        else:
            update = self.sender.make_update(params, version=version,
                                             touched=touched or None)
            update_bytes = len(update)
            kind = _KIND_NAMES[transfer.unframe(update).kind]
        seconds = time.perf_counter() - t0
        skip = (sparse_updates.skip_stats_from_col_alive(m.col_alive)
                if m.col_alive else {})
        self.reports.append(RoundReport(
            round=version, examples=m.examples, seconds=seconds,
            mean_loss=float(np.mean(m.losses)) if m.losses else float("nan"),
            progressive_auc=roc_auc(np.concatenate(m.labels),
                                    np.concatenate(m.scores))
            if m.labels else 0.5,
            update_bytes=update_bytes,
            examples_per_s=m.examples / max(seconds, 1e-9),
            skip_stats=skip, touched_rows=n_rows,
            update_kind=kind,
        ))
        return update

    def checkpoint(self, path: str) -> None:
        store.save(path, self.params, {"acc": self.acc})
