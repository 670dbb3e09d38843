"""Readers of the serving engine's own phase clock and transfer counters:
``ServeStats.phase_s`` (wall seconds per ``serve.*`` span, summed over
threads), ``host_arg_bytes`` and ``slots_scored``, all summed over the
window's calls (set-up's warmup calls bypass ``score_batch``). Each returns
``None`` where the program keeps no such counter."""
from __future__ import annotations

from bench.lib.readers import _ok_calls


def _engine_stat(run, key):
    return None if run.engine is None else getattr(run.engine.stats, key,
                                                   None)


def phase_ms(run, name: str, child: str | None = None):
    """Milliseconds per call in the span ``name``, less the time in its
    nested span ``child``."""
    phases, calls = _engine_stat(run, "phase_s"), len(_ok_calls(run))
    if not phases or name not in phases or not calls:
        return None
    seconds = phases[name] - (phases.get(child, 0.0) if child else 0.0)
    return seconds * 1e3 / calls


def host_arg_mb_per_call(run):
    """MB of host numpy arrays handed to the jitted forwards per call."""
    total, calls = _engine_stat(run, "host_arg_bytes"), len(_ok_calls(run))
    return None if total is None or not calls else total / calls / 1e6


def slots_per_pred(run):
    """Padded ``rows x candidates`` slots the forwards computed per
    prediction."""
    slots = _engine_stat(run, "slots_scored")
    preds = sum(c.candidates for c in _ok_calls(run))
    return None if slots is None or not preds else slots / preds
