"""Scoring pool: time in serve.pool_wait spans (the caller blocked on a
prepare) per call."""
from bench.lib import phases


def read(run):
    return phases.phase_ms(run, "serve.pool_wait")
