"""Prefix cache: time in serve.tails spans (host context-tail arithmetic)
per call."""
from bench.lib import phases


def read(run):
    return phases.phase_ms(run, "serve.tails")
