"""Host pre-gather: time in serve.prepare spans (padding, stacking, the
forward's host arguments), summed over pool threads, per call."""
from bench.lib import phases


def read(run):
    return phases.phase_ms(run, "serve.prepare")
