"""Dedup: time in the serve.dedup span (packed np.unique, chunk layout,
candidate blocks, compact grids) per call."""
from bench.lib import phases


def read(run):
    return phases.phase_ms(run, "serve.dedup")
