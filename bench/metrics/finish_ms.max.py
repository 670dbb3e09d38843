"""Request path: time in the serve.finish span (fused cache inserts,
scatter-back, stats merge) per call."""
from bench.lib import phases


def read(run):
    return phases.phase_ms(run, "serve.finish")
