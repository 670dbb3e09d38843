"""End to end, open loop: 90th percentile request latency from the due time."""
from bench.lib import readers


def read(run):
    return readers.latency_ms(run, 90)
