"""End to end, open loop: 99th percentile request latency from the due time."""
from bench.lib import readers


def read(run):
    return readers.p99_ms(run)
