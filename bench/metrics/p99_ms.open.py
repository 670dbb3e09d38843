"""Request path, open loop: 99th percentile request latency from the due
time. The host's pauses move it more than the program does, so it stands
beside the end-to-end ``p90_ms`` rather than as a bounded metric."""
from bench.lib import readers


def read(run):
    return readers.latency_ms(run, 99)
