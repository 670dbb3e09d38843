"""Jitted forward: device busy time in the trace per score_batch call."""
from bench.lib import readers


def read(run):
    return readers.fwd_device_ms(run)
