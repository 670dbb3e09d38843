"""Load generator: 99th percentile of send time minus due time."""
from bench.lib import readers


def read(run):
    return readers.gen_lag_ms(run)
