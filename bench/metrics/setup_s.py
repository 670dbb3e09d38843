"""End to end: process start to the first timed request."""
from bench.lib import readers


def read(run):
    return readers.setup_s(run)
