"""Whole scoring call: model FLOPs of its predictions over wall time x bf16 peak."""
from bench.lib import readers


def read(run):
    return readers.mfu_per_call(run)
