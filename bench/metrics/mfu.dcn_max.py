"""Whole scoring step: model FLOPs per prediction x predictions/s over bf16 peak."""
from bench.lib import readers


def read(run):
    return readers.mfu_rate(run)
