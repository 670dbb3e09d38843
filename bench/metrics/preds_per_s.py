"""End to end, saturating loop: candidates scored per second of window."""
from bench.lib import readers


def read(run):
    return readers.preds_per_s(run)
