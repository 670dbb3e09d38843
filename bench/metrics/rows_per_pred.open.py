"""Dedup: rows scored per prediction (ServeStats.rows_scored / candidates)."""
from bench.lib import readers


def read(run):
    return readers.rows_per_pred(run)
