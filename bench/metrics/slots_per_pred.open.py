"""Bucket padding: padded rows x candidates slots scored per prediction
(ServeStats.slots_scored / candidates)."""
from bench.lib import phases


def read(run):
    return phases.slots_per_pred(run)
