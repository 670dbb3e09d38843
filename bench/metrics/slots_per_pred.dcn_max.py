"""Bucket padding: padded rows x candidates slots scored per prediction
(ServeStats.slots_scored / candidates). The cross kernel's candidate tile
divides every candidate bucket, so these are also the slots it computes."""
from bench.lib import phases


def read(run):
    return phases.slots_per_pred(run)
