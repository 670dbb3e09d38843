"""Request path: mean wall time of a score_batch call (harness span)."""
from bench.lib import readers


def read(run):
    return readers.batch_ms(run)
