"""Host to device: MB of host arrays the jitted forwards of one call take."""
from bench.lib import readers


def read(run):
    return readers.h2d_mb_per_call(run)
