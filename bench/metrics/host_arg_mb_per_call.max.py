"""Host to device: MB of host numpy arrays the jitted forwards of one call take
(ServeStats.host_arg_bytes; a device-resident array counts 0)."""
from bench.lib import phases


def read(run):
    return phases.host_arg_mb_per_call(run)
