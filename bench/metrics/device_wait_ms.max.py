"""Jitted forward: time in serve.device_wait spans (block_until_ready and the
outputs' copy to numpy) per call."""
from bench.lib import phases


def read(run):
    return phases.phase_ms(run, "serve.device_wait")
