"""Host to device: time in serve.launch spans (the jitted forward call until
it returns, its host arguments' copies included) per call."""
from bench.lib import phases


def read(run):
    return phases.phase_ms(run, "serve.launch")
