"""Which way a Pallas kernel runs on the platform JAX is using.

The TPU compiles every ``pallas_call`` through Mosaic. The CPU has no Mosaic
backend, so there the kernels run in the Pallas interpreter (the parity
tests' setting). Any other platform has neither a tested kernel lowering nor
a reason to pretend, so it is an error rather than a silent interpreter.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """The ``interpret`` flag for a ``pallas_call``.

    An explicit ``interpret`` is returned as given (the described-topology
    compile tests pass ``False`` on a CPU host). ``None`` resolves from
    ``jax.default_backend()``: ``"cpu"`` -> ``True``, ``"tpu"`` -> ``False``,
    anything else raises ``ValueError``.
    """
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise ValueError(
        f"no Pallas kernel path for platform {platform!r}: kernels compile "
        f"on 'tpu' and run in the interpreter on 'cpu'")
