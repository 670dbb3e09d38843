from repro.kernels.dcn_cross import ops  # noqa: F401
from repro.kernels.dcn_cross.ops import dcn_cross_q8  # noqa: F401
