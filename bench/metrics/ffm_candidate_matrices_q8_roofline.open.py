"""Kernel ffm_candidate_matrices_q8: share of its roofline, in percent."""
from bench.lib import readers


def read(run):
    return readers.kernel_roofline(run, "ffm_candidate_matrices_q8")
