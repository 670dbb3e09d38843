"""Kernel dcn_cross_q8: share of its roofline, in percent."""
from bench.lib import readers


def read(run):
    return readers.kernel_roofline(run, "dcn_cross_q8")
