"""Prefix cache: context fields computed per request (ServeStats.ctx_tail_fields / requests)."""
from bench.lib import readers


def read(run):
    return readers.ctx_fields_per_req(run)
