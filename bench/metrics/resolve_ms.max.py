"""Prefix cache: time in the serve.resolve span per call (context tokens,
trie lookups and inserts, lock waits), less its serve.tails spans."""
from bench.lib import phases


def read(run):
    return phases.phase_ms(run, "serve.resolve", child="serve.tails")
