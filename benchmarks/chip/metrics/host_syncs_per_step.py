"""Blocking device-to-host reads the engine makes (its ``host_syncs``
counter: one per running row's sampled token, one per admission's first
token), per decode step of the traced window."""
from benchmarks.chip import program


def read(rec, red):
    return program.count_per_step(rec, "host_syncs")
