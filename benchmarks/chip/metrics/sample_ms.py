"""Host time the engine spends sampling a decode step's tokens and reading
each row's token back to the host (``engine.sample``), per decode step of
the traced window (ms)."""
from benchmarks.chip import program


def read(rec, red):
    return program.ms_per_step(rec, "engine.sample")
