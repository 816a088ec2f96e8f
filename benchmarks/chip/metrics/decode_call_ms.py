"""Host time of the engine's decode call until it returns
(``engine.decode``), per decode step of the traced window (ms)."""
from benchmarks.chip import program


def read(rec, red):
    return program.ms_per_step(rec, "engine.decode")
