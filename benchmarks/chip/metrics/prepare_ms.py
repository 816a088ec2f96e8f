"""Host time the engine spends preparing a decode step (``engine.prepare``:
the tokens, the positions, the active-row mask, the paged block-table
gather), per decode step of the traced window (ms)."""
from benchmarks.chip import program


def read(rec, red):
    return program.ms_per_step(rec, "engine.prepare")
