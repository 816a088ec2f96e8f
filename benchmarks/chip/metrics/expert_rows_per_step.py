"""Rows the routed-expert matmuls compute per decode step (the program's
``expert_rows`` counter: held experts x the capacity the dispatch used x
MoE layers, counted on the host from static shapes), over the traced
window.  None where the program keeps no such counter."""
from benchmarks.chip import program


def read(rec, red):
    w = program.traced_window(rec)
    if w is None or "expert_rows" not in w[1]:
        return None
    return program.count_per_step(rec, "expert_rows")
