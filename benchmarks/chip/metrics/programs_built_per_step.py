"""Programs JAX built or loaded from the persistent compile cache inside the
program's spans (its ``programs_built`` counter), per decode step of the
traced window."""
from benchmarks.chip import program


def read(rec, red):
    return program.count_per_step(rec, "programs_built")
