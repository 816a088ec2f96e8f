"""Batch occupancy: requests that have their first token and are not
finished, counted by the client after each step of the window, over
``max_batch``, averaged over the steps (%)."""


def read(rec, red):
    steps = rec["window_steps"]
    if not steps:
        return None
    return 100.0 * sum(s.occupancy for s in steps) / (
        len(steps) * rec["max_batch"])
