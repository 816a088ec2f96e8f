"""Share of the traced window spent inside the engine's admission
(``Engine._admit``, the harness's ``bench.admit`` span), in %."""


def read(rec, red):
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * red.span_time_s("bench.admit") / red.window_s
