"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (%)."""


def read(rec, red):
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * red.idle_share()
