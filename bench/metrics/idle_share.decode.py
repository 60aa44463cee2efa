"""Share of the traced window in which no operation ran on the device."""


def read(w):
    if not w.traced or w.window_s <= 0:
        return None
    return (1.0 - w.busy_s / w.window_s) * 100.0
