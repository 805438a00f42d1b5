"""Share of the traced window in which no operation ran on the device."""


def read(rec):
    tr = rec.trace
    if tr["window_s"] <= 0:
        return None
    return 100.0 * tr["idle_s"] / tr["window_s"]
