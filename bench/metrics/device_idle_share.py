"""Share of the traced window in which no operation ran on the device."""


def read(run):
    s = run["summary"]
    if not s["devices"] or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
