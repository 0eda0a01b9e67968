"""Device: share of the traced window in which no operation ran on the
chip (1 - the union of the device's op intervals / the window)."""


def read(run):
    if run.summary is None or run.summary["window_s"] <= 0:
        return None
    s = run.summary
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
