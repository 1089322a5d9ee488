"""Device: share of the traced span in which no operation ran on the
chip, 1 minus the union of device-op intervals over the span."""


def read(w):
    t = w["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
