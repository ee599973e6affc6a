"""The device's idle share of the traced window, in percent: 1 - (union
of the intervals in which an operation ran) / window."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
