"""A key of ``device.memory_stats()`` after the window, on the fullest
chip, divided by ``divide_by`` (2**30 for GiB)."""


def read(ctx, key="peak_bytes_in_use", divide_by=1.0):
    vals = [m[key] for m in ctx["memory"] if key in m]
    if not vals:
        return None
    return max(vals) / divide_by
