"""A running total of the entry module's ``counters()`` as it stood
after the window (not a per-call difference): seconds of something the
entry point did once, in set-up.  None where the entry has no such
counter."""


def read(ctx, key):
    return ctx["counters1"].get(key)
