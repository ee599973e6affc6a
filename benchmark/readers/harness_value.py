"""A number the harness took itself: ``setup_s`` (process start to the
start of the window, the reference's time taken out) or ``first_call_s``
(the wall of the warm-up call, which compiles or loads from the cache)."""


def read(ctx, key):
    return ctx.get(key)
