"""Seconds per call that the program's own phase timers charged to the
named categories over the window (difference of the entry module's
``counters()`` before and after, over the calls).

Caveat (PERF.md): ``TimeProfile.stop`` blocks only when given ``sync``,
so a phase that ends without ``block_until_ready`` charges the device
work it enqueued to the next phase."""


def read(ctx, categories):
    n = len(ctx["calls"])
    keys = [f"phase.{c}" for c in categories]
    if not n or not all(k in ctx["counters1"] for k in keys):
        return None
    return sum(ctx["counters1"][k] - ctx["counters0"][k] for k in keys) / n
