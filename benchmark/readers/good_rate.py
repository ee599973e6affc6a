"""Sources whose call returned converged with true_res <= res_bound, per
chip and per ``per_seconds`` of the window's true length (the last call
finishes, and the window is as long as it really was)."""


def read(ctx, per_seconds=3600.0):
    if ctx["window_s"] <= 0:
        return None
    return (ctx["good_sources"] * per_seconds
            / (ctx["window_s"] * ctx["chips"]))
