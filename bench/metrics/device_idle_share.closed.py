"""Device idle share over the traced window, closed mix: 100 (1 - busy / window).

Busy is the union of the device's operation intervals (benchlib.trace)."""


def read(ctx):
    t = ctx.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
