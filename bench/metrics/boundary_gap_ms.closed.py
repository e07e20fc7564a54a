"""Mean device idle per chunk boundary, closed mix, in ms.

The time in the traced window in which no program ran on the device (the
gaps between program executions, not between operations inside one),
divided by the chunks launched in the window."""


def read(ctx):
    t = ctx.trace
    if t is None or t.get("module_idle_s") is None or ctx.chunks_in_trace <= 0:
        return None
    return 1e3 * t["module_idle_s"] / ctx.chunks_in_trace
