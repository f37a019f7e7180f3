"""Share of the traced window in which no executable ran on the device:
1 - (union of device busy intervals) / (window), from the profiler trace
(trace_reduce). Nothing traced: nothing to read."""


def read(ctx):
    red = ctx.get("trace")
    if not red or not red["n_devices"] or red["window_s"] <= 0:
        return None
    return 1.0 - red["busy_s"] / red["window_s"]
