"""Device microseconds per set of the prepare stage (ops/bm: key aggregation, G2 subgroup check, the 64-bit scalar ladders, signature sum, same-message combine): the device time of the
executables ('jit__prepare_pairs',) in the traced calls, from the profiler trace, over the
sets those calls carried. No such executable in the trace: nothing to
read."""

MODULES = ('jit__prepare_pairs',)


def read(ctx):
    red = ctx.get("trace")
    n = ctx.get("sets_traced")
    if not red or not n:
        return None
    secs = sum(v for k, v in red["modules"].items() if k in MODULES)
    return secs / n * 1e6 if secs > 0 else None
