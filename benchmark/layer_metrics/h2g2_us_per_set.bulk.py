"""Device microseconds per set of hash-to-G2 (ops/bm/h2c: SSWU map, isogeny, cofactor clearing, over the distinct messages): the device time of the
executables ('jit__h2g2',) in the traced calls, from the profiler trace, over the
sets those calls carried. No such executable in the trace: nothing to
read."""

MODULES = ('jit__h2g2',)


def read(ctx):
    red = ctx.get("trace")
    n = ctx.get("sets_traced")
    if not red or not n:
        return None
    secs = sum(v for k, v in red["modules"].items() if k in MODULES)
    return secs / n * 1e6 if secs > 0 else None
