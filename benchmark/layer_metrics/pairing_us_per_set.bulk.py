"""Device microseconds per set of the pairing (ops/bm/pairing: Miller loops over the distinct messages and the signature pair, then the final exponentiation): the device time of the
executables ('jit__miller_product', 'jit__final_check') in the traced calls, from the profiler trace, over the
sets those calls carried. No such executable in the trace: nothing to
read."""

MODULES = ('jit__miller_product', 'jit__final_check')


def read(ctx):
    red = ctx.get("trace")
    n = ctx.get("sets_traced")
    if not red or not n:
        return None
    secs = sum(v for k, v in red["modules"].items() if k in MODULES)
    return secs / n * 1e6 if secs > 0 else None
