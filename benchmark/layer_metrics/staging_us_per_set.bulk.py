"""Host microseconds per set in the program's `bls.stage` spans (hash to
field, point conversion, scalar draws, host-to-device copies), from the
profiler trace, over the sets of the traced calls. No such span: nothing
to read."""

from benchmark import program_spans


def read(ctx):
    spans, n = program_spans.read(ctx), ctx.get("sets_traced")
    if not spans or not n or "bls.stage" not in spans["seconds"]:
        return None
    return spans["seconds"]["bls.stage"] / n * 1e6
