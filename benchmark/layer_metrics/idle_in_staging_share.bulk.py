"""Share of the traced window in which the device idled while the host
was inside a `bls.stage` span, at any depth (benchmark/program_spans:
each idle stretch goes to the deepest program span open over it). No
such span: nothing to read."""

from benchmark import program_spans


def read(ctx):
    spans = program_spans.read(ctx)
    if not spans or "bls.stage" not in spans["seconds"]:
        return None
    return program_spans.idle_under(spans, "bls.stage") / spans["window_s"]
