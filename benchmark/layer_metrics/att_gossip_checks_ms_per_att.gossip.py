"""Milliseconds per attestation in the program's `att.checks` spans (the
gossip checks of a batch or a single attestation: slot window, bit
count, head block, committee, first-seen voter), from the profiler
trace, over the attestations processed. No such span: nothing to
read."""

from benchmark import program_spans


def read(ctx):
    return program_spans.ms_per_att(ctx, "att.checks")
