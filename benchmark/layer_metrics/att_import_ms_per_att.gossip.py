"""Milliseconds per attestation in the program's `att.import` spans
(fork-choice apply, slasher and op pool for the verified attestations),
from the profiler trace, over the attestations processed. No such span:
nothing to read."""

from benchmark import program_spans


def read(ctx):
    return program_spans.ms_per_att(ctx, "att.import")
