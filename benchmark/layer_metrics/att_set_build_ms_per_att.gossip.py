"""Milliseconds per attestation in the program's `att.set_build` spans
(indexed attestation and signature set of each attestation that passed
the gossip checks), from the profiler trace, over the attestations
processed. No such span: nothing to read."""

from benchmark import program_spans


def read(ctx):
    return program_spans.ms_per_att(ctx, "att.set_build")
