"""Milliseconds per attestation in the program's `bls.device_wait` spans
(the host blocked on a device verdict, bisection's sub-batches
included), from the profiler trace, over the attestations processed. No
batch reached the device: nothing to read."""

from benchmark import program_spans


def read(ctx):
    return program_spans.ms_per_att(ctx, "bls.device_wait")
