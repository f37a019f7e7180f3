"""Milliseconds per attestation in the BLS backend calls (staging, the
device stages and the wait for them, bisection of a failed batch, and
the native answer for batches of at most 16 sets), from the benchmark's
span on the crypto/bls seam, over the attestations processed. None
recorded: nothing to read."""


def read(ctx):
    spans = ctx.get("spans", ())
    n = sum(s[3].get("n", 1) for s in spans
            if s[0] in ("bench.batch", "bench.single"))
    if not n:
        return None
    return sum(s[2] - s[1] for s in spans if s[0] == "bench.bls") / n * 1e3
