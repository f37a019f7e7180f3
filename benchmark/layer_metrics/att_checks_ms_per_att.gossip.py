"""Host milliseconds per attestation outside the BLS call: the
processor's batch and single-item callbacks (gossip checks, committee
lookup, indexed attestation, signature-set build, fork-choice apply),
minus the BLS backend calls inside them, over the attestations they
processed. From the benchmark's spans; none recorded: nothing to read."""


def read(ctx):
    spans = ctx.get("spans", ())
    outer = [s for s in spans if s[0] in ("bench.batch", "bench.single")]
    n = sum(s[3].get("n", 1) for s in outer)
    if not n:
        return None
    bls = sum(s[2] - s[1] for s in spans if s[0] == "bench.bls")
    return (sum(s[2] - s[1] for s in outer) - bls) / n * 1e3
