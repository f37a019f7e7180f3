"""95th percentile (nearest rank) of the wait in the processor's queue:
from an attestation's due time to the start of the batch, or single
item, that processed it (the benchmark's own span around the processor's
callback). No attestation processed: nothing to read."""

import math


def read(ctx):
    waits = sorted(r["start"] - r["due"] for r in ctx.get("records", ())
                   if r.get("start") is not None)
    if not waits:
        return None
    return waits[max(1, math.ceil(0.95 * len(waits) - 1e-9)) - 1]
