"""500k-validator verification-ON firehose probe (real TPU).

Usage: python scripts/probe_firehose_tpu.py [n_extra] [per_committee] [max_bucket]

Runs the full gossip slot path — batch former -> staging -> device
verify -> fork choice — at the BASELINE.json eval-config-#4 shape and
prints the p50/p99 per-batch and whole-slot-path numbers against the
slot-third deadline (VERDICT round 2 item 6). The CI twin
(tests/test_scale_firehose.py::test_firehose_500k_verification_on) runs
the identical pipeline with small CPU-jax buckets; this script is where
the deadline is actually judged, on the chip that will serve it.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    n_extra = int(sys.argv[1]) if len(sys.argv) > 1 else 500_000
    per_committee = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    max_bucket = int(sys.argv[3]) if len(sys.argv) > 3 else 1024

    import jax

    from lighthouse_tpu.beacon_processor import AdaptiveBatchPolicy
    from lighthouse_tpu.testing.firehose import (
        build_firehose_chain,
        make_signed_single_bit_attestations,
        run_firehose,
    )

    print(f"devices: {jax.devices()}", file=sys.stderr)
    t0 = time.monotonic()
    harness = build_firehose_chain(n_extra)
    chain, spec = harness.chain, harness.spec
    print(f"graft+genesis: {time.monotonic() - t0:.1f}s", file=sys.stderr)

    slot = 1
    chain.slot_clock.set_slot(slot)
    t0 = time.monotonic()
    chain.committees_at(slot)
    shuffle_secs = time.monotonic() - t0

    t0 = time.monotonic()
    atts = make_signed_single_bit_attestations(
        harness, slot, per_committee=per_committee
    )
    sign_secs = time.monotonic() - t0
    print(f"signed {len(atts)} atts in {sign_secs:.1f}s "
          f"(shuffle {shuffle_secs:.1f}s)", file=sys.stderr)

    # Two warm passes over disjoint thirds (disjoint: the observed-attester
    # dedup would drop repeats), then the timed pass. Thirds make the warm
    # and timed passes produce the SAME batch-former shapes — with one
    # warm prefix, the timed pass's larger batches hit cold compiles and
    # the p50 measured XLA, not the slot path (~150 s/batch per shape per
    # process: the persistent cache skips re-optimization, but tracing +
    # lowering a ~60k-op stage still costs ~minutes on this 1-core host;
    # the in-client ShapeWarmer hides this behind startup).
    def policy():
        return AdaptiveBatchPolicy(max_bucket=max_bucket, warm=(max_bucket,))

    n3 = len(atts) // 3
    for lo, hi in ((0, n3), (n3, 2 * n3)):
        stats_warm = run_firehose(harness, atts[lo:hi], policy())
        print(f"warm pass: {stats_warm}", file=sys.stderr)
    stats = run_firehose(harness, atts[2 * n3:], policy())

    third = spec.seconds_per_slot / 3.0
    per_att = stats["total_s"] / max(1, stats["imported"])
    print(
        f"500k firehose (verification ON, real backend): "
        f"n={stats['n_atts']} imported={stats['imported']} "
        f"batches={stats['batches']}\n"
        f"  batch p50 {stats['batch_p50_s']:.3f}s  "
        f"p99 {stats['batch_p99_s']:.3f}s\n"
        f"  slot path total {stats['total_s']:.2f}s "
        f"({per_att*1e3:.2f} ms/att) vs slot third {third:.1f}s"
    )

    from lighthouse_tpu.observability import report as obs_report

    rep = obs_report.make("probe_firehose_tpu", params={
        "n_extra": n_extra, "per_committee": per_committee,
        "max_bucket": max_bucket})
    obs_report.emit(obs_report.finish(
        rep, ok=stats["imported"] == stats["n_atts"], results={
            **stats,
            "sign_secs": round(sign_secs, 2),
            "shuffle_secs": round(shuffle_secs, 2),
            "slot_third_s": third,
            "ms_per_att": round(per_att * 1e3, 3)}))


if __name__ == "__main__":
    main()
