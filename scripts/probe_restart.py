#!/usr/bin/env python
"""Restart-mid-slot probe: warm-bundle vs cold-compile time to first batch.

The failure mode this measures: a node killed mid-slot restarts and must
verify a full-size batch NOW. Cold, each bucket shape pays trace + lower
(minutes per shape even small); with an AOT warm bundle (serving/aot.py)
the stages deserialize in seconds. The probe:

  1. ensures a bundle exists for the probe shape, in a child process
     (exporting it once if needed — that one-time cost is printed as the
     measured cold evidence; `--cold` additionally runs a true cold
     consumer against an empty compilation cache);
  2. spawns a FRESH consumer process (the "restarted node") pointed at
     the bundle, which warms the shape, then drives a mixed
     attestation + sync-signature workload through the continuous
     scheduler + cost router to its first full-size verified batch;
  3. prints warm start-to-first-batch next to the cold number, plus the
     consumer's router decisions and scheduler deadline hits/misses.

The parent never touches JAX: a chip belongs to one process at a time,
so the export and each consumer run in their own child, one after
another. It runs on whatever platform JAX picks; on the CPU:

    JAX_PLATFORMS=cpu python scripts/probe_restart.py --bundle /tmp/wb

Heavy-XLA note: the one-time export (and any --cold run) compiles for
minutes; don't run concurrently with other compile jobs on small hosts.
"""

import argparse
import os
import subprocess
import sys
import time

_T_PROC_START = time.perf_counter()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# ---------------------------------------------------------------------------
# Consumer: the "restarted node" (fresh process, bundle via env)
# ---------------------------------------------------------------------------


def consumer(n: int, k: int, bundle: str) -> int:
    """Measure start-to-first-full-size-verified-batch in THIS process.
    Emits one probe-report JSON line (observability/report.py schema) on
    stdout; everything else goes to stderr. `bundle` "" = no bundle."""
    os.environ["LIGHTHOUSE_TPU_CPU_FALLBACK_MAX"] = "0"  # measure device
    from lighthouse_tpu.serving import aot

    os.environ[aot.ENV_VAR] = bundle

    from lighthouse_tpu.observability import report as obs_report

    rep = obs_report.make("probe_restart.consumer",
                          params={"n": n, "k": k})

    from lighthouse_tpu.beacon_processor.processor import AdaptiveBatchPolicy
    from lighthouse_tpu.beacon_processor.warming import ShapeWarmer
    from lighthouse_tpu.common import metrics as m
    from lighthouse_tpu.common.slot_clock import ManualSlotClock
    from lighthouse_tpu.serving import aot
    from lighthouse_tpu.serving.router import CostModelRouter, LatencyTable
    from lighthouse_tpu.serving.scheduler import (
        ContinuousBatchScheduler,
        VerifyJob,
    )

    policy = AdaptiveBatchPolicy()
    warmer = ShapeWarmer(policy, shapes=[(n, k)], bundle="auto")
    t0 = time.perf_counter()
    warmer.warm_one(n, k)
    policy.note_ran(n)
    warm_secs = time.perf_counter() - t0
    print(f"warm_one({n}, {k}): {warm_secs:.1f}s "
          f"(bundle={bool(warmer.bundle_warmed)})", file=sys.stderr)

    # Mixed workload through the serving stack: all-device routing (the
    # probe measures the device path; small_batch_max=0 disables the
    # small-batch CPU rule).
    clock = ManualSlotClock(genesis_time=0, seconds_per_slot=12)
    clock.set_slot(100)
    router = CostModelRouter(table=LatencyTable(), small_batch_max=0)
    sched = ContinuousBatchScheduler(clock, policy=policy, router=router)

    from lighthouse_tpu.crypto.bls.api import (
        AggregateSignature,
        SecretKey,
        Signature,
        SignatureSet,
    )

    results = []
    kinds = ("gossip_attestation", "gossip_sync_signature")
    for i in range(n):
        sks = [SecretKey(7_000_000 + i * 64 + j) for j in range(k)]
        msg = i.to_bytes(4, "big") * 8
        agg = AggregateSignature.aggregate([sk.sign(msg) for sk in sks])
        sset = SignatureSet(
            signature=Signature(point=agg.point, subgroup_checked=True),
            signing_keys=[sk.public_key() for sk in sks],
            message=msg,
        )
        sched.submit(VerifyJob(kinds[i % 2], sset, results.append))
    sched.run_until_idle()

    secs_to_first_batch = time.perf_counter() - _T_PROC_START
    out = {
        "secs_to_first_batch": round(secs_to_first_batch, 2),
        "warm_one_secs": round(warm_secs, 2),
        "n": n, "k": k,
        "verified": sum(results), "failed": len(results) - sum(results),
        "bundle_warmed": warmer.bundle_warmed,
        "compiled": warmer.compiled,
        "bundle_stats": vars(aot.stats()),
        "scheduler": {
            "batches": sched.stats.batches,
            "deadline_hits": sched.stats.deadline_hits,
            "deadline_misses": sched.stats.deadline_misses,
            "by_route": sched.stats.by_route,
            "close_causes": {
                c: m.REGISTRY.counter_vec(
                    "serving_scheduler_close_total").get(c)
                for c in ("bucket_full", "deadline", "flush")
            },
        },
        "router": {
            "routes": {r: m.REGISTRY.counter_vec(
                "serving_router_route_total").get(r)
                for r in ("cpu", "device")},
            "reasons": {r: m.REGISTRY.counter_vec(
                "serving_router_reason_total").get(r)
                for r in ("small", "deadline", "cost", "default")},
            "latency_table": router.table.snapshot(),
        },
    }
    ok = bool(results) and all(results)
    obs_report.emit(obs_report.finish(rep, ok=ok, results=out))
    return 0 if ok else 1


def export(bundle_dir: str, n: int, k: int) -> int:
    """Ensure the bundle covers (n, k), exporting it if needed; report
    the export seconds (measured now, or recorded in the manifest)."""
    from lighthouse_tpu.observability import report as obs_report
    from lighthouse_tpu.serving import aot

    rep = obs_report.make("probe_restart.export",
                          params={"n": n, "k": k, "bundle": bundle_dir})
    shape_prefix = f"{aot._current_layout()}|n={n}|k={k}|"
    bundle = aot.open_bundle(bundle_dir)
    if bundle is None or not any(
            key.startswith(shape_prefix) for key in bundle.entries):
        print(f"exporting ({n}, {k}) -> {bundle_dir} "
              "(one-time; this is the cold cost being front-loaded)",
              file=sys.stderr)
        report = aot.make_bundle(bundle_dir, [(n, k)],
                                 progress=lambda s: print(s, file=sys.stderr))
        for e in report.errors:
            print(f"  ERROR {e}", file=sys.stderr)
        obs_report.emit(obs_report.finish(
            rep, ok=not report.errors,
            results={"export_secs": report.export_secs}))
        return 1 if report.errors else 0
    # Measured at production time, recorded in the manifest.
    secs = sum(sum(e.get("export_secs", []))
               for key, e in bundle.entries.items()
               if key.startswith(shape_prefix))
    obs_report.emit(obs_report.finish(rep, ok=True,
                                      results={"export_secs": secs}))
    return 0


# ---------------------------------------------------------------------------
# Parent: stays off JAX; runs the export and the consumers one by one
# ---------------------------------------------------------------------------


def _spawn(mode, n, k, bundle, env_extra=None):
    env = dict(os.environ)
    env.update(env_extra or {})
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), f"--{mode}",
         f"--n={n}", f"--k={k}", f"--bundle={bundle}"],
        env=env, cwd=REPO, capture_output=True, text=True)
    sys.stderr.write(proc.stderr)
    from lighthouse_tpu.observability import report as obs_report

    docs = obs_report.parse_lines(proc.stdout)
    if docs and proc.returncode == 0:
        return docs[-1]["results"]
    raise RuntimeError(
        f"{mode} child failed (rc={proc.returncode}):\n"
        f"{proc.stdout[-2000:]}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bundle", default="/tmp/lighthouse_tpu_warm_bundle")
    ap.add_argument("--n", type=int, default=4,
                    help="probe bucket n (default tiny: even n=4 stages "
                    "trace for minutes cold, which is the point)")
    ap.add_argument("--k", type=int, default=1)
    ap.add_argument("--cold", action="store_true",
                    help="also run a TRUE cold consumer (no bundle, empty "
                    "compilation cache) — adds minutes of XLA compile")
    ap.add_argument("--consumer", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--export", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.consumer:
        return consumer(args.n, args.k, args.bundle)
    if args.export:
        return export(args.bundle, args.n, args.k)

    # 1. Bundle export (child), its cost the measured cold evidence.
    export_secs = _spawn("export", args.n, args.k,
                         args.bundle)["export_secs"]

    # 2. Fresh consumer process, bundle active.
    print("\n--- warm consumer (fresh process, bundle active) ---")
    warm = _spawn("consumer", args.n, args.k, args.bundle)

    cold = None
    if args.cold:
        print("\n--- cold consumer (no bundle, empty compile cache) ---")
        import tempfile

        with tempfile.TemporaryDirectory() as empty_cache:
            cold = _spawn("consumer", args.n, args.k, "",
                          {"JAX_COMPILATION_CACHE_DIR": empty_cache})

    # 3. Report.
    print("\n=== restart-mid-slot probe ===")
    print(f"shape: n={args.n} k={args.k}   bundle: {args.bundle}")
    print(f"warm  start-to-first-full-batch: "
          f"{warm['secs_to_first_batch']:.1f}s "
          f"(bundle_warmed={warm['bundle_warmed']}, "
          f"compiled={warm['compiled']})")
    if cold is not None:
        print(f"cold  start-to-first-full-batch: "
              f"{cold['secs_to_first_batch']:.1f}s (measured, empty cache)")
    print(f"cold  trace+lower cost at export time: {export_secs:.1f}s "
          "(measured; what the bundle front-loads)")
    print(f"verified: {warm['verified']}/{warm['verified'] + warm['failed']}"
          f"  batches: {warm['scheduler']['batches']}"
          f"  deadline hits/misses: {warm['scheduler']['deadline_hits']}"
          f"/{warm['scheduler']['deadline_misses']}")
    print(f"router routes: {warm['router']['routes']}"
          f"  reasons: {warm['router']['reasons']}")
    print(f"scheduler close causes: {warm['scheduler']['close_causes']}")
    print(f"bundle stats: {warm['bundle_stats']}")
    ok = warm["failed"] == 0 and warm["verified"] > 0
    if not warm["bundle_warmed"]:
        print("WARNING: warm consumer fell back to the compile path "
              "(stale/missing bundle?)")
    from lighthouse_tpu.observability import report as obs_report

    rep = obs_report.make("probe_restart", params={
        "n": args.n, "k": args.k, "bundle": args.bundle,
        "cold": bool(args.cold)}, env_facts=False)
    obs_report.emit(obs_report.finish(rep, ok=ok, results={
        "warm": warm, "cold": cold,
        "export_secs": round(export_secs, 2)}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
