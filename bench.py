"""Headline benchmark: batched BLS signature-set verification throughput.

Runs the north-star workload (BASELINE.json config #2 shape): a
mainnet-attestation-style batch of signature sets through the device backend
(`lighthouse_tpu.ops.backend.verify_signature_sets_tpu`), and prints ONE JSON
line:

    {"metric": ..., "value": N, "unit": "sigs/sec", "vs_baseline": N, ...}

The headline batch repeats 64 distinct messages (gossip firehose shape,
where same-message pair combining shrinks the pairing stage); the
`all_distinct_*` fields carry the largest all-distinct sweep row as the
first-class companion — the throughput a no-hash-consing workload
(chain-segment replay, op pool) actually gets.

`vs_baseline` divides by a MEASURED same-host baseline: the native C++
batch verifier (native/src/blscpu.cpp — Montgomery arithmetic, batch-
inverted Miller loop, same batch equation and h2c), single-threaded on
this box, measured in the same process right before the device run
(VERDICT round 2, missing #2: the round-2 divisor was an unmeasured
estimate). The old order-of-magnitude blst estimate is still reported as
`vs_blst_16core_estimate` for continuity with BENCH_r01/r02
(~0.7 ms/set/core on a 16-core host; the reference publishes no absolute
numbers, BASELINE.md).

Runs on a TPU only: without one it exits non-zero and prints no result,
and any failed sweep row fails the run. Every result line names the
device it ran on.
"""

import json
import os
import sys
import time

BLST_16CORE_ESTIMATE_SIGS_PER_SEC = 20_000.0

# Batch shape: 4096 sets x 4 aggregated pubkeys. The reference caps GOSSIP
# batches at 64 (beacon_processor/src/lib.rs:215-216) because CPU batches
# amortize poorly against poisoning risk; the BASELINE.json eval configs
# measure 1k/10k/100k-set batches (chain-segment replay + op-pool shapes).
# Round-4's knee at n=2048 (HBM-bound pairing at 4096) moved in round 5:
# same-message pair combining caps the pairing stage at the distinct-
# message count, so larger buckets keep amortizing (probe_bm e2e: 11.2k
# sigs/s at 2048, 13.1k at 4096). Override with LIGHTHOUSE_TPU_BENCH_SETS.
N_SETS = int(os.environ.get("LIGHTHOUSE_TPU_BENCH_SETS", "4096"))
KEYS_PER_SET = 4
N_DISTINCT = 64       # distinct sets signed on the host; tiled up to N_SETS
TIMED_ITERS = 3
CPU_BASELINE_SETS = 32  # sets per CPU-baseline iteration (~0.2 s each)


def _make_sets(n: int):
    from lighthouse_tpu.crypto.bls.api import (
        AggregateSignature,
        SecretKey,
        Signature,
        SignatureSet,
    )

    sets = []
    for i in range(N_DISTINCT):
        sks = [SecretKey(100_000 + i * 64 + j) for j in range(KEYS_PER_SET)]
        msg = i.to_bytes(4, "big") * 8
        agg = AggregateSignature.aggregate([sk.sign(msg) for sk in sks])
        sets.append(
            SignatureSet(
                signature=Signature(point=agg.point, subgroup_checked=True),
                signing_keys=[sk.public_key() for sk in sks],
                message=msg,
            )
        )
    # Tile: device work is identical per set; host signing time is staging
    # cost, not the measured metric.
    return (sets * ((n + N_DISTINCT - 1) // N_DISTINCT))[:n]


def measure_cpu_baseline(sets) -> float:
    """Single-threaded native C++ verifier throughput on this host
    (sigs/sec), same semantics and subgroup-check amortization flags as
    the device run. Returns 0.0 when the native toolchain is missing."""
    try:
        from lighthouse_tpu.crypto.bls import cpu_backend

        batch = sets[:CPU_BASELINE_SETS]
        if not cpu_backend.verify_signature_sets_cpu(batch):  # warm + check
            return 0.0
        iters = 0
        t0 = time.perf_counter()
        while iters < 2 or time.perf_counter() - t0 < 2.0:
            if not cpu_backend.verify_signature_sets_cpu(batch):
                return 0.0
            iters += 1
            if iters >= 50:
                break
        dt = time.perf_counter() - t0
        return len(batch) * iters / dt
    except Exception:
        return 0.0


def _all_distinct_row(sweep) -> dict:
    """The honest no-hash-consing number: the largest sweep row where every
    message is distinct (distinct == n) at the headline k. The 64-distinct
    headline leans on same-message pair combining; chain-segment replay
    and op-pool batches don't get that break, so this row is the
    first-class companion metric (VERDICT: don't let the headline imply
    all workloads hash-cons)."""
    best = None
    for row in sweep or []:
        if row.get("distinct") != row.get("n") or "sigs_per_sec" not in row:
            continue
        if row.get("k") != KEYS_PER_SET:
            continue
        if best is None or row["n"] > best["n"]:
            best = row
    return best or {}


def _emit(sigs_per_sec: float, cpu_baseline: float, device: dict,
          sweep=None) -> None:
    baseline = cpu_baseline if cpu_baseline > 0 else \
        BLST_16CORE_ESTIMATE_SIGS_PER_SEC
    out = {
        "metric": "bls_batch_verify_throughput",
        "value": round(sigs_per_sec, 2),
        "unit": "sigs/sec",
        "vs_baseline": round(sigs_per_sec / baseline, 4),
        "cpu_baseline_sigs_per_sec": round(cpu_baseline, 2),
        "vs_blst_16core_estimate": round(
            sigs_per_sec / BLST_16CORE_ESTIMATE_SIGS_PER_SEC, 4
        ),
        "n_sets": N_SETS,
        "keys_per_set": KEYS_PER_SET,
        "distinct_messages": N_DISTINCT,
        "device": device,
    }
    ad = _all_distinct_row(sweep)
    if ad:
        out["all_distinct_sigs_per_sec"] = ad["sigs_per_sec"]
        out["all_distinct_n_sets"] = ad["n"]
        out["all_distinct_keys_per_set"] = ad["k"]
    if sweep:
        out["sweep"] = sweep
    print(json.dumps(out))


def _default_sweep_shapes() -> list:
    """The eval-config (n, k, distinct_messages) grid, optionally n-capped
    by LIGHTHOUSE_TPU_BENCH_SWEEP_MAX_N."""
    shapes = [
        (1024, 1, 1024),
        (1024, 4, 1024),
        (2048, 4, 2048),
        (2048, 4, 64),        # hash-consed firehose shape (committees)
        (4096, 4, 4096),
        (1024, 64, 1024),
        (256, 256, 256),      # mainnet aggregate k range
        # Round-6 chunked-prep rungs (prep runs as two 4096-wide ladder
        # slabs; pairing stays one full-width pass).
        (8192, 4, 8192),
        (8192, 4, 64),
    ]
    max_n = int(os.environ.get("LIGHTHOUSE_TPU_BENCH_SWEEP_MAX_N", "")
                or 16384)
    return [s for s in shapes if s[0] <= max_n]


def _shape_sweep(be, shapes=None) -> list:
    """Eval-config shape sweep (VERDICT r4 next #3: BASELINE configs #2/#4).

    Times the DEVICE pipeline at the eval shapes — the n axis (1k/2k/4k
    per dispatch, plus the round-6 chunked-prep 8192 rung; the 10k/100k
    batch configs run as chunked pipelines of the best bucket, reported
    via the chunk row), the k axis (mainnet aggregates span k ~ 1..450),
    and the hash-consed firehose shape (per-committee duplicate
    AttestationData -> 64 distinct messages).
    Synthetic staged tensors: the pipeline is branch-free, so timing is
    identical for real and garbage inputs; rows are TIMING-only (the
    headline above verified a real batch end-to-end). A row that raises
    is kept with its error, and main() then fails the run."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lighthouse_tpu.ops import curves as cv
    from lighthouse_tpu.ops import limbs as lb

    bm_layout = be._layout() == "bm"
    if bm_layout:
        from lighthouse_tpu.ops.bm import backend as bmb
        from lighthouse_tpu.ops.bm import curves as bmc

    if shapes is None:
        shapes = _default_sweep_shapes()
    rows = []
    for n, k, m in shapes:
        try:
            inv_idx = jnp.asarray(
                np.arange(n, dtype=np.int32) % max(m, 1)
            )
            chk = jnp.ones((n,), dtype=bool)
            mask = jnp.ones((n,), dtype=bool)
            sc = jnp.asarray(np.arange(1, n + 1, dtype=np.uint64))
            if bm_layout:
                u = jnp.zeros((2, 2, lb.L, m), dtype=lb.DTYPE)
                row_mask = jnp.ones((m,), dtype=bool)
                pk = jnp.broadcast_to(bmc.G1.infinity, (k, 3, lb.L, n))
                sig = jnp.broadcast_to(bmc.G2.infinity, (3, 2, lb.L, n))
                core = bmb.jitted_core(n, k, m)
                args = (u, inv_idx, row_mask, pk, sig, chk, mask, sc)
            else:
                u = jnp.zeros((m, 2, 2, lb.L), dtype=lb.DTYPE)
                pk = jnp.broadcast_to(cv.G1.infinity, (n, k, 3, lb.L))
                sig = jnp.broadcast_to(cv.G2.infinity, (n, 3, 2, lb.L))
                core = be._jitted_core(n, k, False)
                args = (u, inv_idx, pk, sig, chk, mask, sc)
            jax.block_until_ready(core(*args))          # compile + warm
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(core(*args))
                best = min(best, time.perf_counter() - t0)
            rows.append({
                "n": n, "k": k, "distinct": m,
                "sigs_per_sec": round(n / best, 1),
                "secs": round(best, 4),
            })
        except Exception as e:
            rows.append({"n": n, "k": k, "distinct": m,
                         "error": repr(e)[:120]})
    return rows


def _tpu_device() -> dict:
    """The device this run measures; refuses anything but a TPU."""
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX found {d.platform!r}); "
                         "refusing to measure")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def main():
    device = _tpu_device()
    from lighthouse_tpu.ops import backend as be

    sets = _make_sets(N_SETS)
    # Measure the host baseline FIRST (the device warm-up below may
    # compile for minutes; the baseline is quick and independent).
    cpu_baseline = measure_cpu_baseline(sets)

    n_dev = device["count"]
    sharded = n_dev > 1 and N_SETS % n_dev == 0

    # The bench measures the DEVICE path: disable small-batch routing.
    os.environ["LIGHTHOUSE_TPU_CPU_FALLBACK_MAX"] = "0"

    # Warm-up: compile (persistent-cached) + one correctness check.
    if not be.verify_signature_sets_tpu(sets, sharded=sharded):
        raise SystemExit("bench: benchmark batch failed verification")

    # Time at least TIMED_ITERS iterations and at least ~2 seconds,
    # PIPELINED: each iteration's host staging (ints -> digit
    # tensors, SHA-256 hash_to_field, CSPRNG scalars) overlaps the
    # previous iteration's device execution via the async dispatch
    # (NOTES lever #2); the single block_until_ready at the end
    # drains the queue.
    iters = 0
    pending = []
    t0 = time.perf_counter()
    while iters < TIMED_ITERS or time.perf_counter() - t0 < 2.0:
        pending.append(
            be.verify_signature_sets_tpu_async(sets, sharded=sharded)
        )
        iters += 1
        if iters >= 50:
            break
    results = [bool(p) for p in pending]
    dt = time.perf_counter() - t0
    if not all(results):
        raise SystemExit("bench: verification flaked mid-benchmark")
    sweep = None
    if os.environ.get("LIGHTHOUSE_TPU_BENCH_SWEEP", "1") == "1":
        sweep = _shape_sweep(be)
    _emit(N_SETS * iters / dt, cpu_baseline, device, sweep=sweep)
    failed = [r for r in sweep or [] if "error" in r]
    if failed:
        print(f"bench: {len(failed)} sweep row(s) failed", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
