"""The ROADMAP Open-item-2 roofline table, measured from stage traces.

Usage:
    JAX_PLATFORMS=cpu python scripts/report_roofline.py [--n 256] [--k 4]
        [--reps 3] [--device-kind "TPU v5 lite"] [--layout auto|major|bm]
        [--trace /tmp/roofline.trace.json]      # also export the trace
        [--from-trace PATH]                     # table from a saved trace
        [--overhead]                            # measure span overhead

Enables the observability tracer, drives the staged engine core on
synthetic staged tensors for two shapes — the HEADLINE shape (64 sets
per distinct message: the gossip-firehose regime the 14.4k sigs/s claim
lives in) and the ALL-DISTINCT shape (m = n: every set its own message,
the round-6 wall) — and prints per-stage wall time, sigs/s, and the
achieved-vs-peak FLOP fraction. Runs unchanged on chip: the stage spans
come from the engines' own `block_until_ready` seams
(observability/stages.py), not from anything CPU-specific.

FLOP model (NOTES_TPU_PERF.md "what would 200k sigs/s take": the
representation-inflated ~1.7 GFLOP per all-distinct k=4 set, split by
the stage shares measured on the device path — h2c ~31% of all-distinct
device time, prep the scalar ladders, pairing the Miller loop + final
exponentiation over m+1 pairs):

    h2c      0.35 GFLOP per DISTINCT message
    prep     0.55 GFLOP per set
    pairing  0.80 GFLOP per pairing row (m + 1 rows)

so the all-distinct per-set total is 0.35+0.55+0.80 = 1.7 GFLOP, and
200k all-distinct sigs/s needs ~340 TFLOP/s — above the 197 bf16
TFLOP/s peak of the target chip. The table prints that ceiling next to
the measured fraction so the gap is a number, not an argument.

`--overhead` measures the tracing seams' cost: the same shape run with
tracing disabled (async pipelining intact) vs enabled (block + record),
reported as a percentage. The acceptance bar is <2% at n=1024.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# GFLOP model constants (docstring rationale).
FLOPS_H2C_PER_MSG = 0.35e9
FLOPS_PREP_PER_SET = 0.55e9
FLOPS_PAIRING_PER_PAIR = 0.80e9

STAGES = ("h2g2", "prepare", "pairing")

# Published per-chip peaks keyed by jax's `device_kind` (source: Google
# Cloud documentation, "TPU v5e"). A kind not in the table is an error:
# there is no default peak to divide by.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gb_s": 819.0},
}


def device_peaks(kind: str) -> dict:
    if kind not in DEVICE_PEAKS:
        raise SystemExit(f"report_roofline: no published peak for device "
                         f"kind {kind!r} (known: {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[kind]


def _stage_flops(stage: str, n: int, m: int) -> float:
    if stage == "h2g2":
        return FLOPS_H2C_PER_MSG * m
    if stage == "prepare":
        return FLOPS_PREP_PER_SET * n
    return FLOPS_PAIRING_PER_PAIR * (m + 1)


def _staged_args(layout: str, n: int, k: int, m: int):
    """Synthetic staged tensors for one (n, k, m) shape (the bench.py
    sweep idiom: zeros/infinity staging exercises the identical graph)."""
    import jax.numpy as jnp
    import numpy as np

    if layout == "bm":
        from lighthouse_tpu.ops.bm import backend as bmb
        from lighthouse_tpu.ops.bm import curves as cv
        from lighthouse_tpu.ops.bm import limbs as lb

        core = bmb.jitted_core(n, k, m)
        u = jnp.zeros((2, 2, lb.L, m), dtype=lb.DTYPE)
        inv_idx = jnp.asarray(np.arange(n, dtype=np.int32) % m)
        row_mask = jnp.ones((m,), dtype=bool)
        pk = jnp.broadcast_to(cv.G1.infinity, (k, 3, lb.L, n))
        sig = jnp.broadcast_to(cv.G2.infinity, (3, 2, lb.L, n))
        chk = jnp.ones((n,), dtype=bool)
        mask = jnp.ones((n,), dtype=bool)
        sc = jnp.asarray(np.arange(1, n + 1, dtype=np.uint64))
        return core, (u, inv_idx, row_mask, pk, sig, chk, mask, sc)

    from lighthouse_tpu.ops import backend as be
    from lighthouse_tpu.ops import curves as cv
    from lighthouse_tpu.ops import limbs as lb

    core = be._jitted_core(n, k, False)
    u = jnp.zeros((m, 2, 2, lb.L), dtype=lb.DTYPE)
    inv_idx = jnp.asarray(np.arange(n, dtype=np.int32) % m)
    pk = jnp.broadcast_to(cv.G1.infinity, (n, k, 3, lb.L))
    sig = jnp.broadcast_to(cv.G2.infinity, (n, 3, 2, lb.L))
    chk = jnp.ones((n,), dtype=bool)
    mask = jnp.ones((n,), dtype=bool)
    sc = jnp.asarray(np.arange(1, n + 1, dtype=np.uint64))
    return core, (u, inv_idx, pk, sig, chk, mask, sc)


def _collect_stage_times(events, engine: str):
    """Best (min) duration per stage from a trace's stage spans, seconds.
    Min matches the probe discipline: host and OS jitter only ever add
    time."""
    best = {}
    for ev in events:
        if ev.get("ph") != "X" or ev.get("cat") != "stage":
            continue
        args = ev.get("args", {})
        if args.get("engine") != engine:
            continue
        stage = args.get("stage")
        dur_s = ev["dur"] / 1e6
        if stage not in best or dur_s < best[stage]:
            best[stage] = dur_s
    # The BM engine runs the final exponentiation as its own stage; the
    # FLOP model counts it inside the pairing row.
    if "final_exp" in best:
        best["pairing"] = best.get("pairing", 0.0) + best.pop("final_exp")
    return best


def measure_shape(layout: str, n: int, k: int, m: int, reps: int):
    """Run the staged core under tracing; per-stage best-of-reps."""
    import jax

    from lighthouse_tpu.observability import trace

    core, args = _staged_args(layout, n, k, m)
    jax.block_until_ready(core(*args))        # compile + warm (traced too)
    trace.TRACER.clear()                      # drop the compile-heavy warmup
    for _ in range(reps):
        jax.block_until_ready(core(*args))
    return _collect_stage_times(trace.TRACER.events(), layout)


def print_table(shape_name: str, layout: str, n: int, k: int, m: int,
                times: dict, peak_tflops: float):
    total = sum(times.get(s, 0.0) for s in STAGES)
    sigs_s = n / total if total else float("nan")
    print(f"\n=== {shape_name}: layout={layout} n={n} k={k} m={m} "
          f"-> {sigs_s:,.1f} sigs/s (sum of stages {total:.4f}s) ===")
    print(f"  {'stage':<16}{'wall s':>10}{'share':>8}{'sigs/s':>12}"
          f"{'GFLOP':>9}{'TFLOP/s':>9}{'vs peak':>9}")
    rows = []
    for stage in STAGES:
        t = times.get(stage)
        if t is None:
            continue
        fl = _stage_flops(stage, n, m)
        tf = fl / t / 1e12
        label = {"h2g2": "h2c", "prepare": "prep(+combine)",
                 "pairing": "pairing"}[stage]
        print(f"  {label:<16}{t:>10.4f}{t / total:>7.1%}{n / t:>12,.1f}"
              f"{fl / 1e9:>9.2f}{tf:>9.3f}{tf / peak_tflops:>9.2%}")
        rows.append({"stage": label, "wall_s": t, "share": t / total,
                     "sigs_s": n / t, "gflop": fl / 1e9,
                     "tflop_s": tf, "vs_peak": tf / peak_tflops})
    batch_fl = sum(_stage_flops(s, n, m) for s in STAGES)
    batch_tf = batch_fl / total / 1e12 if total else float("nan")
    print(f"  {'TOTAL':<16}{total:>10.4f}{1.0:>7.0%}{sigs_s:>12,.1f}"
          f"{batch_fl / 1e9:>9.2f}{batch_tf:>9.3f}"
          f"{batch_tf / peak_tflops:>9.2%}")
    return {"shape": shape_name, "n": n, "k": k, "m": m, "layout": layout,
            "total_s": total, "sigs_s": sigs_s, "stages": rows,
            "tflop_s": batch_tf, "vs_peak": batch_tf / peak_tflops}


def roofline_statement(peak_tflops: float):
    per_set = (FLOPS_H2C_PER_MSG + FLOPS_PREP_PER_SET
               + FLOPS_PAIRING_PER_PAIR)
    need_200k = 200_000 * per_set / 1e12
    ceiling = peak_tflops * 1e12 / per_set
    print(f"\nroofline: all-distinct k=4 costs ~{per_set / 1e9:.1f} GFLOP/set"
          f" in this representation, so 200k sigs/s needs "
          f"~{need_200k:.0f} TFLOP/s — vs {peak_tflops:.0f} TFLOP/s bf16 "
          f"peak. Compute ceiling at peak: ~{ceiling / 1e3:.0f}k "
          f"all-distinct sigs/s; beyond that takes representation or "
          f"same-message wins, not scheduling (NOTES_TPU_PERF.md).")
    return {"gflop_per_set": per_set / 1e9,
            "tflops_needed_200k": need_200k,
            "peak_tflops": peak_tflops,
            "ceiling_sigs_s": ceiling}


def measure_overhead(layout: str, n: int, k: int, reps: int):
    """Traced vs untraced end-to-end wall time at (n, k, m=n)."""
    import jax

    from lighthouse_tpu.observability import trace

    core, args = _staged_args(layout, n, k, n)
    jax.block_until_ready(core(*args))
    trace.TRACER.disable()

    def best_of(r):
        best = float("inf")
        for _ in range(r):
            t0 = time.perf_counter()
            jax.block_until_ready(core(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    t_off = best_of(reps)
    trace.TRACER.enable()
    trace.TRACER.clear()
    t_on = best_of(reps)
    overhead = (t_on - t_off) / t_off
    print(f"\nspan overhead @ n={n} k={k} m={n} ({layout}): "
          f"untraced {t_off:.4f}s, traced {t_on:.4f}s "
          f"-> {overhead:+.2%} (acceptance: <2%)")
    return {"n": n, "k": k, "untraced_s": t_off, "traced_s": t_on,
            "overhead_frac": overhead}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=256,
                    help="batch bucket (sets); CPU default modest")
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--device-kind", default=None,
                    help="device_kind whose published peak the vs-peak "
                         "column divides by (default: the device this "
                         "run measures on; required with --from-trace)")
    ap.add_argument("--layout", default="auto",
                    choices=("auto", "major", "bm"))
    ap.add_argument("--trace", default=None,
                    help="also save the Chrome trace JSON here")
    ap.add_argument("--from-trace", default=None,
                    help="skip execution; build the table from this trace")
    ap.add_argument("--overhead", action="store_true",
                    help="measure traced-vs-untraced overhead instead")
    args = ap.parse_args(argv)

    from lighthouse_tpu.observability import report as obs_report
    from lighthouse_tpu.observability import trace

    if args.from_trace:
        peak_tflops = device_peaks(args.device_kind)["bf16_tflops"]
        events = json.load(open(args.from_trace))["traceEvents"]
        engines = sorted({e["args"].get("engine") for e in events
                          if e.get("cat") == "stage"})
        results = []
        for engine in engines:
            times = _collect_stage_times(events, engine)
            ns = sorted({e["args"].get("n") for e in events
                         if e.get("cat") == "stage"
                         and e["args"].get("engine") == engine})
            n = ns[-1] if ns else args.n
            results.append(print_table(
                f"from-trace:{os.path.basename(args.from_trace)}",
                engine, n, args.k, n, times, peak_tflops))
        roofline_statement(peak_tflops)
        return 0

    import jax

    from lighthouse_tpu.ops import backend as be

    kind = args.device_kind or jax.devices()[0].device_kind
    layout = args.layout if args.layout != "auto" else be._layout()
    rep = obs_report.make("report_roofline", params={
        "n": args.n, "k": args.k, "reps": args.reps, "layout": layout,
        "device_kind": kind})

    if args.overhead:
        out = measure_overhead(layout, args.n, args.k, args.reps)
        obs_report.emit(obs_report.finish(
            rep, ok=out["overhead_frac"] < 0.02, results=out))
        return 0

    peak_tflops = device_peaks(kind)["bf16_tflops"]
    trace.TRACER.enable()
    from lighthouse_tpu.observability import compile_events

    compile_events.install()

    m_headline = max(1, args.n // 64)
    shapes = [("headline (64 sets/msg)", m_headline),
              ("all-distinct", args.n)]
    tables = []
    for shape_name, m in shapes:
        t0 = time.perf_counter()
        times = measure_shape(layout, args.n, args.k, m, args.reps)
        print(f"[measured {shape_name} in {time.perf_counter() - t0:.1f}s "
              f"(includes compile on cold caches)]", file=sys.stderr)
        if not times:
            print(f"ERROR: no stage spans recorded for {shape_name} — "
                  "is the engine's _traced seam wired?", file=sys.stderr)
            obs_report.emit(obs_report.finish(rep, ok=False))
            return 1
        tables.append(print_table(shape_name, layout, args.n, args.k, m,
                                  times, peak_tflops))
    roof = roofline_statement(peak_tflops)
    print(f"\ncompile events: { {k: int(v) for k, v in compile_events.counts().items() if v} }")

    if args.trace:
        trace.TRACER.save(args.trace)
        rep["trace_path"] = args.trace
        print(f"trace written: {args.trace}")
    obs_report.emit(obs_report.finish(rep, ok=True, results={
        "tables": tables, "roofline": roof,
        "compile_events": compile_events.counts()}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
