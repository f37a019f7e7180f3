#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of BENCHMARK.json's `workloads`: its configuration
file, its traffic file (benchmark/traffic/<traffic>.json, which names
the driver in benchmark/drivers/) and the metrics that list it. The run
refuses anything but a TPU, builds the cell and warms every shape its
traffic uses (set-up, timed as `setup_s`), measures for `--seconds`,
checks every verdict of the window against the plain reference and
prints one JSON line last on stdout. With `--trace 1` the window runs
under the JAX profiler and the line carries the per-layer metrics, read
by benchmark/layer_metrics/<metric>.py, instead of the end-to-end ones.

`--rehearse` runs the same code on whatever JAX finds (the CPU) at the
cell's tiny `rehearsal` sizes and prints no metric and no result line.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAULTS = ("", "half", "flip", "no_isolate", "no_subgroup", "memo")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="any backend, tiny sizes, no metrics printed")
    ap.add_argument("--fault", choices=FAULTS, default="",
                    help="break the verdicts underneath (tests, control)")
    return ap.parse_args(argv)


def diag(**kw) -> None:
    print("diag: " + json.dumps(kw, default=str), file=sys.stderr,
          flush=True)


def run(args, t_start: float = None) -> dict:
    """One cell, start to end; returns the result line's object."""
    t_start = T_START if t_start is None else t_start
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import harness, peaks, trace_reduce

    # The persistent compilation cache at one fixed path in the checkout,
    # given to the program before JAX starts.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    # A node keeps every stage executable it serves in that cache; the
    # program's default cap (256 MiB an entry) would leave the largest
    # all-distinct stages out, to be compiled again by every run.
    os.environ["LIGHTHOUSE_TPU_JAX_CACHE_MAX_BYTES"] = str(4 * 2**30)

    spec = harness.load_cell(args.workload)
    device = harness.device_record(args.rehearse, spec["cell"]["chips"])
    if not args.rehearse:
        peaks.device_peaks(device["kind"])  # a chip with no peaks: refused
    harness.compiles()  # refuses a run that cannot count compiles
    spans = harness.Spans()
    harness.BlsSeam(spans, args.fault)
    driver = importlib.import_module(
        "benchmark.drivers." + spec["traffic"]["driver"])
    cell = driver.Cell(spec["config"], spec["traffic"], args.seed, spans,
                       args.seconds, rehearse=args.rehearse)

    parts = cell.setup()
    # Set-up leaves millions of objects (traced stage graphs, the cell's
    # data): keep them out of the collector's full passes in the window.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    diag(setup=parts, setup_s=setup_s, setup_compiles=harness.compiles())

    spans.records.clear()
    tracer = harness.Tracer(bool(args.trace), args.workload)
    before = harness.compiles()
    cell.run_window(args.seconds, tracer)
    in_window = harness.compiles() - before
    if in_window:
        raise SystemExit(f"benchmark: {in_window} compiles inside the "
                         f"window; a shape was not warmed")
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    e2e = cell.end_to_end()
    attempted, failed = cell.counts()
    cell.close()

    checks = cell.checks()
    diag(window=getattr(cell, "diag", {}))
    result = {"correct": all(v <= lim for _, v, lim in checks),
              "attempted": attempted, "failed": failed}
    if args.trace:
        red = None
        if tracer.done:
            t0 = time.perf_counter()
            red = trace_reduce.reduce(trace_reduce.load(tracer.log_dir),
                                      spec["traffic"]["gap_priority"])
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            diag(trace={"stop_s": tracer.stop_s,
                        "reduce_s": time.perf_counter() - t0,
                        "bytes": trace_reduce.size(tracer.log_dir)})
        ctx = dict(cell.layer_context(), trace=red, spans=spans.records)
        metrics = {}
        for m in spec["per_layer"]:
            value = harness.layer_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        if red is not None:
            result["breakdown"] = trace_reduce.breakdown(red)
    else:
        values = dict(e2e, setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in spec["end_to_end"]}
        result["device"] = device
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    for name, v, lim in checks:
        print(f"check {name}: {v} (limit {lim})", file=sys.stderr,
              flush=True)
    return result


def main(argv=None) -> int:
    args = parse(argv)
    result = run(args)
    if args.rehearse:
        print(f"rehearsal done: correct={result['correct']} "
              f"attempted={result['attempted']}", file=sys.stderr)
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
