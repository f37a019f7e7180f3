"""What every cell shares: spans on the profiler's clock, the trace window,
compile counting, the timing wrapper on the BLS seam, the device record,
and loading a cell's files by the names in BENCHMARK.json."""

from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import time
from contextlib import contextmanager

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(BENCH, ".traces")
DATA_DIR = os.path.join(BENCH, ".cache")


def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def load_cell(workload: str) -> dict:
    """The cell's entry, its configuration and traffic files, and the
    metrics BENCHMARK.json gives it."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "config": load_json(os.path.join(ROOT, cfg_entry["file"])),
        "traffic": load_json(os.path.join(BENCH, "traffic",
                                          cell["traffic"] + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_reader(metric: str):
    """benchmark/layer_metrics/<metric>.py: `read(ctx)` -> number or None."""
    path = os.path.join(BENCH, "layer_metrics", metric + ".py")
    return load_module(path, "layer_metric_" + metric.replace(".", "_")).read


class Spans:
    """Host spans around each call into a layer: kept in memory with the
    host clock, and written into the profiler's trace (on the device
    trace's clock) as `TraceAnnotation`s whenever a trace is running."""

    def __init__(self):
        self.records = []   # (name, t0, t1, attrs); list.append is atomic

    @contextmanager
    def span(self, name: str, **attrs):
        import jax

        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield attrs
        finally:
            self.records.append((name, t0, time.perf_counter(), attrs))

    def of(self, name: str) -> list:
        return [r for r in self.records if r[0] == name]


class Tracer:
    """The profiler around the part of the window a cell traces, opened
    and closed by the cell; the `bench.window` span marks its extent."""

    def __init__(self, on: bool, workload: str):
        self.on = on
        self.log_dir = os.path.join(TRACE_DIR, workload)
        self._ann = None
        self.done = False

    def start(self) -> None:
        if not self.on or self._ann is not None or self.done:
            return
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir)
        # Host spans (TraceMe level 2) and device events; no Python call
        # tracing, which would slow the host path being measured.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()

    def stop(self) -> None:
        if self._ann is None:
            return
        import jax

        self._ann.__exit__(None, None, None)
        self._ann = None
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t0
        self.done = True


def compiles() -> int:
    """XLA compiles and persistent-cache loads so far, as the program's
    `observability/compile_events` records them from jax's monitoring
    events. A window must see none."""
    from lighthouse_tpu.observability import compile_events

    if not compile_events.install():
        raise SystemExit("benchmark: jax's monitoring events are out of "
                         "reach; compiles in the window cannot be counted")
    # Every backend compile is timed into the histogram; a cache miss is
    # counted only where jax writes the entry, so small programs would
    # slip past `first_compile`.
    _, compiled, _ = compile_events._compile_seconds().snapshot()
    return compiled + compile_events.counts()["persistent_cache_hit"]


class BlsSeam:
    """A timing wrapper on the crypto/bls backend seam: the "tpu" backend
    and its bisection verifier are replaced by wrappers that open a
    `bench.bls` span and call the program's own functions. `fault` breaks
    the verdicts underneath, for the correctness tests and the control:
      half         verify only the first half of each call's sets;
      flip         return the opposite verdict;
      no_isolate   a failed batch rejects every set in it (no bisection);
      no_subgroup  signatures marked as subgroup-checked, so the device
                   skips the G2 subgroup check;
      memo         sets that passed in an earlier call are not verified
                   again (a verdict cache)."""

    def __init__(self, spans: Spans, fault: str = ""):
        from lighthouse_tpu.crypto.bls import api
        from lighthouse_tpu.ops import backend as be

        self.spans = spans
        self.fault = fault
        verify, make = be.verify_signature_sets_tpu, be.pinned_verifier
        passed = set()

        def call(fn, sets):
            if self.fault == "half" and len(sets) > 1:
                sets = sets[: len(sets) // 2]
            if self.fault == "no_subgroup":
                sets = [api.SignatureSet(
                    signature=api.Signature(point=s.signature.point,
                                            subgroup_checked=True),
                    signing_keys=s.signing_keys, message=s.message)
                    for s in sets]
            if self.fault == "memo":
                sets = [s for s in sets if id(s) not in passed]
                if not sets:
                    return True
            with spans.span("bench.bls", n=len(sets)):
                ok = bool(fn(sets))
            if self.fault == "memo" and ok:
                passed.update(id(s) for s in sets)
            return (not ok) if self.fault == "flip" else ok

        def timed_verify(sets):
            return call(verify, list(sets))

        def timed_make(root_sets):
            inner = make(root_sets)
            root_n = len(root_sets)

            def verify_sub(sub):
                sub = list(sub)
                if self.fault == "no_isolate" and len(sub) < root_n:
                    return False
                return call(inner, sub)

            return verify_sub

        api.register_backend("tpu", timed_verify)
        api.register_bisect_verifier("tpu", timed_make)


def device_record(rehearse: bool, chips: int) -> dict:
    """The device as JAX reports it. Outside a rehearsal, anything but a
    TPU, or fewer chips than the cell asks for, ends the run."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if not rehearse:
        if d.platform != "tpu":
            raise SystemExit(f"benchmark: JAX found no TPU (platform "
                             f"{d.platform!r}); refusing to run")
        if len(devs) < chips:
            raise SystemExit(f"benchmark: {chips} chips asked, "
                             f"{len(devs)} found")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by the nearest-rank rule."""
    vals = sorted(values)
    if not vals:
        raise ValueError("no samples")
    rank = max(1, math.ceil(q * len(vals) / 100 - 1e-9))
    return vals[min(len(vals), rank) - 1]
