"""Reduce a JAX profiler trace to what the benchmark reports.

Input: the `.xplane.pb` that `jax.profiler.start_trace` writes, read with
`jax.profiler.ProfileData`. Output (`reduce`):

- `window`: the traced window, the extent of the benchmark's own
  `bench.window` span;
- `busy_s`: the union of the intervals in which an executable ran on the
  device, clipped to the window, averaged over the device planes;
- `modules`: device seconds per executable (name without its program id);
- `gaps`: every idle interval of the device inside the window, named by
  the benchmark span that was open on the host at its midpoint, the
  highest in `priority` order.

Device planes: on a TPU, `/device:TPU:<n>`, whose line `XLA Modules`
holds one event per executable run (`jit__h2g2(<program id>)`). Its other
lines (`XLA Ops`, `Async XLA Ops`: one event per operation, millions in a
window) are not read. On the CPU backend, which has no device plane,
XLA's host threads carry the operation events, marked by an `hlo_module`
stat; there each operation is its own interval of its executable. Host
spans are the `TraceAnnotation`s whose names start with `bench.`, on any
host line.
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
_PROGRAM_ID = re.compile(r"\s*\(\d+\)$")


def module_name(name: str) -> str:
    """`jit__h2g2(1234)` -> `jit__h2g2`."""
    return _PROGRAM_ID.sub("", name).strip()


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def collect(profile) -> dict:
    """Raw events: per device plane its executable runs, and the host
    spans. Times in nanoseconds on the trace's clock."""
    devices, spans, cpu_ops = {}, [], []
    for plane in profile.planes:
        if _TPU_PLANE.match(plane.name):
            devices[plane.name] = [
                (e.start_ns, e.start_ns + e.duration_ns, module_name(e.name))
                for line in plane.lines if line.name == "XLA Modules"
                for e in line.events]
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
                    continue
                mod = _stats(e).get("hlo_module")
                if mod is not None and e.duration_ns > 0:
                    cpu_ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                    module_name(str(mod))))
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
    return {"devices": devices, "spans": spans}


def union(intervals) -> list:
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, t in sorted((s, t) for s, t in intervals if t > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(t, hi)) for s, t in intervals
            if t > lo and s < hi]


def _name_gap(mid, spans, priority) -> str:
    open_at = {name for name, s, t in spans if s <= mid < t}
    for name in priority:
        if name in open_at:
            return name
    return "no span open"


def reduce(profile, priority=()) -> dict:
    raw = collect(profile)
    spans = raw["spans"]
    win = [(s, t) for name, s, t in spans if name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace has no {WINDOW_SPAN} span")
    lo, hi = win[0]
    window_s = (hi - lo) / 1e9
    busy, modules, gaps = [], {}, []
    for runs in raw["devices"].values():
        merged = union(_clip([(s, t) for s, t, _ in runs], lo, hi))
        busy.append(sum(t - s for s, t in merged) / 1e9)
        for s, t, name in runs:
            if s >= lo and t <= hi:
                modules[name] = modules.get(name, 0.0) + (t - s) / 1e9
        prev = lo
        for s, t in merged + [(hi, hi)]:
            if s > prev:
                gaps.append((_name_gap((prev + s) / 2, spans, priority),
                             (s - prev) / 1e9))
            prev = max(prev, t)
    n_dev = max(1, len(raw["devices"]))
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "n_devices": len(raw["devices"]),
        "modules": modules,
        "gaps": sorted(gaps, key=lambda g: -g[1]),
    }


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's `breakdown`: the executables that took most
    device time and the longest idle gaps, each [name, seconds]."""
    mods = sorted(red["modules"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in mods],
            "idle_gaps": [[n, s] for n, s in red["gaps"][:top]]}


def size(log_dir: str) -> int:
    return os.path.getsize(find_xplane(log_dir))


def load(log_dir: str):
    import jax

    return jax.profiler.ProfileData.from_file(find_xplane(log_dir))
