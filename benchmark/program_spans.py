"""The program's own spans in the run's profiler trace.

The node opens `observability/trace.span`s named `bp.*` (beacon
processor), `att.*` (attestation checks, set build, import) and `bls.*`
(BLS staging, dispatch, device wait, bisection, native answers). Each is
a `jax.profiler.TraceAnnotation`, so under the benchmark's profiler
session it is a host event of the `.xplane.pb`, on the clock of the
device's `XLA Modules` events.

`read(ctx)` loads the newest `.xplane.pb` under benchmark/.traces/ (once
per path) and returns, for the `bench.window` of that file:

- `seconds[name]`, `count[name]`: each program span's time inside the
  window, summed per name, and how many there were;
- `idle_s[name]`: the device-idle seconds each name covers. Every idle
  stretch of the device (outside the union of its busy intervals) is
  cut at the program spans' boundaries, and each piece goes to the
  deepest program span open over it, ties to the later start. Depth is
  the nesting on the span's own thread. Averaged over device planes.

It returns None where nothing was traced, or where the newest file's
window is not the one `ctx["trace"]` was reduced from. A program without
such spans gives empty maps, and the readers then return None.
"""

from __future__ import annotations

from .harness import TRACE_DIR
from .trace_reduce import WINDOW_SPAN, _clip, collect, find_xplane, union

PREFIXES = ("bp.", "att.", "bls.")
_LOADED = {}


def read(ctx):
    red = ctx.get("trace")
    if not red:
        return None
    try:
        path = find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None
    if path not in _LOADED:
        import jax

        _LOADED[path] = summarize(jax.profiler.ProfileData.from_file(path))
    out = _LOADED[path]
    if out is None or abs(out["window_s"] - red["window_s"]) > 1e-9:
        return None
    return out


def program_spans(profile) -> list:
    """(name, start_ns, end_ns, depth) of every program span on the host
    lines; depth 1 for a span that no other program span on its thread
    encloses."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            mine = sorted(((e.start_ns, -(e.start_ns + e.duration_ns),
                            e.name) for e in line.events
                           if e.name.startswith(PREFIXES)))
            ends = []
            for s, neg_t, name in mine:
                while ends and ends[-1] <= s:
                    ends.pop()
                out.append((name, s, -neg_t, len(ends) + 1))
                ends.append(-neg_t)
    return out


def owners(spans) -> list:
    """The timeline under the spans, cut at every boundary: [(start, end,
    name)] for each piece, owned by the deepest span open over it, ties
    to the later start. Pieces under no span are left out."""
    spans = [sp for sp in spans if sp[2] > sp[1]]
    bounds = sorted([(s, 1, i) for i, (_, s, _, _) in enumerate(spans)]
                    + [(t, 0, i) for i, (_, _, t, _) in enumerate(spans)])
    active, out, prev = set(), [], None
    for pos, opens, i in bounds:
        if active and pos > prev:
            name = spans[max(active, key=lambda k: (spans[k][3],
                                                    spans[k][1]))][0]
            if out and out[-1][2] == name and out[-1][1] == prev:
                out[-1] = (out[-1][0], pos, name)
            else:
                out.append((prev, pos, name))
        (active.add if opens else active.discard)(i)
        prev = pos
    return out


def idle_by_owner(busy, lo, hi, pieces) -> dict:
    """Device-idle ns in [lo, hi] (outside `busy`, merged and sorted) per
    owner of `pieces` (owners())."""
    idle, prev = [], lo
    for s, t in busy + [(hi, hi)]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, t)
    out, j = {}, 0
    for a, b in idle:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            s, t, name = pieces[k]
            cut = min(b, t) - max(a, s)
            if cut > 0:
                out[name] = out.get(name, 0) + cut
            k += 1
    return out


def summarize(profile):
    raw = collect(profile)
    win = [(s, t) for name, s, t in raw["spans"] if name == WINDOW_SPAN]
    if not win:
        return None
    lo, hi = win[0]
    spans = [(n, s, t, d) for n, s, t, d in program_spans(profile)
             if t > lo and s < hi]
    seconds, count = {}, {}
    for name, s, t, _ in spans:
        (cs, ct), = _clip([(s, t)], lo, hi)
        seconds[name] = seconds.get(name, 0.0) + (ct - cs) / 1e9
        count[name] = count.get(name, 0) + 1
    pieces = owners(spans)
    idle_s = {}
    n_dev = max(1, len(raw["devices"]))
    for runs in raw["devices"].values():
        busy = union(_clip([(s, t) for s, t, _ in runs], lo, hi))
        for name, ns in idle_by_owner(busy, lo, hi, pieces).items():
            idle_s[name] = idle_s.get(name, 0.0) + ns / 1e9 / n_dev
    return {"window_s": (hi - lo) / 1e9, "seconds": seconds,
            "count": count, "idle_s": idle_s}


def atts_processed(ctx) -> int:
    """The gossip readers' denominator: the `n` of the benchmark's
    `bench.batch` and `bench.single` spans."""
    return sum(s[3].get("n", 1) for s in ctx.get("spans", ())
               if s[0] in ("bench.batch", "bench.single"))


def ms_per_att(ctx, name: str):
    """Seconds in the program span `name` per attestation processed, in
    ms; None where either is missing."""
    spans, n = read(ctx), atts_processed(ctx)
    if not spans or not n or name not in spans["seconds"]:
        return None
    return spans["seconds"][name] / n * 1e3


def idle_under(spans: dict, name: str) -> float:
    """Device-idle seconds owned by `name` or any span named under it
    (`name.*`)."""
    return sum(v for k, v in spans["idle_s"].items()
               if k == name or k.startswith(name + "."))
