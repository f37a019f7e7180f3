"""The package's one span API, with two sinks.

`span(name, **args)` opens a `jax.profiler.TraceAnnotation(name, **args)`
around the `with` body. With no profiler session that is one TraceMe
check; under `jax.profiler.start_trace` the span lands on the host line
of its thread in the `.xplane.pb`, on the same clock as the device's
`XLA Modules` events, with its args as event stats. Where jax cannot be
imported the annotation is skipped.

The second sink is the operator's trace when no profiler runs: an
in-memory buffer exported as Chrome trace-event JSON (`chrome://tracing`
and Perfetto both open it): complete events (`ph:"X"`, microsecond
`ts`/`dur`) and instants (`ph:"i"`). It is OFF by default and the
disabled path is one attribute read. Enable it programmatically
(`trace.enable()`) or via `LIGHTHOUSE_TPU_TRACE=1`; setting it to a path
(`/tmp/run.trace.json`) also installs an atexit export to that path.

Span names on the node's path take one of three prefixes: `bp.` (the
beacon processor), `att.` (attestation verification and import) and
`bls.` (the BLS backends). Args are small scalars; one span per phase
of a batch, never one per attestation or per set.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional


class Tracer:
    """Thread-safe in-memory trace buffer. All timestamps come from one
    `perf_counter` origin so spans from different threads line up."""

    def __init__(self, max_events: int = 200_000):
        self.max_events = max_events
        self.enabled = False
        self._events: List[Dict[str, Any]] = []
        self._dropped = 0
        self._origin = time.perf_counter()
        self._lock = threading.Lock()
        self._depth = threading.local()

    # ------------------------------------------------------------- control

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events = []
            self._dropped = 0
            self._origin = time.perf_counter()

    def _now_us(self) -> float:
        return (time.perf_counter() - self._origin) * 1e6

    def _depth_stack(self) -> list:
        stack = getattr(self._depth, "stack", None)
        if stack is None:
            stack = self._depth.stack = []
        return stack

    def _push(self, event: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self._dropped += 1
                return
            self._events.append(event)

    # ----------------------------------------------------------- recording

    def _open(self, name: str):
        """Start a complete event on this thread: (t0_us, depth)."""
        stack = self._depth_stack()
        stack.append(name)
        return self._now_us(), len(stack)

    def _close(self, name: str, cat: str, t0: float, depth: int,
               args: Dict[str, Any]) -> None:
        t1 = self._now_us()
        self._depth_stack().pop()
        self._push({
            "name": name, "cat": cat, "ph": "X",
            "ts": t0, "dur": max(t1 - t0, 0.0),
            "pid": os.getpid(), "tid": threading.get_ident(),
            "args": {"depth": depth, **args},
        })

    @contextmanager
    def span(self, name: str, cat: str = "engine", **args):
        """Record a complete event around the `with` body. Nesting depth
        is tracked per thread and stamped into args so exporters (and the
        balance test) can check containment without re-deriving it."""
        if not self.enabled:
            yield None
            return
        t0, depth = self._open(name)
        try:
            yield self
        finally:
            self._close(name, cat, t0, depth, args)

    def instant(self, name: str, cat: str = "engine", **args) -> None:
        if not self.enabled:
            return
        self._push({
            "name": name, "cat": cat, "ph": "i", "s": "t",
            "ts": self._now_us(),
            "pid": os.getpid(), "tid": threading.get_ident(),
            "args": dict(args),
        })

    # ------------------------------------------------------------- export

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def export(self) -> Dict[str, Any]:
        """The Chrome trace-event wrapper object (JSON-serialisable)."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "lighthouse_tpu.observability",
                "dropped_events": dropped,
            },
        }

    def save(self, path: str) -> str:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.export(), f)
        os.replace(tmp, path)
        return path


# The process-global tracer: every instrumentation seam in the package
# records here, so one enable() captures engine + serving + processor.
TRACER = Tracer()

_annotation = None   # jax.profiler.TraceAnnotation, False without jax


def _annotation_cls():
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = False
        _annotation = TraceAnnotation
    return _annotation


class Span:
    """A span of the `span()` API. `set(**args)` adds args known only at
    its end (sub-batch calls made, culprits found); both sinks get them."""

    __slots__ = ("name", "cat", "args", "late", "_ann", "_t0", "_depth")

    def __init__(self, name: str, cat: str, args: Dict[str, Any]):
        self.name, self.cat, self.args = name, cat, args
        self.late: Dict[str, Any] = {}
        self._ann = None
        self._t0 = None

    def set(self, **args) -> None:
        self.late.update(args)

    def __enter__(self) -> "Span":
        cls = _annotation_cls()
        if cls:
            self._ann = cls(self.name, **self.args)
            self._ann.__enter__()
        if TRACER.enabled:
            self._t0, self._depth = TRACER._open(self.name)
        return self

    def __exit__(self, *exc) -> None:
        if self._ann is not None:
            if self.late:
                self._ann.set_metadata(**self.late)
            self._ann.__exit__(None, None, None)
        if self._t0 is not None:
            TRACER._close(self.name, self.cat, self._t0, self._depth,
                          {**self.args, **self.late})


def enabled() -> bool:
    return TRACER.enabled


def enable() -> Tracer:
    TRACER.enable()
    return TRACER


def disable() -> None:
    TRACER.disable()


def span(name: str, cat: str = "engine", **args) -> Span:
    """A span on the profiler's host line (always) and in the Chrome
    buffer (when enabled). `cat` names the Chrome event's category."""
    return Span(name, cat, args)


def instant(name: str, cat: str = "engine", **args) -> None:
    TRACER.instant(name, cat, **args)


def export() -> Dict[str, Any]:
    return TRACER.export()


def save(path: str) -> str:
    return TRACER.save(path)


def _init_from_env() -> Optional[str]:
    val = os.environ.get("LIGHTHOUSE_TPU_TRACE", "")
    if not val or val == "0":
        return None
    TRACER.enable()
    if val == "1":
        return None
    # Any other value is an export path; write it out when the process
    # exits so probe runs under the env var need no code changes.
    atexit.register(lambda: TRACER.save(val))
    return val


_TRACE_PATH = _init_from_env()
