"""Load a cell's device stages from a warm bundle and compile them ahead.

The program serves its four BM stages from an AOT warm bundle when one is
active (`serving/aot.py`, `ops/bm/backend._warm_dispatch`): each stage is
a serialized `jax.export` artifact, loaded without tracing the stage's
Python again. The first run of a checkout exports the cell's stage shapes
into `benchmark/.cache/bundle-<platform>/` (a trace of each graph, minutes
for the large ones); every later run loads them in milliseconds. Each
loaded stage is then compiled in a few threads, overlapping the cell's
host set-up: from scratch once a checkout, from the persistent
compilation cache afterwards. The warm-up calls that follow run every
shape once through the program's public entry.

A loaded stage keeps the name of the program's own jit (`jit__h2g2`,
`jit__prepare_pairs`, `jit__miller_product`, `jit__final_check`), which
the per-stage readers find in the profiler's trace.
"""

from __future__ import annotations

import ctypes
import os
import threading
import time

from .harness import DATA_DIR

# A v5e stage compile peaks at 2.6-6.9 GiB of host memory: three at once,
# plus the executables kept, stay inside a 40 GiB host.
COMPILE_THREADS = 3

# The program's jit of each BM stage, in the order of a bundle entry.
STAGE_NAMES = ("_h2g2", "_prepare_pairs", "_miller_product", "_final_check")


def bm_shape(n: int, k: int, n_distinct: int):
    """The one-chip (n, k, m) buckets ops/backend stages a batch of n sets
    of k keys over n_distinct messages at."""
    from lighthouse_tpu.ops import backend as be

    n_b, k_b = be._next_pow2(n), be._next_pow2(k)
    return n_b, k_b, max(be._m_bucket_for(n_b, n_distinct), be.BM_M_FLOOR)


def _named_bundle_class():
    from lighthouse_tpu.serving import aot

    class NamedBundle(aot.WarmBundle):
        """A warm bundle whose stages carry the program's jit names."""

        def __init__(self, path, manifest):
            super().__init__(path, manifest)
            self.names = {}  # stage key -> name of the program's jit

        def _load_stage_uncached(self, key):
            import jax
            from jax import export

            meta = self.manifest.get("stages", {}).get(key)
            if meta is None:
                return None
            with open(os.path.join(self.path, meta["file"]), "rb") as fh:
                exported = export.deserialize(bytearray(fh.read()))

            def stage(*args):
                return exported.call(*args)

            stage.__name__ = self.names.get(key, "stage")
            call = jax.jit(stage)
            call.in_avals = exported.in_avals
            return call

    return NamedBundle


def bundle_jobs(shapes) -> list:
    """Make (once a checkout) and activate the warm bundle of the (n, k, m)
    shapes; (label, jitted stage, avals) for every distinct stage."""
    import jax
    from lighthouse_tpu.serving import aot

    path = os.path.join(DATA_DIR, "bundle-" + jax.default_backend())
    for n, k, m in shapes:
        bundle = aot.open_bundle(path)
        if bundle is None or not bundle.has_core("bm", n, k, m):
            report = aot.make_bundle(path, [(n, k)], layout="bm",
                                     m_menu=[m])
            if report.errors:
                raise RuntimeError(f"warm bundle: {report.errors}")
    bundle = aot.open_bundle(path)
    named = _named_bundle_class()(bundle.path, bundle.manifest)
    jobs = {}
    for n, k, m in shapes:
        for name, key in zip(STAGE_NAMES, named.entries[
                aot.core_key("bm", n, k, m)]["stages"]):
            named.names[key] = name
            if key not in jobs:
                fn = named.load_stage(key)
                jobs[key] = ((name, key[:8]), fn, [
                    jax.ShapeDtypeStruct(a.shape, a.dtype)
                    for a in fn.in_avals])
    aot.set_active_bundle(named)
    # Longest compiles first: the final exponentiation, then prepare.
    order = {"_final_check": 0, "_prepare_pairs": 1}
    return sorted(jobs.values(), key=lambda j: order.get(j[0][0], 2))


def _release_heap() -> None:
    """Hand freed compiler memory back to the OS: glibc keeps ~2 GiB per
    stage compile in its arenas otherwise."""
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except OSError:  # not glibc: nothing to trim
        pass


class CompileAhead:
    """lower().compile() each (label, jit, avals) job in a small thread
    pool. A later call with the same avals reuses the executable.
    `wait()` blocks until every job is done and raises the first compile
    error."""

    def __init__(self, threads: int = COMPILE_THREADS):
        self._threads = max(1, threads)
        self._active = 0
        self._pending = []
        self._lock = threading.Lock()
        self._done = {}
        self._errors = []
        self._workers = []
        self.secs = {}

    def add_shapes(self, shapes) -> dict:
        """Serve the shapes' stages from the warm bundle and compile them
        ahead; the seconds spent making or opening the bundle."""
        t0 = time.perf_counter()
        self.add(bundle_jobs(shapes))
        return {"bundle_s": time.perf_counter() - t0}

    def add(self, jobs) -> None:
        with self._lock:
            for label, fn, avals in jobs:
                if label not in self._done:
                    self._done[label] = threading.Event()
                    self._pending.append((label, fn, avals))
            while self._active < self._threads and self._pending:
                self._active += 1
                t = threading.Thread(target=self._work, daemon=True)
                self._workers.append(t)
                t.start()

    def _work(self) -> None:
        while True:
            with self._lock:
                if not self._pending or self._errors:
                    self._active -= 1
                    return
                label, fn, avals = self._pending.pop(0)
            t0 = time.perf_counter()
            try:
                fn.lower(*avals).compile()
            except Exception as e:  # re-raised by wait() on the main thread
                self._errors.append((label, e))
            else:
                self.secs[str(label)] = round(time.perf_counter() - t0, 1)
            finally:
                _release_heap()
                self._done[label].set()

    def wait(self) -> None:
        for label in list(self._done):
            while not self._done[label].wait(1.0) and not self._errors:
                pass
            if self._errors:
                bad, e = self._errors[0]
                raise RuntimeError(f"compile of {bad} failed") from e
        for t in self._workers:
            t.join()
