"""Background device-shape warming (VERDICT round-2 weak #6).

A fresh node's verification rate is gated by cold XLA compiles: every
(n_bucket, k_bucket) batch shape compiles on first use (minutes per
shape cold), and the AdaptiveBatchPolicy deliberately refuses to jump to
a shape that has never run (beacon_processor/processor.py:78-99) — so
without warming, a node limps at small batches for tens of minutes after
startup.

The ShapeWarmer closes the loop in-client: a low-priority daemon thread
walks the production shape grid smallest-first, compiles+executes each
bucket's three-stage core on synthetic staged tensors (masked-out sets:
the device work is real, the semantics don't matter), and notifies the
batch policy as each shape becomes safe — the batch former's growth cap
rises behind it. With a populated persistent cache each step is a cache
load, so a warm restart reaches full batch size in seconds.

When an AOT warm bundle is active (serving/aot.py, PR 11), each shape
first tries the verify-bundle fast path — deserialize the exported
stages and run each once on zeros — and only falls back to the compile
path on a miss, so a restarted node reaches full batch size in seconds
even without a populated compilation cache.

The reference has no equivalent (CPU blst needs no compilation); the
closest analog is its `warn`-level startup preconditioning of caches.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence, Tuple

import numpy as np

DEFAULT_SHAPE_GRID: Tuple[Tuple[int, int], ...] = (
    (64, 1), (64, 4), (256, 1), (256, 4), (1024, 1), (1024, 4),
    (2048, 1), (2048, 4),
    # Round-5: same-message pair combining caps the pairing stage at the
    # distinct-message count, so throughput keeps rising past the round-4
    # n=2048 knee (NOTES_TPU_PERF.md round-5 table) — warm 4096 too.
    (4096, 4),
    # Round-6: the CHUNKED prep stage (ops/bm/backend.prep_chunk_width)
    # runs these as sequences of resident-working-set ladder passes;
    # _run skips them when chunking is disabled (ops.backend
    # .max_n_bucket — the monolithic graphs spill past 4096).
    (8192, 4), (16384, 4),
)


def tuned_shape_grid(policy: Optional[dict],
                     default: Sequence[Tuple[int, int]] = DEFAULT_SHAPE_GRID,
                     ) -> Tuple[Tuple[int, int], ...]:
    """The warming grid a persisted autotune policy asks for (the
    `warm_grid` facet of serving/autotune's TunedPolicy dict), or
    `default` when the policy is absent/malformed — a restarted node
    warms exactly the shapes its own traffic proved it needs instead of
    the full static grid."""
    if not isinstance(policy, dict):
        return tuple(default)
    grid = policy.get("warm_grid")
    if not isinstance(grid, (list, tuple)) or not grid:
        return tuple(default)
    out = []
    for pair in grid:
        try:
            n, k = pair
            n, k = int(n), int(k)
        except (TypeError, ValueError):
            return tuple(default)
        if n < 2 or k < 1:
            return tuple(default)
        out.append((n, k))
    return tuple(out)


class ShapeWarmer:
    def __init__(
        self,
        policy=None,
        shapes: Sequence[Tuple[int, int]] = DEFAULT_SHAPE_GRID,
        sharded: bool = False,
        bundle: Optional[str] = "auto",
        layout: Optional[str] = None,
    ):
        self.policy = policy
        self.shapes = tuple(shapes)
        self.sharded = sharded
        # AOT warm bundle (serving/aot.py): "auto" resolves the process
        # bundle (LIGHTHOUSE_TPU_WARM_BUNDLE; unset = none), a path opens
        # that directory, None disables the fast path entirely.
        self.bundle = bundle
        self.layout = layout   # None: resolve from the engine selector
        self.warmed: list = []
        self.bundle_warmed: list = []   # shapes served by bundle verify
        self.compiled: list = []        # shapes that paid the compile path
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------ lifecycle

    def start(self) -> "ShapeWarmer":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="shape-warmer")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def join(self, timeout: Optional[float] = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)

    # -------------------------------------------------------------- warming

    def _resolve_bundle(self):
        """Resolve the AOT bundle object once (None = fast path disabled)."""
        if self.bundle is None:
            return None
        try:
            from lighthouse_tpu.serving import aot
        except Exception:
            return None
        if self.bundle == "auto":
            return aot.active_bundle()
        if isinstance(self.bundle, str):
            resolved = aot.open_bundle(self.bundle)
            # Cache the object so later shapes reuse its artifact cache.
            self.bundle = resolved
            return resolved
        return self.bundle  # already a WarmBundle

    def _warm_from_bundle(self, n_bucket: int, k_bucket: int) -> bool:
        """Verify-bundle fast path: load the shape's exported stages and
        run each once on zeros — seconds instead of the minutes-per-shape
        trace+lower cost. False (missing/stale/corrupt) falls back to the
        compile path, so this can never make warming worse."""
        bundle = self._resolve_bundle()
        if bundle is None:
            return False
        from lighthouse_tpu.ops import backend as be

        layout = self.layout or be._layout()
        try:
            return bundle.warm_core(layout, n_bucket, k_bucket,
                                    sharded=self.sharded)
        except Exception:
            return False

    def warm_one(self, n_bucket: int, k_bucket: int) -> None:
        """Warm one bucket shape: bundle verify fast path first, else
        compile + execute on masked synthetic tensors (whichever engine
        the layout selector routes this process to)."""
        from lighthouse_tpu.observability import compile_events, trace

        with trace.span("warm_one", cat="warming",
                        n=n_bucket, k=k_bucket):
            if self._warm_from_bundle(n_bucket, k_bucket):
                self.bundle_warmed.append((n_bucket, k_bucket))
                return
            self.compiled.append((n_bucket, k_bucket))
            compile_events.record("warm_compile_path",
                                  n=n_bucket, k=k_bucket)
            self._warm_compile(n_bucket, k_bucket)

    def _warm_compile(self, n_bucket: int, k_bucket: int) -> None:
        """The compile path (trace + lower + execute; persistent-cache
        assisted). Separate from warm_one so tests can stub it."""
        import jax.numpy as jnp

        from lighthouse_tpu.ops import backend as be
        from lighthouse_tpu.ops import curves as cv
        from lighthouse_tpu.ops import limbs as lb

        if be._layout() == "bm":
            self._warm_one_bm(n_bucket, k_bucket)
            return

        u = jnp.zeros((n_bucket, 2, 2, lb.L), dtype=lb.DTYPE)
        inv_idx = jnp.arange(n_bucket, dtype=jnp.int32)  # all-distinct shape
        pk_proj = jnp.broadcast_to(
            cv.G1.infinity, (n_bucket, k_bucket, 3, lb.L)
        )
        sig_proj = jnp.broadcast_to(cv.G2.infinity, (n_bucket, 3, 2, lb.L))
        sig_checked = jnp.ones((n_bucket,), dtype=bool)
        set_mask = jnp.zeros((n_bucket,), dtype=bool)   # all padding
        scalars = jnp.asarray(np.ones((n_bucket,), dtype=np.uint64))
        core = be._jitted_core(n_bucket, k_bucket, self.sharded)
        core(u, inv_idx, pk_proj, sig_proj, sig_checked, set_mask, scalars)
        # Also warm a hash-consed h2c shape (committee-repeated messages
        # collapse u to ~n/256 distinct rows in the gossip firehose). The
        # fresh jit here still populates the shared persistent cache.
        m_small = max(1, n_bucket // 256)
        if m_small < n_bucket:
            import jax

            u_s = jnp.zeros((m_small, 2, 2, lb.L), dtype=lb.DTYPE)
            jax.jit(be._h2g2_gather)(
                u_s, jnp.zeros((n_bucket,), dtype=jnp.int32)
            )

    def _warm_one_bm(self, n_bucket: int, k_bucket: int) -> None:
        """Batch-minor twin of warm_one: every m bucket of the quantized
        menu, sharded over the mesh when the warmer is (the round-6
        sharded path runs the BM engine too)."""
        import jax
        import jax.numpy as jnp

        from lighthouse_tpu.ops.bm import backend as bmb
        from lighthouse_tpu.ops.bm import curves as bmc
        from lighthouse_tpu.ops.bm import limbs as lb

        n_devices = len(jax.devices()) if self.sharded else None

        inv_idx = jnp.arange(n_bucket, dtype=jnp.int32)
        pk_proj = jnp.broadcast_to(
            bmc.G1.infinity, (k_bucket, 3, lb.L, n_bucket)
        )
        sig_proj = jnp.broadcast_to(bmc.G2.infinity, (3, 2, lb.L, n_bucket))
        sig_checked = jnp.ones((n_bucket,), dtype=bool)
        set_mask = jnp.zeros((n_bucket,), dtype=bool)   # all padding
        scalars = jnp.asarray(np.ones((n_bucket,), dtype=np.uint64))
        # Every m bucket of the quantized menu (M_BUCKET_SHIFTS — the
        # SAME constant production staging quantizes with, so the warmer
        # cannot desync from the menu): a batch whose distinct-message
        # count lands on an unwarmed step would stall a slot third on
        # the ~2-minute trace+lower cost. The warmer is a background
        # daemon; the duplicate-free set below is len(menu) entries.
        from lighthouse_tpu.ops.backend import bm_m_menu

        for m_bucket in bm_m_menu(n_bucket, n_devices or 1):
            u = jnp.zeros((2, 2, lb.L, m_bucket), dtype=lb.DTYPE)
            row_mask = jnp.zeros((m_bucket,), dtype=bool)
            args = (u, inv_idx % m_bucket, row_mask, pk_proj, sig_proj,
                    sig_checked, set_mask, scalars)
            if self.sharded:
                from lighthouse_tpu.parallel import mesh as pm

                mesh = pm.get_mesh(n_devices)
                args = tuple(pm.shard_batch_minor(a, mesh) for a in args)
            core = bmb.jitted_core(n_bucket, k_bucket, m_bucket,
                                   sharded=self.sharded,
                                   n_devices=n_devices)
            core(*args)

    def _run(self) -> None:
        try:
            # Warming is where compiles happen: make sure the provenance
            # hooks (persistent-cache hit/miss, compile durations) are
            # live before the first shape.
            from lighthouse_tpu.observability import compile_events

            compile_events.install()
        except Exception:
            pass
        try:
            from lighthouse_tpu.ops.backend import max_n_bucket

            n_cap = max_n_bucket()
        except Exception:
            n_cap = None
        for n_bucket, k_bucket in self.shapes:
            if self._stop.is_set():
                return
            if n_cap is not None and n_bucket > n_cap:
                continue  # 8192/16384 rungs are gated on chunked prep
            try:
                self.warm_one(n_bucket, k_bucket)
            except Exception:
                continue  # best-effort: a failed shape warms on first use
            self.warmed.append((n_bucket, k_bucket))
            if self.policy is not None:
                self.policy.note_ran(n_bucket)
