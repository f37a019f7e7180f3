#!/usr/bin/env python
"""Populate the persistent JAX compilation cache for the verification
pipeline's production shapes.

Run after kernel changes (each shape compiles once here, then every later
process — pytest, the driver's dryrun, bench — loads it instantly):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python scripts/warm_cache.py

The cache lands in JAX_COMPILATION_CACHE_DIR when it is set, otherwise in
the checkout's `.jax_cache/` (lighthouse_tpu/ops/__init__.py) — the same
place every later process reads.

pytest itself runs with cache WRITES disabled (see tests/conftest.py):
XLA:CPU executable serialization is flaky in long many-module processes,
so only short dedicated runs like this one write entries.
"""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# NB: the cache-write CAP (LIGHTHOUSE_TPU_JAX_CACHE_MAX_COMPILE_SECS, 400 s)
# stays at its default here: serializing the very largest executables
# segfaults XLA:CPU even in this short dedicated process (observed on the
# device-KZG graph repeatedly). Entries above the cap compile where used.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import __graft_entry__ as g

    t0 = time.time()
    fn, args = g.entry()
    assert bool(fn(*args))
    print(f"entry shapes warm ({time.time() - t0:.0f}s)")

    t1 = time.time()
    g.dryrun_multichip(8)
    print(f"sharded dryrun shapes warm ({time.time() - t1:.0f}s)")

    # Unit-test shapes that otherwise compile INSIDE pytest every run.
    # The pairing-suite pair-batch of 4 has repeatedly segfaulted XLA:CPU
    # when compiled in a long many-module pytest process; compiled here in
    # a short process it caches fine and pytest only loads it.
    t2 = time.time()
    import jax

    from lighthouse_tpu.crypto.bls import curves as oc
    from lighthouse_tpu.crypto.bls import hash_to_curve as oh2c
    from lighthouse_tpu.ops import limbs as lb
    from lighthouse_tpu.ops import pairing as pr

    sk = 0x1234567890ABCDEF
    h = oh2c.hash_to_g2(b"\x42" * 32)
    sig = oc.g2_mul(h, sk)
    pk = oc.g1_mul(oc.G1_GEN, sk)

    def stage_g1(pts):
        flat = []
        for x, y in pts:
            flat.extend([x, y])
        return lb.ints_to_mont(flat).reshape(-1, 2, lb.L)

    def stage_g2(pts):
        flat = []
        for (x0, x1), (y0, y1) in pts:
            flat.extend([x0, x1, y0, y1])
        return lb.ints_to_mont(flat).reshape(-1, 2, 2, lb.L)

    import jax.numpy as jnp

    p4 = stage_g1([pk, oc.g1_neg(oc.G1_GEN), oc.G1_GEN, oc.G1_GEN])
    q4 = stage_g2([h, sig, oc.G2_GEN, oc.G2_GEN])
    mask = jnp.asarray([True, True, False, False])
    assert bool(jax.jit(pr.multi_pairing_is_one)(p4, q4, mask))
    jax.jit(pr.miller_loop)(p4, q4).block_until_ready()
    jax.jit(pr.final_exponentiation)(
        jax.jit(pr.miller_loop)(p4, q4)[0]
    ).block_until_ready()
    print(f"pairing-suite shapes warm ({time.time() - t2:.0f}s)")

    # h2c-suite shapes (tests/test_ops_h2c.py batch of 4).
    t2b = time.time()
    from lighthouse_tpu.ops import h2c as _h2c

    msgs4 = [bytes([i]) * 32 for i in range(4)]
    u4 = _h2c.hash_to_field_device(msgs4)
    jax.jit(_h2c.hash_to_g2_device)(u4).block_until_ready()
    jax.jit(_h2c.map_to_curve_sswu_projective)(u4)[0].block_until_ready()
    print(f"h2c-suite shapes warm ({time.time() - t2b:.0f}s)")

    # Remaining tier-1 bucket shapes: every (n, k[, m]) core a test
    # compiles that the entry/dryrun warms above don't cover. Each is a
    # fresh set of persistent-cache entries (cache keys include shapes),
    # and a cold stage compile is minutes on a 1-core host — warming them
    # here is what keeps the suite inside its budget on a fresh box.
    t3 = time.time()
    import numpy as np

    from lighthouse_tpu.ops import backend as be
    from lighthouse_tpu.ops import curves as cv

    def warm_major(n_bucket, k_bucket, sharded=False, m_bucket=None):
        m = m_bucket or n_bucket
        u = jnp.zeros((m, 2, 2, lb.L), dtype=lb.DTYPE)
        inv_idx = jnp.asarray(np.arange(n_bucket, dtype=np.int32) % m)
        pk = jnp.broadcast_to(cv.G1.infinity, (n_bucket, k_bucket, 3, lb.L))
        sg = jnp.broadcast_to(cv.G2.infinity, (n_bucket, 3, 2, lb.L))
        chk = jnp.ones((n_bucket,), dtype=bool)
        mask = jnp.zeros((n_bucket,), dtype=bool)
        sc = jnp.asarray(np.ones((n_bucket,), dtype=np.uint64))
        args = (u, inv_idx, pk, sg, chk, mask, sc)
        if sharded:
            from lighthouse_tpu.parallel import mesh as pm

            sh = pm.batch_sharding(pm.get_mesh())
            args = tuple(jax.device_put(a, sh) for a in args)
        core = be._jitted_core(n_bucket, k_bucket, sharded)
        jax.block_until_ready(core(*args))

    # test_backend.py unsharded (4, 2); sharded (8, 1) + (16, 4) (the
    # find_invalid_sets bisection stays at its root's shape);
    # beacon-processor warm_one (2, 1); firehose buckets (<=8, k=1).
    for shape in [(4, 2, False), (2, 1, False), (8, 1, False),
                  (8, 1, True), (16, 4, True)]:
        warm_major(*shape)
    print(f"tier-1 major bucket shapes warm ({time.time() - t3:.0f}s)")

    # Batch-minor tier-1 shapes (tests/test_ops_bm.py, test_sharded_bm
    # .py): the (8, 2, m=8) core, its round-6 chunked-prep twin
    # (prep_chunk=4), and the sharded BM core at the dryrun shape
    # (n=16, k=4, m=16 — the m bucket floors at the 8-device mesh).
    t4 = time.time()
    from lighthouse_tpu.ops.bm import backend as bmb
    from lighthouse_tpu.ops.bm import curves as bmc
    from lighthouse_tpu.ops.bm import limbs as bml
    from lighthouse_tpu.parallel import mesh as pm

    def warm_bm(n_bucket, k_bucket, m_bucket, prep_chunk=None,
                sharded=False):
        u = jnp.zeros((2, 2, bml.L, m_bucket), dtype=bml.DTYPE)
        inv_idx = jnp.asarray(
            np.arange(n_bucket, dtype=np.int32) % m_bucket
        )
        row_mask = jnp.zeros((m_bucket,), dtype=bool)
        pk = jnp.broadcast_to(bmc.G1.infinity, (k_bucket, 3, bml.L, n_bucket))
        sg = jnp.broadcast_to(bmc.G2.infinity, (3, 2, bml.L, n_bucket))
        chk = jnp.ones((n_bucket,), dtype=bool)
        mask = jnp.zeros((n_bucket,), dtype=bool)
        sc = jnp.asarray(np.ones((n_bucket,), dtype=np.uint64))
        args = (u, inv_idx, row_mask, pk, sg, chk, mask, sc)
        n_devices = None
        if sharded:
            n_devices = jax.device_count()
            mesh = pm.get_mesh(n_devices)
            args = tuple(pm.shard_batch_minor(a, mesh) for a in args)
        core = bmb.jitted_core(n_bucket, k_bucket, m_bucket,
                               prep_chunk=prep_chunk, sharded=sharded,
                               n_devices=n_devices)
        jax.block_until_ready(core(*args))

    warm_bm(8, 2, 8, prep_chunk=0)
    warm_bm(8, 2, 8, prep_chunk=4)       # round-6 chunked differential
    warm_bm(16, 4, 16, sharded=True)     # round-6 sharded BM dryrun
    print(f"tier-1 bm bucket shapes warm ({time.time() - t4:.0f}s)")

    # NOTE: the device-KZG graph and the bench shape are deliberately NOT
    # warmed here — their XLA:CPU compiles have repeatedly died in this
    # process (huge-executable serialization segfaults / LLVM mmap
    # exhaustion). pytest compiles the KZG graph read-only; the bench's
    # TPU executable is cached by the TPU runs themselves.



if __name__ == "__main__":
    main()
