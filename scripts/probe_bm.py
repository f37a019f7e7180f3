"""Chip A/B: batch-minor engine (ops/bm/) vs the batch-major engine.

Usage: python scripts/probe_bm.py [micro|stages|e2e|chunk|all] [n ...]

  micro  — dependency-chained fp2_mul / fp12_sqr loops in both layouts
           (the tile-utilization claim, measured directly).
  stages — the three verify stages on synthetic staged tensors at
           (n, k=4), both layouts.
  e2e    — pipelined verify_signature_sets_tpu_async throughput with
           LIGHTHOUSE_TPU_LAYOUT toggled (real sets, real staging).
  chunk  — prep-stage A/B at (n, k=4): monolithic ladder vs the round-6
           chunked ladder passes (lax.scan over fixed-width slabs); run
           with n 8192 16384 on a chip to size the new bucket rungs.
           ("chunk" is not in "all": the monolithic 8192 graph can spill
           hard enough to OOM a small chip — run it deliberately.)

Measurement discipline per NOTES_TPU_PERF.md: chained dependencies with a
forced np.asarray fetch, best-of-3, so no run is served from a cache
and no timing stops before the device does.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _timed(fn, reps=3):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def micro(sizes):
    """Dependency-chained micro A/B: each timed call feeds the previous
    output back in (values keep evolving, so no run can be served from
    a cache) and forces a full fetch at the end."""
    out = []
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops import limbs as lb
    from lighthouse_tpu.ops import tower as tw
    from lighthouse_tpu.ops.bm import tower as btw

    CHAIN = 8

    def run(name, f, x, ops_per_call):
        x = f(x)
        jax.block_until_ready(x)            # compile + warm
        best = float("inf")
        for _ in range(3):
            x = f(x)                         # evolve between timings
            np.asarray(x)
            t0 = time.perf_counter()
            y = f(x)
            np.asarray(y)
            best = min(best, time.perf_counter() - t0)
            x = y
        print(f"  {name}: {best*1e3:8.2f} ms  "
              f"({ops_per_call / best / 1e3:9.1f} kops/s)")
        return best

    for n in sizes:
        print(f"micro n={n}")
        rng = np.random.default_rng(0)
        digits = rng.integers(0, 256, size=(n, 2, lb.L)).astype(np.float32)
        a_maj = jnp.asarray(digits)
        a_bm = jnp.asarray(np.moveaxis(digits, 0, -1))
        t1 = run("fp2_mul  major",
                 jax.jit(lambda x: _chain(tw.fp2_mul, x, CHAIN)), a_maj,
                 n * CHAIN)
        t2 = run("fp2_mul  bm   ",
                 jax.jit(lambda x: _chain(btw.fp2_mul, x, CHAIN)), a_bm,
                 n * CHAIN)
        print(f"  fp2_mul speedup: {t1 / t2:.2f}x")
        out.append({"op": "fp2_mul", "n": n, "major_s": t1, "bm_s": t2,
                    "speedup": t1 / t2})

        d12 = rng.integers(0, 256, size=(n, 2, 3, 2, lb.L)).astype(np.float32)
        f_maj = jnp.asarray(d12)
        f_bm = jnp.asarray(np.moveaxis(d12, 0, -1))
        t1 = run("fp12_sqr major",
                 jax.jit(lambda x: _chain1(tw.fp12_sqr, x, CHAIN)), f_maj,
                 n * CHAIN)
        t2 = run("fp12_sqr bm   ",
                 jax.jit(lambda x: _chain1(btw.fp12_sqr, x, CHAIN)), f_bm,
                 n * CHAIN)
        print(f"  fp12_sqr speedup: {t1 / t2:.2f}x")
        out.append({"op": "fp12_sqr", "n": n, "major_s": t1, "bm_s": t2,
                    "speedup": t1 / t2})
    return out


def _chain(op, x, k):
    for _ in range(k):
        x = op(x, x)
    return x


def _chain1(op, x, k):
    for _ in range(k):
        x = op(x)
    return x


def stages(sizes):
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops import backend as be
    from lighthouse_tpu.ops import curves as cv
    from lighthouse_tpu.ops import limbs as lb
    from lighthouse_tpu.ops.bm import backend as bmb
    from lighthouse_tpu.ops.bm import curves as bmc

    out = []
    k = 4
    for n in sizes:
        print(f"stages n={n} k={k}")
        # --- major
        u = jnp.zeros((n, 2, 2, lb.L), dtype=lb.DTYPE)
        inv_idx = jnp.arange(n, dtype=jnp.int32)
        pk = jnp.broadcast_to(cv.G1.infinity, (n, k, 3, lb.L))
        sig = jnp.broadcast_to(cv.G2.infinity, (n, 3, 2, lb.L))
        chk = jnp.ones((n,), dtype=bool)
        mask = jnp.ones((n,), dtype=bool)
        sc = jnp.asarray(np.arange(1, n + 1, dtype=np.uint64))
        core = be._jitted_core(n, k, False)
        args = (u, inv_idx, pk, sig, chk, mask, sc)
        jax.block_until_ready(core(*args))
        t_maj = _timed(lambda: bool(core(*args)))
        print(f"  major total: {t_maj:.3f}s -> {n / t_maj:8.1f} sigs/s")

        # --- bm (all-distinct messages: m_bucket = n)
        u_bm = jnp.zeros((2, 2, lb.L, n), dtype=lb.DTYPE)
        pk_bm = jnp.broadcast_to(bmc.G1.infinity, (k, 3, lb.L, n))
        sig_bm = jnp.broadcast_to(bmc.G2.infinity, (3, 2, lb.L, n))
        row_mask = jnp.ones((n,), dtype=bool)
        core_bm = bmb.jitted_core(n, k, n)
        args_bm = (u_bm, inv_idx, row_mask, pk_bm, sig_bm, chk, mask, sc)
        jax.block_until_ready(core_bm(*args_bm))
        t_bm = _timed(lambda: bool(core_bm(*args_bm)))
        print(f"  bm    total: {t_bm:.3f}s -> {n / t_bm:8.1f} sigs/s "
              f"({t_maj / t_bm:.2f}x)")
        out.append({"n": n, "k": k, "major_s": t_maj, "bm_s": t_bm,
                    "major_sigs_s": n / t_maj, "bm_sigs_s": n / t_bm,
                    "speedup": t_maj / t_bm})
    return out


def chunk(sizes):
    """Prep-chunk A/B: stage-2 (the ladder stage chunking targets) and
    whole-core timings at (n, k=4, all-distinct m), monolithic
    (prep_chunk=0) vs the resolved chunk width. Bit-exactness is pinned
    in tests/test_ops_bm.py; this measures the spill-vs-scan tradeoff."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops.bm import backend as bmb
    from lighthouse_tpu.ops.bm import curves as bmc
    from lighthouse_tpu.ops.bm import limbs as lb

    out = []
    k = 4
    for n in sizes:
        width = bmb.prep_chunk_width(n)
        print(f"chunk n={n} k={k} (resolved width {width or 'monolithic'})")
        u = jnp.zeros((2, 2, lb.L, n), dtype=lb.DTYPE)
        inv_idx = jnp.arange(n, dtype=jnp.int32)
        row_mask = jnp.ones((n,), dtype=bool)
        pk = jnp.broadcast_to(bmc.G1.infinity, (k, 3, lb.L, n))
        sig = jnp.broadcast_to(bmc.G2.infinity, (3, 2, lb.L, n))
        chk = jnp.ones((n,), dtype=bool)
        mask = jnp.ones((n,), dtype=bool)
        sc = jnp.asarray(np.arange(1, n + 1, dtype=np.uint64))
        args = (u, inv_idx, row_mask, pk, sig, chk, mask, sc)
        times = {}
        for w in dict.fromkeys((0, width)):        # dedupe, keep order
            name = f"prep_chunk={w}"
            try:
                core = bmb.jitted_core(n, k, n, prep_chunk=w)
                stage2 = core.stages[1]
                s2_args = (pk, sig, chk, mask, sc, inv_idx)
                jax.block_until_ready(stage2(*s2_args))  # compile + warm
                t2 = _timed(lambda: jax.block_until_ready(
                    stage2(*s2_args)))
                jax.block_until_ready(core(*args))
                tt = _timed(lambda: bool(core(*args)))
                times[w] = tt
                print(f"  {name:16s}: stage2 {t2:.3f}s, total {tt:.3f}s "
                      f"-> {n / tt:8.1f} sigs/s")
            except Exception as e:                 # monolithic may OOM
                print(f"  {name:16s}: FAILED ({type(e).__name__}: "
                      f"{str(e)[:80]})")
        if len(times) == 2:
            print(f"  chunked speedup: {times[0] / times[width]:.2f}x")
        out.append({"n": n, "k": k, "width": width,
                    "total_s": {str(w): t for w, t in times.items()}})
    return out


def e2e(sizes):
    import jax

    from lighthouse_tpu.ops import backend as be
    import __graft_entry__ as ge

    out = []
    os.environ["LIGHTHOUSE_TPU_CPU_FALLBACK_MAX"] = "0"
    for n in sizes:
        base = ge._example_sets(64, keys_per_set=4)
        sets = (base * ((n + 63) // 64))[:n]
        for layout in ("major", "bm"):
            os.environ["LIGHTHOUSE_TPU_LAYOUT"] = layout
            ok = be.verify_signature_sets_tpu(sets, sharded=False)
            if not ok:
                print(f"  e2e n={n} {layout}: FAILED VERIFY")
                out.append({"n": n, "layout": layout, "ok": False})
                continue
            iters = 0
            pending = []
            t0 = time.perf_counter()
            while iters < 3 or time.perf_counter() - t0 < 2.0:
                pending.append(
                    be.verify_signature_sets_tpu_async(sets, sharded=False)
                )
                iters += 1
                if iters >= 30:
                    break
            assert all(bool(p) for p in pending)
            dt = time.perf_counter() - t0
            print(f"  e2e n={n} {layout}: {n * iters / dt:8.1f} sigs/s "
                  f"({iters} iters)")
            out.append({"n": n, "layout": layout, "ok": True,
                        "iters": iters, "sigs_s": n * iters / dt})
    return out


def main():
    from lighthouse_tpu.observability import report

    mode = sys.argv[1] if len(sys.argv) > 1 else "all"
    sizes = [int(a) for a in sys.argv[2:]] or [1024]
    import jax
    print(f"devices: {jax.devices()}", file=sys.stderr)
    rep = report.make("probe_bm", params={"mode": mode, "sizes": sizes})
    results = {}
    if mode in ("micro", "all"):
        results["micro"] = micro(sizes)
    if mode in ("stages", "all"):
        results["stages"] = stages(sizes)
    if mode == "chunk":
        results["chunk"] = chunk(sizes)
    if mode in ("e2e", "all"):
        results["e2e"] = e2e(sizes)
    ok = all(row.get("ok", True)
             for rows in results.values() for row in rows)
    report.emit(report.finish(rep, ok=ok, results=results))


if __name__ == "__main__":
    main()
