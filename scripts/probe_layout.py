"""Chip probe: does a batch-minor (lane-packed) layout beat the current
batch-leading layout for the VPU-bound tower ops?

Round-5 hypothesis (NOTES_TPU_PERF.md roofline): elementwise carry/CRT
work runs on tensors whose two minor dims ((2,48) limb tensors, (4,101)
domain tensors) fill 9-40% of each (8,128) vector tile; putting the
batch axis minor (trailing) fills tiles >95%. Probed WITHOUT a rewrite
by vmapping the existing per-element ops over a trailing axis
(in_axes=-1/out_axes=-1 keeps the batch dim minor through every
elementwise primitive's batching rule).

Measurement discipline per NOTES: chained dependency loop inside ONE
jitted call (lax.scan), forced np.asarray fetch, best-of-3.

Emits one probe-report JSON line (observability/report.py schema) on
stdout; the human-readable table goes to stderr so sweeps can pipe the
schema line straight into a collector.

Usage: python scripts/probe_layout.py [n] [chain]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

from lighthouse_tpu.ops import limbs as lb
from lighthouse_tpu.ops import tower as tw

N = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
CHAIN = int(sys.argv[2]) if len(sys.argv) > 2 else 32


def chain_jit(op, length):
    def body(acc, _):
        return op(acc), None

    @jax.jit
    def run(x):
        y, _ = jax.lax.scan(body, x, None, length=length)
        return y

    return run


def bench(name, fn, x):
    y = fn(x)
    jax.block_until_ready(y)          # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        y = fn(x)
        np.asarray(y).ravel()[:1]     # forced fetch: time the device, not the enqueue
        dt = time.perf_counter() - t0
        best = min(best, dt)
    per = best / CHAIN
    print(f"{name:34s} total {best*1e3:8.2f} ms   {per*1e6:9.1f} us/op",
          file=sys.stderr)
    return per


def main():
    from lighthouse_tpu.observability import report as obs_report

    rep = obs_report.make("probe_layout", {"n": N, "chain": CHAIN})
    print(f"devices: {jax.devices()}  n={N} chain={CHAIN}", file=sys.stderr)
    rng = np.random.default_rng(0)
    # Valid lazy Fp12 inputs: canonical digits (small, within every bound).
    base = rng.integers(0, 256, size=(N, 2, 3, 2, lb.L)).astype(np.float32)

    results = {}

    # --- fp12_sqr: the Miller-loop workhorse --------------------------------
    x_lead = jnp.asarray(base)
    f_lead = chain_jit(tw.fp12_sqr, CHAIN)
    results["sqr/lead"] = bench("fp12_sqr batch-leading", f_lead, x_lead)

    x_tail = jnp.asarray(np.moveaxis(base, 0, -1))      # (2,3,2,L,N)
    op_tail = jax.vmap(tw.fp12_sqr, in_axes=-1, out_axes=-1)
    f_tail = chain_jit(op_tail, CHAIN)
    results["sqr/tail"] = bench("fp12_sqr batch-trailing (vmap)", f_tail, x_tail)

    # Split: leading batch N/128 stays leading (the op is shape-polymorphic
    # over it), 128 lanes ride a vmapped trailing axis -> minor dims (L, 128).
    x_split = jnp.asarray(
        np.moveaxis(base.reshape(N // 128, 128, 2, 3, 2, lb.L), 1, -1)
    )                                                   # (N/128,2,3,2,L,128)
    f_split = chain_jit(op_tail, CHAIN)
    results["sqr/split"] = bench("fp12_sqr split (lead+128 lanes)", f_split,
                                 x_split)

    # --- plain field mul chain (squeeze/fwd/inv/reduce machinery) -----------
    fb = jnp.asarray(base.reshape(N * 12, lb.L))

    def mul_self(v):
        return lb.mul(v, v + 1.0)

    f_mlead = chain_jit(mul_self, CHAIN)
    results["mul/lead"] = bench("fp_mul batch-leading", f_mlead, fb)

    fb_t = jnp.asarray(np.moveaxis(np.asarray(fb), 0, -1))  # (L, m)
    op_mtail = jax.vmap(mul_self, in_axes=-1, out_axes=-1)
    f_mtail = chain_jit(op_mtail, CHAIN)
    results["mul/tail"] = bench("fp_mul batch-trailing (vmap)", f_mtail, fb_t)

    fb_s = jnp.asarray(
        np.moveaxis(np.asarray(fb).reshape(N * 12 // 128, 128, lb.L), 1, -1)
    )
    f_msplit = chain_jit(op_mtail, CHAIN)
    results["mul/split"] = bench("fp_mul split (lead+128 lanes)", f_msplit,
                                 fb_s)

    print(file=sys.stderr)
    speedups = {}
    for k in ("sqr", "mul"):
        lead = results[f"{k}/lead"]
        for v in ("tail", "split"):
            speedups[f"{k}/{v}"] = round(lead / results[f"{k}/{v}"], 3)
            print(f"{k}/{v}: {speedups[f'{k}/{v}']:5.2f}x vs leading",
                  file=sys.stderr)
    obs_report.emit(obs_report.finish(
        rep, ok=True,
        results={"us_per_op": {k: round(v * 1e6, 2)
                               for k, v in results.items()},
                 "speedup_vs_leading": speedups}))


if __name__ == "__main__":
    main()
