#!/usr/bin/env python
"""Chip smoke: the node's BLS verification path, once, on one TPU chip.

    python chip_smoke.py               # one chip (what the driver runs)
    python chip_smoke.py --four-chip   # the sharded core on a 4-chip host

Phase A  device: a TPU, or exit non-zero with the reason. No CPU fallback.
Phase B  one mainnet gossip slot at 500,000 validators (BASELINE.json
         eval config #4): every member of the slot's 64 committees signs
         one single-bit attestation (~15.6k, 64 distinct AttestationData),
         fed through the node's own BeaconProcessor with its default
         AdaptiveBatchPolicy() into a chain whose BLS backend is "tpu"
         (client/builder.py). Every attestation must import. Then the same
         slot on a fresh chain with a few attestations re-signed over the
         wrong root, spread across batches: exactly those must be rejected
         (poisoned-batch bisection on the device).
Phase C  block-import / range-sync shape: n=4096 sets of k=4 keys, 4096
         distinct messages, signatures unchecked as they come off the wire.
         verify_signature_sets(backend="tpu") must agree with the native
         verifier, valid and with one poisoned set, and find_invalid_sets
         must name the poisoned index.

Before the phases, every stage shape they will run is compiled at once in
a few threads (what a node's shape warmer does at start-up), so the phases
themselves run no compile. Lines before the last are diagnostics, not a
benchmark. The last line of stdout is the result:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
Any failed check raises, so the script exits non-zero without it.
"""

import argparse
import ctypes
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

N_VALIDATORS = 500_000
PER_COMMITTEE = 256
N_POISON_ATTS = 4
C_SETS, C_KEYS = 4096, 4
SEED = 21
# A v5e stage compile peaks at 2.6-6.9 GiB of host memory (my CPU run,
# PR 21): three at once, plus the executables kept, stay inside the chip
# machine's 40 GiB.
COMPILE_THREADS = 3


def diag(**kw) -> None:
    print("diag: " + json.dumps(kw, default=str), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# Phase A: the device
# ---------------------------------------------------------------------------


def phase_a(n_chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{d.platform!r}); refusing to run")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: {n_chips} chips asked, "
                         f"{len(devs)} found")
    print(f"device: {d.device_kind} x{len(devs)}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# Stage shapes and their compile-ahead
# ---------------------------------------------------------------------------


def _stage_avals(n: int, k: int, m: int, sharding=None):
    """ShapeDtypeStructs of the three BM stages' inputs at (n, k, m), as
    ops/backend.stage_bm stages them; `sharding(ndim)` places each."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops.bm import limbs as lb

    def sds(shape, dtype):
        sh = sharding(len(shape)) if sharding and shape else None
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    f = lb.DTYPE
    s1 = (sds((2, 2, lb.L, m), f),)
    s2 = (sds((k, 3, lb.L, n), f), sds((3, 2, lb.L, n), f),
          sds((n,), jnp.bool_), sds((n,), jnp.bool_),
          sds((n,), jnp.uint64), sds((n,), jnp.int32))
    s3 = (sds((3, lb.L, m + 1), f), sds((3, 2, lb.L, m), f),
          sds((3, 2, lb.L, 1), f), sds((m,), jnp.bool_))
    return s1, s2, s3


def stage_jobs(shapes):
    """(label, jit, avals) for every distinct unsharded BM stage the
    (n, k, m) shapes run — the exact jit objects the engine dispatches
    (h2g2 and Miller are keyed by m alone, the final exponentiation by
    nothing)."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops.bm import backend as bmb

    jobs = {("final_exp",): (bmb._stage4_jit, (
        jax.ShapeDtypeStruct(bmb.FP12_SHAPE, bmb.lb.DTYPE),
        jax.ShapeDtypeStruct((), jnp.bool_)))}
    for n, k, m in shapes:
        s1, s2, s3 = _stage_avals(n, k, m)
        chunk = bmb.prep_chunk_width(n)
        jobs[("pairing", m)] = (bmb._stage3_jit, s3)
        jobs[("prepare", n, k, m, chunk)] = (bmb._prepare_jit(m, chunk), s2)
        jobs[("h2g2", m)] = (bmb._stage1_jit, s1)
    # Longest compiles first: the final exponentiation (~5 min in the chip
    # machine's pool), then prepare and h2g2 by width, the Miller stages.
    order = {"final_exp": 0, "prepare": 1, "h2g2": 2, "pairing": 3}
    return sorted(((lbl, fn, av) for lbl, (fn, av) in jobs.items()),
                  key=lambda j: (order[j[0][0]], -max(j[0][1:], default=0)))


def _release_heap() -> None:
    """Hand freed compiler memory back to the OS. glibc keeps it in its
    arenas (~2 GiB per stage compile, my CPU run, PR 21), and a dozen
    compiles then held 38.6 GiB of the chip machine's 40 GiB."""
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except OSError:  # not glibc: nothing to trim
        pass


class CompileAhead:
    """lower().compile() each (label, jit, avals) job in a small thread
    pool, in the order added. A later call with the same avals reuses the
    executable (no re-trace, no compile). `wait(labels)` blocks until
    those jobs are compiled and raises the first compile error, so a
    phase can start while the next phase's shapes still compile."""

    def __init__(self, threads: int = COMPILE_THREADS):
        self._threads = max(1, threads)
        self._active = 0
        self._pending = []
        self._lock = threading.Lock()
        self._done = {}
        self._errors = []
        self.secs = {}

    def add(self, jobs) -> None:
        """Queue jobs (a label already queued is skipped) and start
        workers up to the pool size."""
        with self._lock:
            for label, fn, avals in jobs:
                if label not in self._done:
                    self._done[label] = threading.Event()
                    self._pending.append((label, fn, avals))
            while self._active < self._threads and self._pending:
                self._active += 1
                threading.Thread(target=self._work, daemon=True).start()

    def labels(self) -> list:
        return [str(label) for label in self._done]

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

    def wait(self, labels=None) -> None:
        for label in self._done if labels is None else labels:
            while not self._done[label].wait(1.0) and not self._errors:
                pass
            if self._errors:
                bad, e = self._errors[0]
                raise RuntimeError(f"compile of {bad} failed") from e


def _compiles() -> int:
    from lighthouse_tpu.observability import compile_events

    return int(compile_events.counts()["first_compile"]
               + compile_events.counts()["persistent_cache_hit"])


def _host_peak_rss_gib() -> float:
    """This process's peak resident memory (Linux): the compile-ahead
    threads are what bound COMPILE_THREADS on a 40 GiB machine."""
    import resource

    return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20, 2)


def _device_batches() -> int:
    """Batches the device engine answered so far:
    `bls_batches_total{route="device"}` (BM on a chip, batch-major on the
    CPU rehearsal)."""
    from lighthouse_tpu.ops import backend as be

    return int(be.batches_total().get("device"))


# ---------------------------------------------------------------------------
# Phase B: one gossip slot at 500k validators through the node's processor
# ---------------------------------------------------------------------------


def slot_attestations(n_validators: int, per_committee: int, spec=None):
    from lighthouse_tpu.testing.firehose import (
        build_firehose_chain,
        make_signed_single_bit_attestations,
    )

    harness = build_firehose_chain(n_validators, spec=spec)
    harness.chain.slot_clock.set_slot(1)
    return harness, make_signed_single_bit_attestations(
        harness, 1, per_committee=per_committee)


def planned_batches(atts) -> list:
    """(n_sets, n_distinct_messages) of each batch the node's default
    batch former will cut from this queue (the AdaptiveBatchPolicy
    arithmetic of BeaconProcessor._pop_next/step)."""
    from lighthouse_tpu.beacon_processor import AdaptiveBatchPolicy

    policy = AdaptiveBatchPolicy()
    out, lo = [], 0
    while lo < len(atts):
        depth = len(atts) - lo
        n = min(policy.batch_limit(depth), depth) if depth >= 2 else 1
        if n > 1:
            policy.note_ran(n)
        out.append((n, len({id(a.data) for a in atts[lo:lo + n]})))
        lo += n
    return out


def device_shapes(batches, k: int) -> set:
    """The (n_bucket, k_bucket, m_bucket) a list of (n, distinct) batches
    stages at on one chip (small batches answer natively)."""
    from lighthouse_tpu.ops import backend as be

    shapes = set()
    for n, n_uniq in batches:
        if n <= be.cpu_fallback_max():
            continue
        n_b = be._next_pow2(n)
        shapes.add((n_b, be._next_pow2(k),
                    max(be._m_bucket_for(n_b, n_uniq), be.BM_M_FLOOR)))
    return shapes


def poison(harness, atts, n_bad: int) -> list:
    """Re-sign `n_bad` attestations, spread evenly over the queue, over a
    wrong root; returns their positions."""
    import copy

    sk0 = harness.keys[0]
    bad_sig = sk0.sign(b"\x0b" * 32).to_bytes()
    where = sorted({len(atts) * (2 * i + 1) // (2 * n_bad)
                    for i in range(n_bad)})
    for i in where:
        att = copy.copy(atts[i])
        att.signature = bad_sig
        atts[i] = att
    return where


def phase_b(prepared, n_poison: int = N_POISON_ATTS) -> dict:
    """The valid slot, then the poisoned one: `prepared` holds two
    (harness, attestations) pairs of the same slot on separate chains."""
    from lighthouse_tpu.ops.backend import cpu_fallback_max
    from lighthouse_tpu.testing.firehose import run_firehose

    (h_ok, atts), (h_bad, atts_bad) = prepared
    out = {}

    t0 = time.perf_counter()
    runs0 = _device_batches()
    st = run_firehose(h_ok, atts)
    check(st["imported"] == len(atts) and not st["rejected"],
          f"valid slot: imported {st['imported']}/{len(atts)}")
    device_batches = sum(1 for b in st["batch_sizes"]
                         if b > cpu_fallback_max())
    check(_device_batches() - runs0 >= device_batches,
          "valid slot: batches did not run on the device engine")
    out["valid"] = {"n_atts": len(atts), "imported": st["imported"],
                    "batch_sizes": st["batch_sizes"],
                    "device_batches": device_batches,
                    "wall_s": round(time.perf_counter() - t0, 2)}

    t0 = time.perf_counter()
    bad = poison(h_bad, atts_bad, n_poison)
    st = run_firehose(h_bad, atts_bad)
    check(st["rejected"] == sorted(bad),
          f"poisoned slot: rejected {st['rejected']}, poisoned {bad}")
    check(st["imported"] == len(atts_bad) - len(bad),
          "poisoned slot: a valid attestation was not imported")
    spread = {st["batch_of"][i] for i in bad}
    check(len(spread) > 1 or n_poison == 1,
          "poisoned attestations all fell in one batch")
    out["poisoned"] = {"poisoned": bad, "rejected": st["rejected"],
                       "batches_hit": sorted(spread),
                       "batch_sizes": st["batch_sizes"],
                       "wall_s": round(time.perf_counter() - t0, 2)}
    return out


# ---------------------------------------------------------------------------
# Phase C: n sets x k keys, all messages distinct, unchecked signatures
# ---------------------------------------------------------------------------


def distinct_sets(n: int, k: int, seed: int = SEED) -> list:
    """Each set signed once with the sum of its k secret keys mod r (the
    same aggregate signature k signers would produce). H(m) comes from the
    native hash-to-curve (35x the oracle's speed); the device runs its
    own h2c, so a native fault would show as a disagreement."""
    import random

    from lighthouse_tpu.crypto.bls import api
    from lighthouse_tpu.crypto.bls import curves as oc
    from lighthouse_tpu.crypto.bls.constants import R
    from lighthouse_tpu.crypto.bls.cpu_backend import hash_to_g2_native

    rng = random.Random(seed)
    sets = []
    for i in range(n):
        sks = [rng.randrange(1, R) for _ in range(k)]
        msg = rng.randbytes(28) + i.to_bytes(4, "big")
        sig = oc.g2_mul(hash_to_g2_native(msg), sum(sks) % R)
        sets.append(api.SignatureSet(
            signature=api.Signature(point=sig, subgroup_checked=False),
            signing_keys=[api.SecretKey(sk).public_key() for sk in sks],
            message=msg,
        ))
    return sets


def poisoned_copy(sets, idx: int) -> list:
    """sets with set `idx` carrying set idx+1's signature."""
    from lighthouse_tpu.crypto.bls import api

    out = list(sets)
    out[idx] = api.SignatureSet(
        signature=sets[idx + 1].signature,
        signing_keys=sets[idx].signing_keys, message=sets[idx].message)
    return out


def phase_c(sets) -> dict:
    from lighthouse_tpu.crypto.bls import api, cpu_backend
    from lighthouse_tpu.ops.backend import cpu_fallback_max

    n = len(sets)
    bad_idx = n // 3
    bad = poisoned_copy(sets, bad_idx)
    out = {"n": n, "k": len(sets[0].signing_keys), "poisoned_index": bad_idx}
    for name, batch, want in (("valid", sets, True), ("poisoned", bad, False)):
        runs0 = _device_batches()
        t0 = time.perf_counter()
        dev = api.verify_signature_sets(batch, backend="tpu")
        t_dev = time.perf_counter() - t0
        check(_device_batches() - runs0 >= (n > cpu_fallback_max()),
              f"phase C {name}: the batch did not run on the device engine")
        t0 = time.perf_counter()
        native = cpu_backend.verify_signature_sets_cpu(batch)
        t_nat = time.perf_counter() - t0
        check(dev is want and native is want,
              f"phase C {name}: device {dev}, native {native}, want {want}")
        out[name] = {"device": dev, "native": native,
                     "device_s": round(t_dev, 2), "native_s": round(t_nat, 2)}
    t0 = time.perf_counter()
    found = api.find_invalid_sets(bad, backend="tpu")
    check(found == [bad_idx], f"find_invalid_sets named {found}, "
                              f"poisoned {bad_idx}")
    out["find_invalid"] = {"found": found,
                           "wall_s": round(time.perf_counter() - t0, 2)}
    return out


# ---------------------------------------------------------------------------
# --four-chip: Phase C's batches through the sharded core
# ---------------------------------------------------------------------------


def sharded_jobs(n: int, k: int, m: int, n_devices: int):
    """Stage 1/2 of the mesh-constrained core with minor-axis-sharded
    avals (stage 3's input layout is whatever stages 1/2 put out, so it
    compiles on first use)."""
    from lighthouse_tpu.ops.bm import backend as bmb
    from lighthouse_tpu.parallel import mesh as pm

    mesh = pm.get_mesh(n_devices)
    s1, s2, _ = _stage_avals(n, k, m, lambda nd: pm.minor_sharding(mesh, nd))
    core = bmb.jitted_core(n, k, m, sharded=True, n_devices=n_devices)
    return [(("sharded-h2g2", m), core.stages[0], s1),
            (("sharded-prepare", n, k, m), core.stages[1], s2)]


def verify_sharded(sets, n_devices: int):
    """The default multi-device path (ops/backend._verify_bm_impl) with
    the staged inputs kept, to show where their shards live."""
    from lighthouse_tpu.ops import backend as be
    from lighthouse_tpu.ops.bm import backend as bmb
    from lighthouse_tpu.parallel import mesh as pm

    _, n_dev, n_b, k_b, m_floor = be._buckets(sets, True)
    check(n_dev == n_devices, f"mesh over {n_dev} devices, want {n_devices}")
    args, m_b = be.stage_bm(sets, len(sets), n_b, k_b, m_floor=m_floor)
    mesh = pm.get_mesh(n_devices)
    args = tuple(pm.shard_batch_minor(a, mesh) for a in args)
    shards = [sorted(s.device.id for s in a.addressable_shards) for a in args]
    core = bmb.jitted_core(n_b, k_b, m_b, sharded=True, n_devices=n_devices)
    return bool(core(*args)), shards


def four_chip(sets, n_devices: int = 4) -> dict:
    from lighthouse_tpu.crypto.bls import cpu_backend
    from lighthouse_tpu.ops import backend as be

    bad = poisoned_copy(sets, len(sets) // 3)
    out = {}
    for name, batch, want in (("valid", sets, True), ("poisoned", bad, False)):
        sharded, shards = verify_sharded(batch, n_devices)
        single = be.verify_signature_sets_tpu(batch, sharded=False)
        native = cpu_backend.verify_signature_sets_cpu(batch)
        check(sharded is single is native is want,
              f"four-chip {name}: sharded {sharded}, one chip {single}, "
              f"native {native}, want {want}")
        check(all(s == list(range(n_devices)) for s in shards),
              f"four-chip {name}: inputs not spread over all chips: {shards}")
        out[name] = {"sharded": sharded, "one_chip": single,
                     "native": native, "input_shard_devices": shards}
    return out


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only Phase C's batches through the sharded "
                         "core on 4 chips, against one chip and native")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chip else 1
    device = phase_a(n_chips)

    t_start = time.perf_counter()
    from lighthouse_tpu.common import metrics as m
    from lighthouse_tpu.observability import compile_events

    compile_events.install()

    # Compile every stage shape in threads while the main thread builds
    # the phases' data (pure Python); Phase B starts once its own shapes
    # are compiled, while Phase C's still compile.
    from lighthouse_tpu.ops.backend import BM_M_FLOOR, _m_bucket_for

    c_shape = (C_SETS, C_KEYS,
               max(_m_bucket_for(C_SETS, C_SETS), BM_M_FLOOR))
    t0 = time.perf_counter()
    compiler = CompileAhead()
    compiler.add(stage_jobs([]))    # the final exponentiation: any shape
    if args.four_chip:
        b_jobs = []
        compiler.add(stage_jobs([c_shape]) + sharded_jobs(*c_shape, n_chips))
    else:
        from lighthouse_tpu.testing.firehose import mainnet_capella_spec

        spec = mainnet_capella_spec()
        prepared = [slot_attestations(N_VALIDATORS, PER_COMMITTEE, spec)]
        plan = planned_batches(prepared[0][1])
        diag(phase="B-plan", n_atts=len(prepared[0][1]),
             planned_batches=plan)
        b_jobs = stage_jobs(sorted(device_shapes(plan, 1)))
        compiler.add(b_jobs)
        compiler.add(stage_jobs([c_shape]))
    diag(compile_ahead=compiler.labels())
    if not args.four_chip:
        prepared.append(slot_attestations(N_VALIDATORS, PER_COMMITTEE, spec))
    c_sets = distinct_sets(C_SETS, C_KEYS)
    diag(phase="setup", wall_s=round(time.perf_counter() - t0, 2))

    if args.four_chip:
        compiler.wait()
        diag(phase="compile", wall_s=round(time.perf_counter() - t0, 2),
             per_stage_s=compiler.secs)
        t0 = time.perf_counter()
        diag(phase="four-chip", **four_chip(c_sets, n_chips),
             wall_s=round(time.perf_counter() - t0, 2))
    else:
        compiler.wait([j[0] for j in b_jobs])
        diag(phase="compile-B", wall_s=round(time.perf_counter() - t0, 2))
        t1 = time.perf_counter()
        diag(phase="B", **phase_b(prepared),
             wall_s=round(time.perf_counter() - t1, 2))
        compiler.wait()
        diag(phase="compile", wall_s=round(time.perf_counter() - t0, 2),
             per_stage_s=compiler.secs)
        t1 = time.perf_counter()
        diag(phase="C", **phase_c(c_sets),
             wall_s=round(time.perf_counter() - t1, 2))

    retried = m.REGISTRY.counter_vec(
        "serving_router_fallback_total",
        "Device-route failures retried on the native CPU route",
        "outcome").get("retried")
    check(retried == 0, f"router retried {retried} device batches on CPU")
    import jax

    mem = jax.devices()[0].memory_stats() or {}
    # Compiles beyond the compile-ahead jobs: small eager ops of staging,
    # sharded stage 3, or a shape the plan missed.
    cache_dir = jax.config.jax_compilation_cache_dir
    diag(compiles=_compiles(), compile_ahead_jobs=len(compiler.labels()),
         cache_dir=cache_dir, cache_files=len(os.listdir(cache_dir))
         if cache_dir and os.path.isdir(cache_dir) else 0,
         device_batches=_device_batches(),
         router_fallback_retried=retried,
         peak_bytes_in_use=mem.get("peak_bytes_in_use"),
         host_peak_rss_gib=_host_peak_rss_gib(),
         total_s=round(time.perf_counter() - t_start, 2))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
