"""Bulk verification: a closed loop of `verify_signature_sets` calls.

One caller, one call in flight. The pool (pool.py) is made from the seed
and holds `pool_calls` slices of `sets_per_call` triples, every message
distinct. Call i sends slice i mod `pool_calls` in a fresh order drawn
from the seed, and `poisoned_share` of the calls, chosen by the seed,
carry one invalid triple in place of a valid one. So the first pass over
the pool sends only messages never sent before, and later passes send
them again. Each call is staged afresh by the program (host
hash-to-field, point conversion, transfer) and run through its device
stages. The rate is the sets of every call in the window over the
window's whole time.

Correctness, after the window:
  wrong_verdicts       calls whose verdict differs from the reference's:
                       true iff every triple of the call is valid;
  reference_disagrees  triples of a sample drawn from the seed (valid
                       ones and some of each invalid kind) on which the
                       plain reference's verdict differs from the one
                       the pool was made with;
  repeat_speedup       how much faster the calls of later passes ran than
                       those of the first, as a share of the first's
                       median: a program that kept anything from one call
                       for a later one (a hash, a verdict) would gain
                       here, where no deployment sends a set twice.
"""

from __future__ import annotations

import random
import statistics
import time

import numpy as np

from .. import compile_ahead
from .. import pool as pool_mod


class Cell:
    def __init__(self, config, traffic, seed, spans, seconds,
                 rehearse=False):
        self.cfg = dict(config, **(config.get("rehearsal", {})
                                   if rehearse else {}))
        self.t = dict(traffic, **(traffic.get("rehearsal", {})
                                  if rehearse else {}))
        self.seed = seed
        self.spans = spans
        self.rehearse = rehearse
        self.calls = []          # (indices, verdict, t0, t1, pass, traced)
        self.traced_sets = 0

    # ---------------------------------------------------------------- set-up

    def setup(self) -> dict:
        from lighthouse_tpu.crypto.bls import api

        parts = {}
        t = self.t
        n = self.cfg["sets_per_call"]
        compiler = compile_ahead.CompileAhead()
        if not self.rehearse:
            shape = compile_ahead.bm_shape(n, self.cfg["keys_per_set"], n)
            parts["stages"] = compiler.add_shapes([shape] if shape else [])

        self.pool = pool_mod.make(self.seed, n, t["pool_calls"],
                                  t["invalid"])
        parts["pool_made_s"] = self.pool["made_s"]

        t0 = time.perf_counter()
        self.set_objs = [
            api.SignatureSet(
                signature=api.Signature(point=sig, subgroup_checked=False),
                signing_keys=[api.PublicKey(point=pk)], message=msg)
            for pk, msg, sig, _ in self.pool["triples"]]
        parts["sets_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        compiler.wait()
        parts["compile_wait_s"] = time.perf_counter() - t0
        parts["compile_ahead_s"] = dict(compiler.secs)

        # Warm-up: whole calls through the window's own path, on a slice
        # and a stream of draws of their own.
        t0 = time.perf_counter()
        warm = np.random.default_rng([self.seed, 1])
        for i in range(t["warmup_calls"]):
            api.verify_signature_sets(
                self._draw(warm, self.pool["warm"], poisoned=i % 2 == 1),
                backend="tpu")
        parts["warmup_s"] = time.perf_counter() - t0
        return parts

    def _draw(self, rng, indices, poisoned: bool, out=None):
        idx = [indices[i] for i in rng.permutation(len(indices))]
        if poisoned:
            bad = [i for kind in sorted(self.pool["invalid"])
                   for i in self.pool["invalid"][kind]]
            idx[int(rng.integers(len(idx)))] = bad[int(rng.integers(
                len(bad)))]
        if out is not None:
            out.append(idx)
        return [self.set_objs[i] for i in idx]

    # ---------------------------------------------------------------- window

    def run_window(self, seconds: float, tracer) -> None:
        from lighthouse_tpu.crypto.bls import api

        rng = np.random.default_rng([self.seed, 2])
        poison = random.Random(self.seed)
        share = self.t["poisoned_share"]
        trace_calls = self.t["trace_calls"]
        slices = self.pool["slices"]
        start = time.perf_counter()
        i = 0
        while True:
            if i == 0:
                tracer.start()
            traced = tracer.on and not tracer.done
            drawn = []
            t0 = time.perf_counter()
            with self.spans.span("bench.call", n=self.cfg["sets_per_call"]):
                sets = self._draw(rng, slices[i % len(slices)],
                                  poison.random() < share, drawn)
                ok = api.verify_signature_sets(sets, backend="tpu")
            t1 = time.perf_counter()
            self.calls.append((drawn[0], bool(ok), t0, t1,
                               i // len(slices), traced))
            i += 1
            if i == trace_calls and tracer.on:
                tracer.stop()
                self.traced_sets = trace_calls * self.cfg["sets_per_call"]
            if t1 - start >= seconds:
                break
        tracer.stop()
        if not self.traced_sets:
            self.traced_sets = i * self.cfg["sets_per_call"]
        self.window_s = time.perf_counter() - start

    def close(self) -> None:
        self.set_objs = None

    # --------------------------------------------------------------- results

    def end_to_end(self) -> dict:
        n = sum(len(c[0]) for c in self.calls)
        return {"bulk_sets_per_s": n / self.window_s}

    def counts(self) -> tuple:
        n = sum(len(c[0]) for c in self.calls)
        return n, 0

    def repeat_speedup(self) -> float:
        """1 - median(later passes) / median(first pass), over the calls
        that ran untraced; 0 where either has none."""
        first = [t1 - t0 for _, _, t0, t1, k, tr in self.calls
                 if k == 0 and not tr]
        later = [t1 - t0 for _, _, t0, t1, k, tr in self.calls
                 if k > 0 and not tr]
        if not first or not later:
            return 0.0
        return 1.0 - statistics.median(later) / statistics.median(first)

    def checks(self) -> list:
        from ..reference import bls

        triples = self.pool["triples"]
        wrong = sum(1 for idx, ok, *_ in self.calls
                    if ok != all(triples[i][3] for i in idx))
        rng = random.Random(f"recheck:{self.seed}")
        picks = rng.sample([i for s in self.pool["slices"] for i in s],
                           self.t["recheck_valid"])
        for kind, n in sorted(self.t["recheck_invalid"].items()):
            picks += rng.sample(self.pool["invalid"][kind], n)
        disagree = sum(1 for i in picks
                       if bls.verify(triples[i][0], triples[i][1],
                                     triples[i][2]) != triples[i][3])
        secs = sorted(t1 - t0 for _, _, t0, t1, _, _ in self.calls)
        speedup = self.repeat_speedup()
        self.diag = {"calls": len(self.calls),
                     "passes": 1 + max(c[4] for c in self.calls),
                     "reject_verdicts": sum(1 for c in self.calls
                                            if not c[1]),
                     "rechecked_triples": len(picks),
                     "call_s_median": secs[len(secs) // 2],
                     "call_s_max": secs[-1]}
        return [("wrong_verdicts", wrong, 0),
                ("reference_disagrees", disagree, 0),
                ("repeat_speedup", speedup, self.t["repeat_speedup_limit"])]

    def layer_context(self) -> dict:
        return {"sets_traced": self.traced_sets}
