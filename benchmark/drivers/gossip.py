"""Gossip attestations on the slot clock, through the node's processor.

A beacon node at the configuration's validator count, subscribed to
`subnets` attestation subnets (picked by the seed), receives every
single-bit attestation of those subnets' committees: each is due at a
time drawn uniformly from `due_window` seconds into its slot. An open
loop sends each at its due time into `BeaconProcessor` with the node's
default `AdaptiveBatchPolicy()`, whose batches go to
`chain.process_attestation_batch` (gossip checks, one BLS call with
bisection on failure, fork choice). `invalid_per_slot` attestations of
each slot are invalid, of the `invalid_kinds` in turn from slot to slot:
a signature over a wrong root, or a valid signature plus a point of
order 13, on the curve but outside G2. Every seed offers the same
load: the due times of a slot, and the places of its invalid
attestations in arrival order, are drawn from the slot's place in the
run alone; the seed picks the subnets, the slots and which attestation
comes when.

The window is whole slots; then the run waits until every attestation
due in it has a verdict. Latency runs from an attestation's due time to
the end of the batch that decided it. A traced run sends only the first
`trace_slots` slots, traced whole: stopping the profiler takes about a
minute, which would stall the sender inside the window.

Correctness: every attestation of the window, its verdict (imported or
rejected) against the reference's verdict on its signature, computed with
the benchmark's own SSZ roots and BLS arithmetic. The traffic passes
every other gossip check by construction.
"""

from __future__ import annotations

import hashlib
import random
import time

from .. import compile_ahead, pool
from ..harness import percentile
from ..reference import bls as rbls
from ..reference import ssz
from ..reference.bls12_381 import curves as c

GWEI_32 = 32 * 10**9
SUBNET_COUNT = 64


def _signing_root(data, domain: bytes) -> bytes:
    return ssz.signing_root(ssz.attestation_data_root(
        data.slot, data.index, bytes(data.beacon_block_root),
        (data.source.epoch, bytes(data.source.root)),
        (data.target.epoch, bytes(data.target.root))), domain)


class Cell:
    def __init__(self, config, traffic, seed, spans, seconds,
                 rehearse=False):
        self.seconds = seconds
        self.cfg = dict(config, **(config.get("rehearsal", {})
                                   if rehearse else {}))
        self.t = dict(traffic, **(traffic.get("rehearsal", {})
                                  if rehearse else {}))
        self.seed = seed
        self.spans = spans
        self.rehearse = rehearse
        self.rng = random.Random(seed)

    # ---------------------------------------------------------------- set-up

    def _spec(self):
        from lighthouse_tpu.types import spec as sp

        if self.cfg["preset"] == "minimal":
            return sp.minimal_spec()
        return sp.ChainSpec(altair_fork_epoch=0, bellatrix_fork_epoch=0,
                            capella_fork_epoch=0, deneb_fork_epoch=None)

    def _build_chain(self):
        """A chain at genesis with `n_real` interop validators, grown to
        `n_validators` by a tail that shares validator 0's key, so that
        signatures by key 0 verify for every index."""
        from lighthouse_tpu.testing.harness import BeaconChainHarness

        n_real = self.cfg["n_real_validators"]
        harness = BeaconChainHarness(n_validators=n_real, bls_backend="tpu",
                                     spec=self._spec())
        chain = harness.chain
        types = chain.types
        state = chain.head.state
        pk0 = bytes(state.validators[0].pubkey)
        far = 2**64 - 1
        for _ in range(self.cfg["n_validators"] - n_real):
            state.validators.append(types.Validator(
                pubkey=pk0, withdrawal_credentials=b"\x00" * 32,
                effective_balance=GWEI_32, slashed=False,
                activation_eligibility_epoch=0, activation_epoch=0,
                exit_epoch=far, withdrawable_epoch=far))
            state.balances.append(GWEI_32)
            state.previous_epoch_participation.append(0)
            state.current_epoch_participation.append(0)
            state.inactivity_scores.append(0)
        # The justified balances were taken at construction: refresh them
        # so the grown registry's votes carry fork-choice weight.
        chain.fork_choice._refresh_justified_balances(state, chain.spec)
        key0 = chain.pubkey_cache.get(0)
        chain.pubkey_getter = lambda i: key0
        return chain

    def _slot_attestations(self, k: int, slot: int, indices, sk0: int,
                           domain):
        """Every member's single-bit attestation of the committees
        `indices` at `slot`, the run's `k`-th slot, with due times, and
        `invalid_per_slot` of them made invalid."""
        chain = self.chain
        types = chain.types
        committees = chain.committees_at(slot)
        lo, hi = self.t["due_window"]
        atts = []
        for index in indices:
            committee = committees.committee(slot, index)
            data = chain.produce_unaggregated_attestation(slot, index)
            sig = rbls.g2_to_bytes(rbls.sign(sk0, _signing_root(data,
                                                                domain)))
            for pos in range(len(committee)):
                bits = [False] * len(committee)
                bits[pos] = True
                atts.append(types.Attestation(aggregation_bits=bits,
                                              data=data, signature=sig))
        n = len(atts)
        fixed = random.Random(f"arrivals:{k}")
        due = sorted(lo + (hi - lo) * fixed.random() for _ in range(n))
        order = list(range(n))
        self.rng.shuffle(order)
        kinds = self.t["invalid_kinds"]
        bad = self.t["invalid_per_slot"]
        for r in range(bad):
            i = order[n * (r + 1) // (bad + 1)]
            root = _signing_root(atts[i].data, domain)
            if kinds[(k * bad + r) % len(kinds)] == "wrong_root":
                sig = rbls.sign(sk0, hashlib.sha256(b"wrong:" + root)
                                .digest())
            else:  # on the curve, outside G2
                sig = c.g2_add(rbls.sign(sk0, root), self.t13)
            atts[i] = types.Attestation(
                aggregation_bits=atts[i].aggregation_bits, data=atts[i].data,
                signature=rbls.g2_to_bytes(sig))
        return [(due[r], atts[i]) for r, i in enumerate(order)]

    def setup(self) -> dict:
        from lighthouse_tpu.beacon_processor import (
            AdaptiveBatchPolicy,
            BeaconProcessor,
        )
        from lighthouse_tpu.crypto.bls import api
        from lighthouse_tpu.ops.backend import cpu_fallback_max

        parts = {}
        t = self.t
        # Every device bucket a batch of this traffic can take: from the
        # smallest batch that is not answered natively to a slot's worth.
        cfg = self.cfg
        spe = cfg["slots_per_epoch"]
        cps = max(1, min(cfg["max_committees_per_slot"],
                         cfg["n_validators"] // spe
                         // cfg["target_committee_size"]))
        per_slot = t["subnets"] * -(-cfg["n_validators"] // (spe * cps))
        self.buckets = []
        b = 1
        while True:
            if b > cpu_fallback_max():
                self.buckets.append(b)
            if b >= per_slot:
                break
            b *= 2
        compiler = compile_ahead.CompileAhead()
        if not self.rehearse:
            # Distinct messages in a batch: a committee each, over at most
            # two slots; every such count folds onto one m bucket.
                parts["stages"] = compiler.add_shapes(
                [compile_ahead.bm_shape(b, 1, min(b, 2 * t["subnets"]))
                 for b in self.buckets])

        t0 = time.perf_counter()
        self.chain = self._build_chain()
        cps = self.chain.committees_at(1).committees_per_slot
        parts["chain_s"] = time.perf_counter() - t0

        # Which subnets, and which slots of the first epoch: the warm-up
        # slot, then the window's.
        t0 = time.perf_counter()
        spe = self.chain.spec.preset.SLOTS_PER_EPOCH
        self.slot_s = self.chain.spec.seconds_per_slot
        n_slots = max(1, int(self.seconds // self.slot_s))
        self.subnets = self.rng.sample(range(min(SUBNET_COUNT, cps)),
                                       t["subnets"])
        first = self.rng.randint(1, spe - 1 - n_slots)
        sk0 = rbls.interop_secret_key(0)
        self.t13 = pool.order13_point()
        pks = [rbls.g1_to_bytes(rbls.public_key(rbls.interop_secret_key(i)))
               for i in range(self.cfg["n_real_validators"])]
        domain = ssz.compute_domain(
            ssz.DOMAIN_BEACON_ATTESTER,
            bytes.fromhex(self.cfg["fork_version"][2:]),
            ssz.interop_validators_root(
                pks, self.cfg["max_effective_balance_gwei"]))
        self.chain.slot_clock.set_slot(first + n_slots)

        def indices(slot):
            return [(s - cps * (slot % spe)) % SUBNET_COUNT % cps
                    for s in self.subnets]

        self.slots = [(s, self._slot_attestations(k, s, indices(s), sk0,
                                                  domain))
                      for k, s in enumerate(range(first,
                                                  first + n_slots + 1))]
        parts["attestations_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        compiler.wait()
        parts["compile_wait_s"] = time.perf_counter() - t0
        parts["compile_ahead_s"] = dict(compiler.secs)

        # Warm-up: one call per device bucket, then the warm-up slot
        # through the processor at once, which also grows the batch
        # policy as the previous slots of a running node would have.
        t0 = time.perf_counter()
        warm_slot, warm_atts = self.slots.pop(0)
        self.chain.slot_clock.set_slot(warm_slot)
        key0 = self.chain.pubkey_cache.get(0)
        data = warm_atts[0][1].data
        sset = api.SignatureSet(
            signature=api.Signature(point=rbls.g2_from_bytes(
                bytes(warm_atts[0][1].signature))),
            signing_keys=[key0], message=_signing_root(data, domain))
        for b in self.buckets:
            api.verify_signature_sets([sset] * b, backend="tpu")
        self.policy = AdaptiveBatchPolicy()
        self.proc = BeaconProcessor(batch_policy=self.policy)
        self.recs = {}
        for due, att in warm_atts:
            self._send(att, due)
        self.proc.run_until_idle()
        warm_missing = sum(1 for r in self.recs.values() if r["ok"] is None)
        if warm_missing:
            raise RuntimeError(f"warm-up slot: {warm_missing} attestations "
                               f"got no verdict")
        parts["warmup_s"] = time.perf_counter() - t0
        parts["warm_buckets"] = sorted(self.policy.warm)
        self.domain = domain
        self.recs = {}
        return parts

    # ---------------------------------------------------------------- window

    def _send(self, att, due):
        from lighthouse_tpu.beacon_processor import WorkEvent

        rec = {"due": due, "sent": time.perf_counter(), "start": None,
               "done": None, "ok": None, "att": att}
        self.recs[id(att)] = rec
        ok = self.proc.send(WorkEvent(
            kind="gossip_attestation", item=att,
            process_individual=self._process_one,
            process_batch=self._process_batch))
        if not ok:
            rec["dropped"] = True

    def _process_batch(self, batch):
        t0 = time.perf_counter()
        with self.spans.span("bench.batch", n=len(batch)):
            results = self.chain.process_attestation_batch(batch)
        t1 = time.perf_counter()
        for att, r in zip(batch, results):
            rec = self.recs[id(att)]
            rec.update(start=t0, done=t1, ok=not isinstance(r, Exception),
                       why=type(r).__name__)

    def _process_one(self, att):
        t0 = time.perf_counter()
        why = "imported"
        with self.spans.span("bench.single", n=1):
            try:
                self.chain.process_attestation(att)
            except Exception as e:  # a rejection is a verdict; kept by name
                why = f"{type(e).__name__}: {e}"
        self.recs[id(att)].update(start=t0, done=time.perf_counter(),
                                  ok=why == "imported", why=why)

    def run_window(self, seconds: float, tracer) -> None:
        if tracer.on:
            self.slots = self.slots[:self.t["trace_slots"]]
        tracer.start()
        self.proc.start()
        try:
            t0 = time.perf_counter() + 0.2
            for k, (slot, atts) in enumerate(self.slots):
                start = t0 + k * self.slot_s
                time.sleep(max(0.0, start - time.perf_counter()))
                self.chain.slot_clock.set_slot(slot)
                due0 = start + atts[0][0]
                time.sleep(max(0.0, due0 - time.perf_counter()))
                with self.spans.span("bench.arrivals", slot=slot):
                    for due, att in atts:
                        due += start
                        time.sleep(max(0.0, due - time.perf_counter()))
                        self._send(att, due)
            end = t0 + len(self.slots) * self.slot_s
            time.sleep(max(0.0, end - time.perf_counter()))
            deadline = time.perf_counter() + self.t["drain_timeout_s"]
            while time.perf_counter() < deadline and any(
                    r["ok"] is None and not r.get("dropped")
                    for r in self.recs.values()):
                time.sleep(0.05)
        finally:
            self.proc.stop()
            tracer.stop()

    def close(self) -> None:
        self.proc = None
        self.chain = None

    # --------------------------------------------------------------- results

    def _latencies(self):
        return [r["done"] - r["due"] for r in self.recs.values()
                if r["done"] is not None]

    def end_to_end(self) -> dict:
        return {"gossip_att_p95_s": percentile(self._latencies(), 95)}

    def counts(self) -> tuple:
        missing = sum(1 for r in self.recs.values() if r["ok"] is None)
        return len(self.recs), missing

    def checks(self) -> list:
        pk0 = rbls.public_key(rbls.interop_secret_key(0))
        verdicts = {}
        wrong = 0
        for r in self.recs.values():
            if r["ok"] is None:
                continue
            att = r["att"]
            key = (_signing_root(att.data, self.domain),
                   bytes(att.signature))
            if key not in verdicts:
                verdicts[key] = rbls.verify(pk0, key[0],
                                            rbls.g2_from_bytes(key[1]))
            wrong += r["ok"] != verdicts[key]
        lat = self._latencies()
        p95 = percentile(lat, 95) if lat else None
        late = [r["sent"] - r["due"] for r in self.recs.values()]
        why = {}
        for r in self.recs.values():
            why[r.get("why")] = why.get(r.get("why"), 0) + 1
        self.diag = {
            "attestations": len(self.recs),
            "rejected": sum(1 for r in self.recs.values()
                            if r["ok"] is False),
            "reference_verifications": len(verdicts),
            "subnets": self.subnets, "slots": [s for s, _ in self.slots],
            "outcomes": why,
            "samples_beyond_p95": sum(1 for v in lat if v > p95),
            "latency_p50_s": percentile(lat, 50) if lat else None,
            "sender_late_max_s": max(late, default=0.0),
            "batch_sizes": [r[3]["n"] for r in self.spans.of("bench.batch")],
        }
        return [("wrong_verdicts", wrong, 0),
                ("missing_verdicts", self.counts()[1], 0)]

    def layer_context(self) -> dict:
        return {"records": [{k: v for k, v in r.items() if k != "att"}
                            for r in self.recs.values()]}
