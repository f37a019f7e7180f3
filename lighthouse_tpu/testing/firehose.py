"""Verification-ON firehose rig: the full gossip slot path at scale.

Shared by tests/test_scale_firehose.py (CPU-jax, small device buckets)
and scripts/probe_firehose_tpu.py (real chip, production batches):
a big-registry chain whose grafted validators all carry validator 0's
REAL pubkey — so single-bit attestations signed by key 0 verify under
the genuine batch equation while the registry scales to the eval shape
(BASELINE.json config #4: 500k validators, verification on).

Pipeline driven: BeaconProcessor batch former (AdaptiveBatchPolicy) ->
SignatureSet staging -> device/native verify -> fork-choice apply
(reference gossip path beacon_processor/src/lib.rs:974-1060).
"""

from __future__ import annotations

import time
from typing import List

from lighthouse_tpu.beacon_processor import (
    AdaptiveBatchPolicy,
    BeaconProcessor,
    WorkEvent,
)
from lighthouse_tpu.types.spec import (
    DOMAIN_BEACON_ATTESTER,
    compute_signing_root,
    get_domain,
)


GWEI_32 = 32 * 10**9


def graft_validators(chain, n_extra: int, pubkey: bytes = None) -> None:
    """Append a synthetic active-validator tail to the head state (the
    scale rig for eval config #4; fake-backend tests pass opaque pubkey
    bytes, the verification-on rig passes a real compressed point)."""
    from lighthouse_tpu.types.spec import FAR_FUTURE_EPOCH

    types = chain.types
    state = chain.head.state
    for i in range(n_extra):
        state.validators.append(types.Validator(
            pubkey=pubkey or (1_000_000 + i).to_bytes(48, "big"),
            withdrawal_credentials=b"\x00" * 32,
            effective_balance=GWEI_32,
            slashed=False,
            activation_eligibility_epoch=0,
            activation_epoch=0,
            exit_epoch=FAR_FUTURE_EPOCH,
            withdrawable_epoch=FAR_FUTURE_EPOCH,
        ))
        state.balances.append(GWEI_32)
        state.previous_epoch_participation.append(0)
        state.current_epoch_participation.append(0)
        state.inactivity_scores.append(0)


def mainnet_capella_spec():
    """Mainnet preset (32 slots, up to 64 committees per slot) with every
    fork through Capella active at genesis, as the harness's genesis
    builder expects: the committee shape of a mainnet gossip slot."""
    from lighthouse_tpu.types.spec import ChainSpec

    return ChainSpec(altair_fork_epoch=0, bellatrix_fork_epoch=0,
                     capella_fork_epoch=0, deneb_fork_epoch=None)


def build_firehose_chain(n_extra: int, n_real: int = 32, spec=None):
    """Harness chain with `n_extra` grafted validators sharing validator
    0's pubkey (signatures by key 0 are honestly verifiable for every
    registry index via the pubkey-cache shortcut). `spec` defaults to the
    harness's minimal spec."""
    from lighthouse_tpu.testing.harness import BeaconChainHarness

    harness = BeaconChainHarness(n_validators=n_real, bls_backend="tpu",
                                 spec=spec)
    chain = harness.chain
    pk0_bytes = bytes(chain.head.state.validators[0].pubkey)
    graft_validators(chain, n_extra, pubkey=pk0_bytes)
    # The justified-balance snapshot was taken at chain construction
    # (n_real validators); refresh it so the grafted registry's votes
    # carry fork-choice weight, as they would on a real justified state.
    chain.fork_choice._refresh_justified_balances(
        chain.head.state, chain.spec
    )
    pk0 = chain.pubkey_cache.get(0)
    chain.pubkey_getter = lambda i: pk0
    return harness


def make_signed_single_bit_attestations(harness, slot: int,
                                        per_committee: int) -> List:
    """Up to `per_committee` single-bit attestations per committee of
    `slot`, each genuinely signed by key 0 over the correct
    DOMAIN_BEACON_ATTESTER signing root."""
    chain = harness.chain
    types, spec = harness.types, harness.spec
    state = chain.head.state
    committees = chain.committees_at(slot)
    sk0 = harness.keys[0]
    atts = []
    for index in range(committees.committees_per_slot):
        committee = committees.committee(slot, index)
        data = chain.produce_unaggregated_attestation(slot, index)
        domain = get_domain(
            spec, DOMAIN_BEACON_ATTESTER, data.target.epoch,
            state.fork.current_version, state.fork.previous_version,
            state.fork.epoch, state.genesis_validators_root,
        )
        root = compute_signing_root(data, types.AttestationData, domain)
        sig = sk0.sign(root).to_bytes()
        for pos in range(min(per_committee, len(committee))):
            bits = [False] * len(committee)
            bits[pos] = True
            atts.append(types.Attestation(
                aggregation_bits=bits, data=data, signature=sig,
            ))
    return atts


def run_firehose(harness, attestations, policy=None) -> dict:
    """Feed attestations through the batch former into
    chain.process_attestation_batch; returns per-batch latencies, import
    counts, the batch sizes the former chose and the input positions of
    rejected attestations. `policy` defaults to the node's own
    AdaptiveBatchPolicy() (client/builder.py)."""
    chain = harness.chain
    proc = BeaconProcessor(batch_policy=policy or AdaptiveBatchPolicy())
    pos = {id(att): i for i, att in enumerate(attestations)}
    batch_lat: List[float] = []
    batch_sizes: List[int] = []
    batch_of: dict = {}
    rejected: List[int] = []

    def process_batch(batch):
        t0 = time.monotonic()
        results = chain.process_attestation_batch(batch)
        batch_lat.append(time.monotonic() - t0)
        batch_sizes.append(len(batch))
        for att, r in zip(batch, results):
            batch_of[pos[id(att)]] = len(batch_sizes) - 1
            if isinstance(r, Exception):
                rejected.append(pos[id(att)])

    def process_one(att):
        t0 = time.monotonic()
        batch_sizes.append(1)
        batch_of[pos[id(att)]] = len(batch_sizes) - 1
        try:
            chain.process_attestation(att)
        except Exception:
            rejected.append(pos[id(att)])
        finally:
            batch_lat.append(time.monotonic() - t0)

    for att in attestations:
        ok = proc.send(WorkEvent(
            kind="gossip_attestation", item=att,
            process_individual=process_one, process_batch=process_batch,
        ))
        assert ok, "gossip queue overflow"

    t0 = time.monotonic()
    proc.run_until_idle()
    total = time.monotonic() - t0
    batch_lat.sort()
    return {
        "n_atts": len(attestations),
        "imported": len(attestations) - len(rejected),
        "rejected": sorted(rejected),
        "batch_of": batch_of,
        "batch_sizes": batch_sizes,
        "batches": proc.stats.batches,
        "batched_items": proc.stats.batched_items,
        "total_s": total,
        "batch_p50_s": batch_lat[len(batch_lat) // 2] if batch_lat else 0.0,
        "batch_p99_s": batch_lat[int(len(batch_lat) * 0.99)]
        if batch_lat else 0.0,
    }
