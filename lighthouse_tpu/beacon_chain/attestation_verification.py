"""Gossip attestation verification — typestate pipeline + device batching.

Mirror of beacon_chain/src/attestation_verification.rs (+ batch.rs): an
attestation progresses Indexed -> Verified through per-object gossip checks
(slot window, aggregation-bit shape, known target/head block, first-seen
equivocation tracking), committee indexing via the shuffling cache, then BLS
verification — one set per unaggregated attestation, three per aggregate
(selection proof, aggregate-and-proof envelope, indexed attestation;
batch.rs:78-108).

The batch entry points run ALL sets of a batch through one backend call
(TPU batch verify); on a failed batch they re-verify per item to isolate
the poisoned attestation(s) (batch.rs:123-134) — valid items still import.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

from lighthouse_tpu.crypto.bls import api as bls
from lighthouse_tpu.observability import trace
from lighthouse_tpu.state_transition import signature_sets as sigsets


class AttestationError(Exception):
    def __init__(self, kind: str, detail: str = ""):
        self.kind = kind
        super().__init__(f"{kind}{': ' + detail if detail else ''}")


@dataclass
class IndexedUnaggregatedAttestation:
    """Gossip-checked + committee-indexed, signature NOT yet verified
    (attestation_verification.rs:805)."""

    attestation: object
    validator_index: int
    committee: List[int]
    subnet_id: int


@dataclass
class VerifiedUnaggregatedAttestation:
    attestation: object
    validator_index: int
    indexed_attestation: object


@dataclass
class IndexedAggregatedAttestation:
    signed_aggregate: object
    indexed_attestation: object


@dataclass
class VerifiedAggregatedAttestation:
    signed_aggregate: object
    indexed_attestation: object


def _attestation_slot_window_ok(chain, slot: int) -> None:
    """MAXIMUM_GOSSIP_CLOCK_DISPARITY-free variant of the slot propagation
    window (verify_early_checks): slot <= current, within one epoch."""
    current = chain.current_slot()
    if slot > current:
        raise AttestationError("FutureSlot", f"att {slot} > current {current}")
    earliest = current - chain.spec.preset.SLOTS_PER_EPOCH
    if slot < earliest:
        raise AttestationError("PastSlot", f"att {slot} < earliest {earliest}")


def _indexed_from_committee(types, attestation, committee: List[int]):
    bits = list(attestation.aggregation_bits)
    if len(bits) != len(committee):
        raise AttestationError(
            "CommitteeLengthMismatch", f"{len(bits)} bits vs {len(committee)}"
        )
    indices = sorted(v for v, b in zip(committee, bits) if b)
    if not indices:
        raise AttestationError("EmptyAggregationBitfield")
    return types.IndexedAttestation(
        attesting_indices=indices,
        data=attestation.data,
        signature=attestation.signature,
    )


# ---------------------------------------------------------------------------
# Unaggregated (subnet) attestations
# ---------------------------------------------------------------------------


def verify_unaggregated_checks(
    chain, attestation, subnet_id: Optional[int] = None
) -> IndexedUnaggregatedAttestation:
    """All gossip checks except the signature
    (verify_early_checks :711 / verify_middle_checks :752)."""
    data = attestation.data
    _attestation_slot_window_ok(chain, data.slot)

    bits = list(attestation.aggregation_bits)
    if sum(1 for b in bits if b) != 1:
        raise AttestationError("NotExactlyOneAggregationBitSet")

    head_root = bytes(data.beacon_block_root)
    if not chain.block_is_known(head_root):
        raise AttestationError("UnknownHeadBlock", head_root.hex())

    committees = chain.committees_at(data.slot)
    if data.index >= committees.committees_per_slot:
        raise AttestationError("BadCommitteeIndex", str(data.index))
    committee = committees.committee(data.slot, data.index)
    indexed = _indexed_from_committee(chain.types, attestation, committee)
    validator_index = indexed.attesting_indices[0]

    epoch = chain.spec.epoch_at_slot(data.slot)
    if chain.observed_attesters.observe(epoch, validator_index):
        raise AttestationError(
            "PriorAttestationKnown", f"validator {validator_index} epoch {epoch}"
        )
    return IndexedUnaggregatedAttestation(
        attestation=attestation,
        validator_index=validator_index,
        committee=committee,
        subnet_id=subnet_id if subnet_id is not None else 0,
    )


def _unagg_signature_set(chain, indexed_att):
    state = chain.head_state_for_signatures()
    return sigsets.indexed_attestation_signature_set(
        state, chain.types, chain.spec, indexed_att, chain.pubkey_getter
    )


def verify_unaggregated_attestation(
    chain, attestation, subnet_id: Optional[int] = None
) -> VerifiedUnaggregatedAttestation:
    """Single-item path (verify_attestation_signature :1088-1116)."""
    with trace.span("att.checks", cat="attestation", n=1):
        indexed = verify_unaggregated_checks(chain, attestation, subnet_id)
    with trace.span("att.set_build", cat="attestation", n=1):
        iatt = _indexed_from_committee(chain.types, attestation,
                                       indexed.committee)
        sset = _unagg_signature_set(chain, iatt)
    if not bls.verify_signature_sets([sset], backend=chain.bls_backend):
        raise AttestationError("InvalidSignature")
    return VerifiedUnaggregatedAttestation(
        attestation=attestation,
        validator_index=indexed.validator_index,
        indexed_attestation=iatt,
    )


def _report_poisoned_origin(chain, origins, i) -> None:
    """Bisection named a culprit: route it back to the networking layer's
    peer penalties instead of silently dropping (the reference's
    `BeaconChainError -> PeerAction` mapping). `chain.peer_reporter` is
    installed by NetworkService; standalone chains have none."""
    reporter = getattr(chain, "peer_reporter", None)
    if reporter is None or origins is None:
        return
    origin = origins[i]
    if origin is not None:
        reporter(origin, "InvalidSignature")


def batch_verify_unaggregated_attestations(
    chain, attestations: Sequence[Tuple[object, Optional[int]]],
    origins: Optional[Sequence[Optional[str]]] = None,
) -> List[object]:
    """One BLS backend call for the whole batch (batch.rs:140); per-item
    fallback isolates poison. Returns results aligned with the inputs:
    VerifiedUnaggregatedAttestation or AttestationError. `origins` (when
    given, aligned with the inputs) names the gossip peer each item came
    from so a poisoned signature is charged to its sender."""
    results: List[object] = [None] * len(attestations)
    # Two passes in item order: the gossip checks (and their observed_*
    # side effects), then the indexed attestations and signature sets of
    # the items that passed them (which the checks have already indexed
    # once, so the second pass raises no AttestationError).
    checked = []  # (idx, IndexedUnaggregated)
    with trace.span("att.checks", cat="attestation", n=len(attestations)):
        for i, (att, subnet_id) in enumerate(attestations):
            try:
                checked.append(
                    (i, verify_unaggregated_checks(chain, att, subnet_id)))
            except AttestationError as e:
                results[i] = e
    staged = []  # (idx, IndexedUnaggregated, indexed_att, sig_set)
    with trace.span("att.set_build", cat="attestation", n=len(checked)):
        for i, ind in checked:
            iatt = _indexed_from_committee(chain.types, attestations[i][0],
                                           ind.committee)
            staged.append((i, ind, iatt, _unagg_signature_set(chain, iatt)))

    if staged:
        sets = [s[3] for s in staged]
        # Poisoned batches isolate culprits by bisection (log2 passes, not
        # n per-item re-verifies — batch.rs:123-134 upgraded per SURVEY §7.3).
        bad = set(bls.find_invalid_sets(sets, backend=chain.bls_backend))
        for pos, (i, ind, iatt, _) in enumerate(staged):
            if pos in bad:
                results[i] = AttestationError("InvalidSignature")
                _report_poisoned_origin(chain, origins, i)
            else:
                results[i] = VerifiedUnaggregatedAttestation(
                    attestation=attestations[i][0],
                    validator_index=ind.validator_index,
                    indexed_attestation=iatt,
                )
    return results


# ---------------------------------------------------------------------------
# Aggregated attestations
# ---------------------------------------------------------------------------


def _is_aggregator(chain, slot: int, committee_len: int, selection_proof: bytes) -> bool:
    """spec is_aggregator: hash(selection_proof) mod max(1, len//TARGET) == 0."""
    import hashlib

    target = chain.spec.preset.TARGET_AGGREGATORS_PER_COMMITTEE
    modulo = max(1, committee_len // target)
    digest = hashlib.sha256(bytes(selection_proof)).digest()
    return int.from_bytes(digest[:8], "little") % modulo == 0


def verify_aggregated_checks(chain, signed_aggregate) -> IndexedAggregatedAttestation:
    msg = signed_aggregate.message
    aggregate = msg.aggregate
    data = aggregate.data
    _attestation_slot_window_ok(chain, data.slot)

    agg_root = chain.types.Attestation.hash_tree_root(aggregate)
    if chain.observed_aggregates.observe(data.slot, agg_root):
        raise AttestationError("AttestationSupersetKnown")
    if chain.observed_aggregators.observe(
        chain.spec.epoch_at_slot(data.slot), msg.aggregator_index
    ):
        raise AttestationError(
            "AggregatorAlreadyKnown", str(msg.aggregator_index)
        )

    head_root = bytes(data.beacon_block_root)
    if not chain.block_is_known(head_root):
        raise AttestationError("UnknownHeadBlock", head_root.hex())

    committees = chain.committees_at(data.slot)
    if data.index >= committees.committees_per_slot:
        raise AttestationError("BadCommitteeIndex", str(data.index))
    committee = committees.committee(data.slot, data.index)
    if msg.aggregator_index not in committee:
        raise AttestationError("AggregatorNotInCommittee")
    if not _is_aggregator(chain, data.slot, len(committee), msg.selection_proof):
        raise AttestationError("InvalidSelectionProof", "not selected")

    indexed = _indexed_from_committee(chain.types, aggregate, committee)
    return IndexedAggregatedAttestation(
        signed_aggregate=signed_aggregate, indexed_attestation=indexed
    )


def _aggregate_signature_sets(chain, signed_aggregate, indexed_att):
    """The three sets per aggregate (batch.rs:78-108)."""
    state = chain.head_state_for_signatures()
    t, s = chain.types, chain.spec
    return [
        sigsets.selection_proof_signature_set(
            state, t, s, signed_aggregate, chain.pubkey_getter
        ),
        sigsets.aggregate_and_proof_signature_set(
            state, t, s, signed_aggregate, chain.pubkey_getter
        ),
        sigsets.indexed_attestation_signature_set(
            state, t, s, indexed_att, chain.pubkey_getter
        ),
    ]


def verify_aggregated_attestation(chain, signed_aggregate) -> VerifiedAggregatedAttestation:
    """Single-item 3-set verification (attestation_verification.rs:1204-1232)."""
    ind = verify_aggregated_checks(chain, signed_aggregate)
    sets = _aggregate_signature_sets(chain, signed_aggregate, ind.indexed_attestation)
    if not bls.verify_signature_sets(sets, backend=chain.bls_backend):
        raise AttestationError("InvalidSignature")
    return VerifiedAggregatedAttestation(
        signed_aggregate=signed_aggregate,
        indexed_attestation=ind.indexed_attestation,
    )


def batch_verify_aggregated_attestations(
    chain, signed_aggregates: Sequence[object],
    origins: Optional[Sequence[Optional[str]]] = None,
) -> List[object]:
    """3 sets per aggregate, one backend call (batch.rs:31); fallback as
    above. Results align with inputs; `origins` as in the unaggregated
    batch — poisoned aggregates are charged to their gossip sender."""
    results: List[object] = [None] * len(signed_aggregates)
    staged = []
    for i, agg in enumerate(signed_aggregates):
        try:
            ind = verify_aggregated_checks(chain, agg)
            sets = _aggregate_signature_sets(chain, agg, ind.indexed_attestation)
            staged.append((i, ind, sets))
        except AttestationError as e:
            results[i] = e

    if staged:
        # Flatten each aggregate's sets, keeping the flat-index -> item map
        # explicit (no assumption about how many sets an item contributes).
        all_sets = []
        owner = []
        for pos, (_, _, sets) in enumerate(staged):
            all_sets.extend(sets)
            owner.extend([pos] * len(sets))
        bad_sets = bls.find_invalid_sets(all_sets, backend=chain.bls_backend)
        bad_items = {owner[f] for f in bad_sets}
        for pos, (i, ind, _) in enumerate(staged):
            if pos in bad_items:
                results[i] = AttestationError("InvalidSignature")
                _report_poisoned_origin(chain, origins, i)
            else:
                results[i] = VerifiedAggregatedAttestation(
                    signed_aggregate=signed_aggregates[i],
                    indexed_attestation=ind.indexed_attestation,
                )
    return results
