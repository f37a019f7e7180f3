"""BeaconChain integration tests on the in-process harness.

Models beacon_node/beacon_chain/tests/{block_verification,
attestation_verification,tests}.rs driven through BeaconChainHarness
(SURVEY.md §4.3) — minimal spec, oracle BLS backend.
"""

import pytest

from lighthouse_tpu.beacon_chain import (
    AttestationError,
    BlockError,
    batch_verify_unaggregated_attestations,
    verify_chain_segment,
)
from lighthouse_tpu.testing.harness import BeaconChainHarness

N_VALIDATORS = 64


@pytest.fixture()
def harness():
    return BeaconChainHarness(n_validators=N_VALIDATORS)


def test_genesis_head(harness):
    chain = harness.chain
    assert chain.head.block_root == chain.genesis_block_root
    assert chain.head.state.slot == 0
    assert len(chain.pubkey_cache) == N_VALIDATORS


def test_import_blocks_and_head_follows(harness):
    chain = harness.chain
    blocks = harness.extend_chain(3, attest=False)
    assert chain.head.block_root == blocks[-1][0]
    assert chain.head.state.slot == 3
    # store has them all
    for root, signed in blocks:
        assert chain.store.get_block(root) is not None


def test_duplicate_block_rejected(harness):
    chain = harness.chain
    harness.advance_slot()
    signed, root = harness.make_block()
    chain.process_block(signed)
    with pytest.raises(BlockError) as ei:
        chain.process_block(signed)
    assert ei.value.kind in ("BlockIsAlreadyKnown", "RepeatProposal")


def test_future_slot_block_rejected(harness):
    chain = harness.chain
    harness.advance_slot()
    signed, _ = harness.make_block(slot=harness.current_slot + 2)
    with pytest.raises(BlockError) as ei:
        chain.process_block(signed)
    assert ei.value.kind == "FutureSlot"


def test_bad_proposer_signature_rejected(harness):
    chain = harness.chain
    harness.advance_slot()
    signed, _ = harness.make_block()
    # graft a signature from the wrong key
    wrong = harness.keys[(signed.message.proposer_index + 1) % N_VALIDATORS]
    signed.signature = wrong.sign(b"\x11" * 32).to_bytes()
    with pytest.raises(BlockError) as ei:
        chain.process_block(signed)
    assert ei.value.kind == "ProposalSignatureInvalid"


def test_unknown_parent_rejected(harness):
    chain = harness.chain
    harness.advance_slot()
    signed, _ = harness.make_block()
    signed.message.parent_root = b"\xee" * 32
    with pytest.raises(BlockError) as ei:
        chain.process_block(signed)
    assert ei.value.kind in ("ParentUnknown", "IncorrectBlockProposer",
                            "ProposalSignatureInvalid")


def test_gossip_attestation_verify_and_fork_choice(harness):
    chain = harness.chain
    harness.extend_chain(2, attest=False)
    slot = harness.current_slot
    atts = harness.make_attestations(slot)
    committees = chain.committees_at(slot)
    committee = committees.committee(slot, 0)
    single = harness.single_attestation(atts[0], 0, committee)

    harness.advance_slot()  # votes apply from the next slot
    verified = chain.process_attestation(single)
    assert verified.validator_index == committee[0]
    # the vote landed in fork choice
    head = chain.recompute_head()
    assert head == chain.head.block_root


def test_attestation_equivocation_rejected(harness):
    chain = harness.chain
    harness.extend_chain(2, attest=False)
    slot = harness.current_slot
    atts = harness.make_attestations(slot)
    committee = chain.committees_at(slot).committee(slot, 0)
    single = harness.single_attestation(atts[0], 0, committee)
    harness.advance_slot()
    chain.process_attestation(single)
    with pytest.raises(AttestationError) as ei:
        chain.process_attestation(single)
    assert ei.value.kind == "PriorAttestationKnown"


def test_attestation_unknown_block_rejected(harness):
    chain = harness.chain
    harness.extend_chain(1, attest=False)
    slot = harness.current_slot
    atts = harness.make_attestations(slot)
    committee = chain.committees_at(slot).committee(slot, 0)
    bad = harness.single_attestation(atts[0], 0, committee)
    bad.data.beacon_block_root = b"\x77" * 32
    # re-sign over mutated data
    bad = harness.single_attestation(bad, 0, committee)
    harness.advance_slot()
    with pytest.raises(AttestationError) as ei:
        chain.process_attestation(bad)
    assert ei.value.kind == "UnknownHeadBlock"


def test_batch_verify_with_poison_isolates_culprit(harness):
    """The poisoned-batch fallback (batch.rs:123-134): one bad signature
    fails the batch; per-item retry verifies the good ones."""
    chain = harness.chain
    harness.extend_chain(2, attest=False)
    slot = harness.current_slot
    atts = harness.make_attestations(slot)
    committee = chain.committees_at(slot).committee(slot, 0)
    singles = [
        harness.single_attestation(atts[0], pos, committee)
        for pos in range(min(4, len(committee)))
    ]
    # poison one: signature by the wrong validator
    bad = singles[2]
    wrong_sig = harness.keys[committee[3]].sign(b"\x99" * 32)
    bad.signature = wrong_sig.to_bytes()

    harness.advance_slot()
    results = batch_verify_unaggregated_attestations(
        chain, [(a, None) for a in singles]
    )
    from lighthouse_tpu.beacon_chain import VerifiedUnaggregatedAttestation

    assert isinstance(results[0], VerifiedUnaggregatedAttestation)
    assert isinstance(results[1], VerifiedUnaggregatedAttestation)
    assert isinstance(results[2], AttestationError)
    assert results[2].kind == "InvalidSignature"
    assert isinstance(results[3], VerifiedUnaggregatedAttestation)


def test_batch_decodes_signatures_on_the_native_library(harness):
    """Each attestation that passes the gossip checks has its signature
    decoded once, by the native library; one that fails them (a repeat
    of a validator's vote) is never decoded."""
    from lighthouse_tpu.crypto.bls import api

    chain = harness.chain
    slot = harness.current_slot
    atts = harness.make_attestations(slot)
    committee = chain.committees_at(slot).committee(slot, 0)
    singles = [harness.single_attestation(atts[0], pos, committee)
               for pos in range(min(3, len(committee)))]
    harness.advance_slot()
    decodes = api.signature_decodes_total()
    before = decodes.get("native"), decodes.get("python")
    results = batch_verify_unaggregated_attestations(
        chain, [(a, None) for a in singles + singles[:1]])
    assert isinstance(results[-1], AttestationError)
    passed = sum(not isinstance(r, AttestationError) for r in results)
    assert passed == len(singles)
    assert decodes.get("native") - before[0] == passed
    assert decodes.get("python") == before[1]


def test_aggregate_verification(harness):
    chain = harness.chain
    harness.extend_chain(2, attest=False)
    slot = harness.current_slot
    atts = harness.make_attestations(slot)
    committee = chain.committees_at(slot).committee(slot, 0)
    agg = harness.make_aggregate(atts[0], committee)
    harness.advance_slot()
    verified = chain.process_aggregate(agg)
    assert sorted(verified.indexed_attestation.attesting_indices) == sorted(committee)
    # duplicate aggregate rejected
    with pytest.raises(AttestationError):
        chain.process_aggregate(agg)


def test_fork_resolution_by_lmd_votes(harness):
    """Two competing heads; attestation weight decides (LMD-GHOST)."""
    chain = harness.chain
    harness.extend_chain(1, attest=False)
    common = chain.head.block_root

    harness.advance_slot()
    slot_a = harness.current_slot
    block_a, root_a = harness.make_block(parent_root=common, slot=slot_a)
    chain.process_block(block_a)

    # competing block at the next slot building on the same parent
    harness.advance_slot()
    slot_b = harness.current_slot
    block_b, root_b = harness.make_block(parent_root=common, slot=slot_b)
    chain.process_block(block_b)

    # without votes the tie-breaks favour... whatever find_head picks;
    # vote for A explicitly with one committee
    atts = harness.make_attestations(slot_a, head_root=root_a)
    committee = chain.committees_at(slot_a).committee(slot_a, 0)
    harness.advance_slot()
    for pos in range(len(committee)):
        single = harness.single_attestation(atts[0], pos, committee)
        try:
            chain.process_attestation(single)
        except AttestationError:
            pass
    head = chain.recompute_head()
    assert head == root_a


def test_chain_segment_bulk_verify_and_import(harness):
    """Range-sync path: batch of blocks, one bulk signature pass, imports
    (signature_verify_chain_segment :572)."""
    chain = harness.chain
    # Build 4 blocks WITHOUT importing them (on a scratch harness)
    donor = BeaconChainHarness(n_validators=N_VALIDATORS)
    blocks = [signed for _, signed in donor.extend_chain(4, attest=False)]

    harness.set_slot(4)
    verified = verify_chain_segment(chain, blocks)
    assert len(verified) == 4
    for sv in verified:
        chain.process_block_from_segment(sv)
    assert chain.head.state.slot == 4

    # poisoned segment fails as a whole
    donor2 = BeaconChainHarness(n_validators=N_VALIDATORS)
    blocks2 = [signed for _, signed in donor2.extend_chain(2, attest=False)]
    fresh = BeaconChainHarness(n_validators=N_VALIDATORS)
    fresh.set_slot(2)
    blocks2[1].signature = donor2.keys[0].sign(b"\x13" * 32).to_bytes()
    with pytest.raises(BlockError):
        verify_chain_segment(fresh.chain, blocks2)


def test_justification_advances_through_harness(harness):
    """Three attested epochs justify epoch >= 1 and prune via finalization
    machinery without breaking imports."""
    chain = harness.chain
    n = 3 * harness.spec.preset.SLOTS_PER_EPOCH
    harness.extend_chain(n, attest=True)
    assert chain.head.state.current_justified_checkpoint.epoch >= 1
