"""The SSZ roots an attestation signature is taken over (consensus-specs
phase0 `hash_tree_root`, `compute_domain`, `compute_signing_root`), written
out for the few containers the benchmark needs.
"""

from __future__ import annotations

import hashlib

DOMAIN_BEACON_ATTESTER = bytes.fromhex("01000000")
FAR_FUTURE_EPOCH = 2**64 - 1
VALIDATOR_REGISTRY_LIMIT = 2**40


def _h(a: bytes, b: bytes) -> bytes:
    return hashlib.sha256(a + b).digest()


def _uint64(v: int) -> bytes:
    return int(v).to_bytes(8, "little") + b"\x00" * 24


def _merkleize(chunks, limit: int = None) -> bytes:
    """Root of `chunks` padded with zero chunks to the next power of two
    of `limit` (default: of their count)."""
    width = 1
    while width < (limit if limit is not None else max(1, len(chunks))):
        width *= 2
    zero = b"\x00" * 32
    layer = list(chunks)
    while width > 1:
        if len(layer) % 2:
            layer.append(zero)
        layer = [_h(layer[i], layer[i + 1]) for i in range(0, len(layer), 2)]
        zero = _h(zero, zero)
        width //= 2
    return layer[0] if layer else zero


def checkpoint_root(epoch: int, root: bytes) -> bytes:
    return _merkleize([_uint64(epoch), bytes(root)])


def attestation_data_root(slot, index, beacon_block_root, source, target):
    """source and target are (epoch, root) pairs."""
    return _merkleize([
        _uint64(slot), _uint64(index), bytes(beacon_block_root),
        checkpoint_root(*source), checkpoint_root(*target)])


def _validator_root(pubkey: bytes, withdrawal_credentials: bytes,
                    effective_balance: int) -> bytes:
    pk_root = _merkleize([pubkey[:32], pubkey[32:] + b"\x00" * 16])
    return _merkleize([
        pk_root, withdrawal_credentials, _uint64(effective_balance),
        _uint64(0),                       # slashed: False
        _uint64(0), _uint64(0),           # activation eligibility, activation
        _uint64(FAR_FUTURE_EPOCH), _uint64(FAR_FUTURE_EPOCH)])


def interop_validators_root(pubkeys, effective_balance: int) -> bytes:
    """hash_tree_root of a genesis registry of active validators with BLS
    withdrawal credentials (the interop genesis)."""
    roots = [
        _validator_root(pk, b"\x00" + hashlib.sha256(pk).digest()[1:],
                        effective_balance)
        for pk in pubkeys]
    return _h(_merkleize(roots, VALIDATOR_REGISTRY_LIMIT),
              len(roots).to_bytes(32, "little"))


def compute_domain(domain_type: bytes, fork_version: bytes,
                   genesis_validators_root: bytes) -> bytes:
    fork_data_root = _h(fork_version + b"\x00" * 28, genesis_validators_root)
    return domain_type + fork_data_root[:28]


def signing_root(object_root: bytes, domain: bytes) -> bytes:
    return _h(object_root, domain)
