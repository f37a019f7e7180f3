"""BLS signatures (the Ethereum proof-of-possession ciphersuite,
BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_) on the plain arithmetic of
`bls12_381`: key derivation, signing and the verdict of one signature.

Points are affine tuples of Python integers: G1 (x, y), G2 ((x0, x1),
(y0, y1)); None is the point at infinity.
"""

from __future__ import annotations

import hashlib

from .bls12_381 import curves as c
from .bls12_381 import hash_to_curve as h2c
from .bls12_381 import pairing as pr
from .bls12_381.constants import R


def interop_secret_key(index: int) -> int:
    """The eth2 interop key: int_LE(sha256(uint256_LE(index))) mod r."""
    digest = hashlib.sha256(index.to_bytes(32, "little")).digest()
    return int.from_bytes(digest, "little") % R


def public_key(sk: int):
    return c.g1_mul(c.G1_GEN, sk)


def sign(sk: int, message: bytes):
    return c.g2_mul(h2c.hash_to_g2(message), sk)


def verify(pk, message: bytes, sig) -> bool:
    """e(pk, H(m)) == e(g1, sig), with the checks a wire signature gets:
    not infinity, in G2; the key not infinity, in G1."""
    if pk is None or sig is None:
        return False
    if not (c.g1_is_on_curve(pk) and c.g1_in_subgroup(pk)):
        return False
    if not (c.g2_is_on_curve(sig) and c.g2_in_subgroup(sig)):
        return False
    return pr.pairings_product_is_one(
        [(pk, h2c.hash_to_g2(message)), (c.g1_neg(c.G1_GEN), sig)])


g1_to_bytes = c.g1_to_compressed
g2_to_bytes = c.g2_to_compressed
g2_from_bytes = c.g2_from_compressed
