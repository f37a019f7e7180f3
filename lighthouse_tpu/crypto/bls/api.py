"""Ethereum BLS signature API with pluggable backends.

Mirrors the *seam* of the reference's `crypto/bls` crate — the `define_mod!`
backend instantiation (crypto/bls/src/lib.rs:99-140) with its trait family
`TPublicKey` / `TSignature` / `TAggregateSignature` (generic_*.rs) and the
`GenericSignatureSet {signature, signing_keys, message}` device ABI
(crypto/bls/src/generic_signature_set.rs:61-72).

Backends:
    * ``oracle``  — pure-Python bignum implementation (ground truth).
    * ``fake``    — always-true verification, mirrors the reference's
                    fake_crypto backend (crypto/bls/src/impls/fake_crypto.rs:29-33)
                    used to run state-transition tests without crypto cost.
    * ``tpu``     — the JAX/TPU batched implementation (lighthouse_tpu.ops),
                    registered lazily by lighthouse_tpu.ops.backend.

Semantics match blst's (crypto/bls/src/impls/blst.rs:36-118):
    * batch verification uses per-set random nonzero 64-bit scalars
      (RAND_BITS at blst.rs:15) from the host CSPRNG,
    * signatures are subgroup-checked on use (blst.rs:72-82),
    * infinity public keys are rejected (generic_public_key.rs),
    * a failed batch is the caller's cue to fall back to per-set verification
      (beacon_chain/src/attestation_verification/batch.rs:123-134).
"""

from __future__ import annotations

import secrets
from dataclasses import dataclass
from functools import lru_cache
from typing import Iterable, List, Optional, Sequence

from lighthouse_tpu.observability import trace

from . import curves as c
from . import fields as f
from . import hash_to_curve as h2c
from . import pairing as pr
from .constants import (
    FLAG_COMPRESSED,
    FLAG_INFINITY,
    PUBLIC_KEY_BYTES_LEN,
    R,
    RAND_BITS,
    SECRET_KEY_BYTES_LEN,
    SIGNATURE_BYTES_LEN,
)

# ---------------------------------------------------------------------------
# Key / signature types
# ---------------------------------------------------------------------------


class BlsError(Exception):
    pass


class SecretKey:
    """A scalar in [1, r). Serialized big-endian 32 bytes (EIP-2335 ordering)."""

    __slots__ = ("_k",)

    def __init__(self, k: int):
        if not 0 < k < R:
            raise BlsError("secret key out of range")
        self._k = k

    @classmethod
    def from_bytes(cls, data: bytes) -> "SecretKey":
        if len(data) != SECRET_KEY_BYTES_LEN:
            raise BlsError("bad secret key length")
        return cls(int.from_bytes(data, "big"))

    @classmethod
    def random(cls) -> "SecretKey":
        while True:
            k = secrets.randbelow(R)
            if k:
                return cls(k)

    def to_bytes(self) -> bytes:
        return self._k.to_bytes(SECRET_KEY_BYTES_LEN, "big")

    def public_key(self) -> "PublicKey":
        return PublicKey(point=c.g1_mul(c.G1_GEN, self._k))

    def sign(self, message: bytes) -> "Signature":
        """message is hashed to G2 and multiplied by the key (PoP scheme)."""
        h = h2c.hash_to_g2(message)
        return Signature(point=c.g2_mul(h, self._k), subgroup_checked=True)

    @property
    def scalar(self) -> int:
        return self._k


@dataclass(frozen=True)
class PublicKey:
    """Decompressed G1 public key.

    The decompressed in-memory form exists for the same reason as the
    reference's validator pubkey cache (beacon_chain/src/validator_pubkey_cache.rs:10-23):
    decompression is expensive and amortized once per validator.
    """

    point: tuple  # affine (x, y); infinity is rejected at construction sites

    @classmethod
    def from_bytes(cls, data: bytes) -> "PublicKey":
        try:
            pt = c.g1_from_compressed(data)
        except ValueError as e:
            # Malformed wire bytes (bad length/flags, x >= p, not on
            # curve) surface as BlsError — decode-path callers catch
            # exactly that (default_pubkey_getter etc.).
            raise BlsError(str(e))
        if pt is None:
            raise BlsError("infinity public key rejected")
        if not c.g1_in_subgroup(pt):
            raise BlsError("public key not in G1 subgroup")
        return cls(point=pt)

    def to_bytes(self) -> bytes:
        return c.g1_to_compressed(self.point)

    def hex(self) -> str:
        return "0x" + self.to_bytes().hex()


@dataclass(frozen=True)
class AggregatePublicKey:
    point: Optional[tuple]

    @classmethod
    def aggregate(cls, pubkeys: Sequence[PublicKey]) -> "AggregatePublicKey":
        if not pubkeys:
            raise BlsError("cannot aggregate zero public keys")
        acc = None
        for pk in pubkeys:
            acc = c.g1_add(acc, pk.point)
        return cls(point=acc)


@dataclass(frozen=True)
class Signature:
    """A G2 signature. ``point is None`` encodes the infinity signature, which
    deserializes successfully (it is a valid group element) but never verifies
    against a valid public key.

    ``subgroup_checked`` records that the point has already passed the G2
    subgroup check so verification does not pay for it twice (the check costs
    a full scalar multiplication)."""

    point: Optional[tuple]
    subgroup_checked: bool = False

    @classmethod
    def from_bytes(cls, data: bytes, subgroup_check: bool = True) -> "Signature":
        native = _native_g2()
        try:
            pt = _g2_decompress(data, native)
        except ValueError as e:
            raise BlsError(str(e))   # malformed wire bytes (see PublicKey)
        if subgroup_check and pt is not None:
            ok = (native.g2_in_subgroup_native(pt) if native
                  else c.g2_in_subgroup(pt))
            if not ok:
                raise BlsError("signature not in G2 subgroup")
        return cls(point=pt, subgroup_checked=subgroup_check)

    def to_bytes(self) -> bytes:
        return c.g2_to_compressed(self.point)

    def hex(self) -> str:
        return "0x" + self.to_bytes().hex()

    @classmethod
    def infinity(cls) -> "Signature":
        return cls(point=None)


@lru_cache(maxsize=None)
def _native_g2():
    """The native library's G2 decoder (cpu_backend), or None where the
    library cannot be built (no toolchain): the oracle decodes then."""
    try:
        from . import cpu_backend
        cpu_backend.get_lib()
    except Exception:
        return None
    return cpu_backend


@lru_cache(maxsize=None)
def signature_decodes_total():
    """`bls_signature_decodes_total{route}`: G2 signature decodes by the
    path that took the square root: the native library (`native`) or the
    pure-Python oracle (`python`; also every encoding the oracle answers
    from its length or flags alone, infinity included)."""
    from lighthouse_tpu.common import metrics as m

    return m.REGISTRY.counter_vec(
        "bls_signature_decodes_total",
        "G2 signature decodes, by the route that decoded them "
        "(native|python)", "route")


def _g2_decompress(data: bytes, native):
    """curves.g2_from_compressed, with the square root on the native
    library where it loads. The same point, or the same ValueError."""
    if (native is None or len(data) != SIGNATURE_BYTES_LEN
            or not data[0] & FLAG_COMPRESSED or data[0] & FLAG_INFINITY):
        signature_decodes_total().labels("python").inc()
        return c.g2_from_compressed(data)
    signature_decodes_total().labels("native").inc()
    return native.g2_decompress_native(data)


@dataclass(frozen=True)
class AggregateSignature:
    point: Optional[tuple]

    @classmethod
    def infinity(cls) -> "AggregateSignature":
        return cls(point=None)

    @classmethod
    def aggregate(cls, sigs: Sequence[Signature]) -> "AggregateSignature":
        acc = None
        for s in sigs:
            acc = c.g2_add(acc, s.point)
        return cls(point=acc)

    @classmethod
    def from_bytes(cls, data: bytes) -> "AggregateSignature":
        return cls(point=Signature.from_bytes(data).point)

    def to_bytes(self) -> bytes:
        return c.g2_to_compressed(self.point)


@dataclass(frozen=True)
class SignatureSet:
    """One verification unit: does `signature` sign `message` under the
    aggregate of `signing_keys`? Identical in shape to the reference's
    GenericSignatureSet (crypto/bls/src/generic_signature_set.rs:61-72); this
    is the ABI that gets staged into fixed-shape tensors for the TPU backend.
    """

    signature: Signature
    signing_keys: Sequence[PublicKey]
    message: bytes  # 32-byte signing root

    def aggregate_pubkey(self) -> Optional[tuple]:
        if not self.signing_keys:
            return None
        return AggregatePublicKey.aggregate(self.signing_keys).point


# ---------------------------------------------------------------------------
# Verification (oracle backend primitives)
# ---------------------------------------------------------------------------


def _sig_in_subgroup(sig) -> bool:
    # `sig` may be a Signature (carries its deserialization-time subgroup
    # flag) or an AggregateSignature (aggregation of checked points — no
    # flag; re-check the point).
    return getattr(sig, "subgroup_checked", False) or \
        c.g2_in_subgroup(sig.point)


def verify(pubkey: PublicKey, message: bytes, signature: Signature) -> bool:
    """Single verification: e(pk, H(m)) == e(g1, sig)."""
    if signature.point is None:
        return False
    if not _sig_in_subgroup(signature):
        return False
    h = h2c.hash_to_g2(message)
    return pr.pairings_product_is_one(
        [(pubkey.point, h), (c.g1_neg(c.G1_GEN), signature.point)]
    )


def fast_aggregate_verify(pubkeys: Sequence[PublicKey], message: bytes, signature: Signature) -> bool:
    """All keys sign the same message (attestation aggregate shape)."""
    if not pubkeys:
        return False
    agg = AggregatePublicKey.aggregate(pubkeys)
    if agg.point is None:
        return False
    return verify(PublicKey(point=agg.point), message, signature)


def aggregate_verify(pubkeys: Sequence[PublicKey], messages: Sequence[bytes], signature: Signature) -> bool:
    """Distinct message per key: prod e(pk_i, H(m_i)) == e(g1, sig)."""
    if not pubkeys or len(pubkeys) != len(messages):
        return False
    if signature.point is None:
        return False
    if not _sig_in_subgroup(signature):
        return False
    pairs = [(pk.point, h2c.hash_to_g2(m)) for pk, m in zip(pubkeys, messages)]
    pairs.append((c.g1_neg(c.G1_GEN), signature.point))
    return pr.pairings_product_is_one(pairs)


def _random_batch_scalar() -> int:
    while True:
        k = secrets.randbits(RAND_BITS)
        if k:
            return k


def verify_signature_sets_oracle(sets: Sequence[SignatureSet]) -> bool:
    """Random-scalar batch verification (Vitalik's scheme), semantics of
    blst's verify_multiple_aggregate_signatures as driven by
    crypto/bls/src/impls/blst.rs:36-118:

        prod_i e(r_i * agg_pk_i, H(m_i)) * e(-g1, sum_i r_i * sig_i) == 1

    with r_i random nonzero 64-bit scalars.
    """
    if not sets:
        return False
    pairs = []
    sig_acc = None
    for s in sets:
        if not s.signing_keys:
            return False
        if s.signature.point is None:
            return False
        if not _sig_in_subgroup(s.signature):
            return False
        agg_pk = s.aggregate_pubkey()
        if agg_pk is None:
            return False
        r = _random_batch_scalar()
        pairs.append((c.g1_mul(agg_pk, r), h2c.hash_to_g2(s.message)))
        sig_acc = c.g2_add(sig_acc, c.g2_mul(s.signature.point, r))
    pairs.append((c.g1_neg(c.G1_GEN), sig_acc))
    return pr.pairings_product_is_one(pairs)


# ---------------------------------------------------------------------------
# Backend seam
# ---------------------------------------------------------------------------

_BACKENDS = {
    "oracle": verify_signature_sets_oracle,
    # Mirrors fake_crypto: unconditional success (fake_crypto.rs:29-33).
    "fake": lambda sets: True,
}
_active_backend = "oracle"


def register_backend(name: str, fn) -> None:
    _BACKENDS[name] = fn


def set_backend(name: str) -> None:
    global _active_backend
    if name == "tpu" and "tpu" not in _BACKENDS:
        # Lazy import so the pure-Python oracle has no JAX dependency.
        from lighthouse_tpu.ops import backend as _tpu_backend  # noqa: F401
    if name == "cpu" and "cpu" not in _BACKENDS:
        # Lazy: compiles the native verifier on first use.
        from . import cpu_backend as _cpu_backend  # noqa: F401
    if name not in _BACKENDS:
        raise BlsError(f"unknown BLS backend: {name}")
    _active_backend = name


def get_backend() -> str:
    return _active_backend


# Backends whose bisection verifies sub-batches through a verifier built
# from the ROOT batch (name -> make(sets) -> verify(sub_sets) -> bool): the
# device backend pins every sub-batch to the root's compiled shapes.
_BISECT_VERIFIERS = {}


def register_bisect_verifier(name: str, make) -> None:
    _BISECT_VERIFIERS[name] = make


def _backend_fn(name: str):
    if name == "tpu" and "tpu" not in _BACKENDS:
        from lighthouse_tpu.ops import backend as _tpu_backend  # noqa: F401
    if name == "cpu" and "cpu" not in _BACKENDS:
        from . import cpu_backend as _cpu_backend  # noqa: F401
    return _BACKENDS[name]


def verify_signature_sets(sets: Sequence[SignatureSet], backend: Optional[str] = None) -> bool:
    """The north-star entry point (BASELINE.json): batch-verify signature sets
    on the active backend. On False, callers re-verify individually to find
    the poisoned item (reference batch.rs:123-134 fallback semantics)."""
    return _backend_fn(backend or _active_backend)(list(sets))


def find_invalid_sets(
    sets: Sequence[SignatureSet], backend: Optional[str] = None
) -> list:
    """Poisoned-batch isolation by BISECTION: a failing range splits in two
    and each failing half recurses — ~2·log2(n)·k batch calls for k culprits
    instead of the reference's n per-item re-verifications
    (attestation_verification/batch.rs:123-134; SURVEY.md §7.3 item 4 says
    do this on-device to avoid host round-trips). A backend with a
    registered bisect verifier (the device backend) stages every sub-batch
    at the root batch's shapes, so isolation never compiles a new one.

    Returns the indices of invalid sets (empty when the whole batch
    verifies). The `bls.bisect` span carries `n`, and on exit `calls`
    (verifications made, the whole batch's included) and `bad`."""
    sets = list(sets)
    out: list = []
    calls = 0
    name = backend or _active_backend
    _backend_fn(name)  # lazy backends register their bisect verifier
    if name in _BISECT_VERIFIERS and sets:
        verify = _BISECT_VERIFIERS[name](sets)
    else:
        def verify(sub):
            return verify_signature_sets(sub, backend=backend)

    def recurse(lo: int, hi: int) -> None:
        nonlocal calls
        calls += 1
        if verify(sets[lo:hi]):
            return
        if hi - lo == 1:
            out.append(lo)
            return
        mid = (lo + hi) // 2
        recurse(lo, mid)
        recurse(mid, hi)

    with trace.span("bls.bisect", cat="bls", n=len(sets)) as sp:
        if sets:
            recurse(0, len(sets))
        sp.set(calls=calls, bad=len(out))
    return out
