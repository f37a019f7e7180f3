"""Differential tests: native C++ batch verifier (native/src/blscpu.cpp)
vs the pure-Python oracle — the bit-agreement contract of VERDICT r2 #2
("both backends bit-agree on the KATs"). The oracle itself is pinned to
external known-answer vectors in test_known_answers.py, so agreement here
chains the native path to the same ground truth."""

import functools
import os
import secrets

import pytest

from lighthouse_tpu.crypto.bls import api
from lighthouse_tpu.crypto.bls import curves as cv
from lighthouse_tpu.crypto.bls import fields as f
from lighthouse_tpu.crypto.bls import hash_to_curve as h2c
from lighthouse_tpu.crypto.bls.constants import P, R

cpu_backend = pytest.importorskip(
    "lighthouse_tpu.crypto.bls.cpu_backend",
    reason="native toolchain unavailable",
)


def _keypair(seed: int):
    sk = (seed * 6364136223846793005 + 1442695040888963407) % R or 1
    return api.SecretKey(sk)


def _set_for(sk: "api.SecretKey", msg: bytes) -> api.SignatureSet:
    return api.SignatureSet(
        signature=sk.sign(msg), signing_keys=[sk.public_key()], message=msg
    )


def test_hash_to_g2_matches_oracle():
    for msg in [b"\x00" * 32, b"abc", bytes(range(64)), secrets.token_bytes(32)]:
        assert cpu_backend.hash_to_g2_native(msg) == h2c.hash_to_g2(msg)


def test_valid_batch_and_poison():
    sets = [_set_for(_keypair(i), bytes([i]) * 32) for i in range(6)]
    assert cpu_backend.verify_signature_sets_cpu(sets) is True
    # poison one signature
    bad = list(sets)
    wrong = _keypair(99).sign(bad[3].message)
    bad[3] = api.SignatureSet(
        signature=wrong, signing_keys=bad[3].signing_keys,
        message=bad[3].message,
    )
    assert cpu_backend.verify_signature_sets_cpu(bad) is False
    # oracle agrees on both
    assert api.verify_signature_sets_oracle(sets) is True
    assert api.verify_signature_sets_oracle(bad) is False


def test_aggregate_pubkeys_set():
    msg = b"\x42" * 32
    sks = [_keypair(10 + i) for i in range(4)]
    agg_sig = api.AggregateSignature.aggregate([sk.sign(msg) for sk in sks])
    s = api.SignatureSet(
        signature=api.Signature(point=agg_sig.point),
        signing_keys=[sk.public_key() for sk in sks],
        message=msg,
    )
    assert cpu_backend.verify_signature_sets_cpu([s]) is True
    # drop one signer from the key list -> invalid
    s_bad = api.SignatureSet(
        signature=api.Signature(point=agg_sig.point),
        signing_keys=[sk.public_key() for sk in sks[:-1]],
        message=msg,
    )
    assert cpu_backend.verify_signature_sets_cpu([s_bad]) is False


def test_rejects_match_oracle_edges():
    sk = _keypair(1)
    msg = b"\x01" * 32
    good = _set_for(sk, msg)
    # empty batch
    assert cpu_backend.verify_signature_sets_cpu([]) is False
    # empty signing keys
    s_empty = api.SignatureSet(
        signature=sk.sign(msg), signing_keys=[], message=msg
    )
    assert cpu_backend.verify_signature_sets_cpu([s_empty]) is False
    # infinity signature
    s_inf = api.SignatureSet(
        signature=api.Signature(point=None), signing_keys=[sk.public_key()],
        message=msg,
    )
    assert cpu_backend.verify_signature_sets_cpu([good, s_inf]) is False


def test_non_subgroup_signature_rejected():
    # A point on E2 but outside G2 (cofactor not cleared).
    xx = 5
    cand = None
    while cand is None:
        y2 = f.fp2_add(f.fp2_mul(f.fp2_sqr((xx, 0)), (xx, 0)), (4, 4))
        y = f.fp2_sqrt(y2)
        if y is not None and not cv.g2_in_subgroup(((xx, 0), y)):
            cand = ((xx, 0), y)
        xx += 1
    sk = _keypair(2)
    msg = b"\x02" * 32
    s = api.SignatureSet(
        signature=api.Signature(point=cand, subgroup_checked=False),
        signing_keys=[sk.public_key()],
        message=msg,
    )
    assert cpu_backend.verify_signature_sets_cpu([s]) is False


def test_small_batch_routing(monkeypatch):
    """verify_signature_sets_tpu routes small batches to the native path
    when the fallback threshold allows it."""
    from lighthouse_tpu.ops import backend as tpu_backend

    monkeypatch.setenv("LIGHTHOUSE_TPU_CPU_FALLBACK_MAX", "8")
    calls = {}
    real = cpu_backend.verify_signature_sets_cpu

    def spy(sets):
        calls["n"] = len(sets)
        return real(sets)

    monkeypatch.setattr(cpu_backend, "verify_signature_sets_cpu", spy)
    sets = [_set_for(_keypair(30 + i), bytes([i]) * 32) for i in range(3)]
    assert tpu_backend.verify_signature_sets_tpu(sets) is True
    assert calls.get("n") == 3


def test_cpu_backend_registered_via_api():
    sets = [_set_for(_keypair(40), b"\x07" * 32)]
    assert api.verify_signature_sets(sets, backend="cpu") is True


def test_native_build_is_keyed_by_source_flags_and_host(tmp_path,
                                                         monkeypatch):
    """A build whose key (source, flags, host CPU flags) does not match is
    never loaded: another host's -march=native artifact is rebuilt, not
    reused (it could die with SIGILL here)."""
    from lighthouse_tpu import native

    src = tmp_path / "src"
    src.mkdir()
    (src / "probe.cpp").write_text(
        'extern "C" int probe_answer() { return 42; }\n')
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_BUILD", str(tmp_path / "build"))
    monkeypatch.setattr(native, "_cache", {})

    assert native.load("probe").probe_answer() == 42
    here = sorted(os.listdir(tmp_path / "build"))
    assert here == [f"libprobe-{native.build_key(str(src / 'probe.cpp'))}.so"]

    # Same source and flags on a host with other CPU features: new key,
    # new build; the first artifact is left alone, not loaded.
    monkeypatch.setattr(native, "_cpu_flags", lambda: "another-host")
    monkeypatch.setattr(native, "_cache", {})
    assert native.load("probe").probe_answer() == 42
    assert len(os.listdir(tmp_path / "build")) == 2

    # An edited source is a new key, so it is rebuilt: the key, not a
    # timestamp, decides.
    (src / "probe.cpp").write_text(
        'extern "C" int probe_answer() { return 7; }\n')
    monkeypatch.setattr(native, "_cache", {})
    assert native.load("probe").probe_answer() == 7


# ---------------------------------------------------------------------------
# G2 signature decoding: blscpu_g2_decompress behind Signature.from_bytes,
# against the oracle curves.g2_from_compressed.
# ---------------------------------------------------------------------------

# benchmark/pool.order13_point(): on E2, of order 13, outside G2. The
# gossip benchmark's invalid attestations carry a valid signature plus it.
_ORDER13 = bytes.fromhex(
    "a792718e185cdb1c6c487ddb1d7aa26201545014e38fa86b90438501ec546be9"
    "fcc7eaf637367b82cbe47779ff8ea79e104d14ecc0a2abed5c64adf27363c0e2"
    "73c7e19f5385785e5b6a2b070a2970e64284507acb64bdf40dbcef84f93cb220")


@functools.lru_cache(maxsize=None)
def _sig_point():
    return _keypair(7).sign(b"\x05" * 32).point


def _valid_sigs():
    """32 G2 points, 16 and their negations: both sign bits."""
    s, acc, out = _sig_point(), None, []
    for _ in range(16):
        acc = cv.g2_add(acc, s)
        out += [cv.g2_to_compressed(acc), cv.g2_to_compressed(cv.g2_neg(acc))]
    return out


def _x1_zero():
    """An on-curve x = (x0, 0), both signs."""
    x0 = 5
    while f.fp2_sqrt(f.fp2_add(f.fp2_mul(f.fp2_sqr((x0, 0)), (x0, 0)),
                               (4, 4))) is None:
        x0 += 1
    enc = bytearray(96)
    enc[0] = 0x80
    enc[48:] = x0.to_bytes(48, "big")
    return [bytes(enc), bytes([0xA0]) + bytes(enc[1:])]


def _with_x(x0=None, x1=None):
    enc = bytearray(cv.g2_to_compressed(_sig_point()))
    if x1 is not None:
        enc[:48] = x1.to_bytes(48, "big")
        enc[0] |= 0x80
    if x0 is not None:
        enc[48:] = x0.to_bytes(48, "big")
    return bytes(enc)


def _off_curve():
    enc = cv.g2_to_compressed(_sig_point())
    x0 = int.from_bytes(enc[48:], "big")
    while True:
        x0 += 1
        try:
            cv.g2_from_compressed(_with_x(x0=x0))
        except ValueError:
            return [_with_x(x0=x0)]


def _order13():
    return [_ORDER13, cv.g2_to_compressed(
        cv.g2_add(_sig_point(), cv.g2_from_compressed(_ORDER13)))]


_DECODE_CASES = {
    "valid-both-signs": _valid_sigs,
    "x1-zero": _x1_zero,
    "infinity": lambda: [bytes([0xC0]) + bytes(95)],
    "infinity-noncanonical": lambda: [bytes([0xE0]) + bytes(95),
                                      bytes([0xC0]) + bytes(94) + b"\x01"],
    "x0-ge-p": lambda: [_with_x(x0=P), _with_x(x0=2**381 - 1)],
    "x1-ge-p": lambda: [_with_x(x1=P), _with_x(x1=2**381 - 1)],
    "off-curve": _off_curve,
    "uncompressed": lambda: [bytes([_with_x()[0] & 0x7F]) + _with_x()[1:]],
    "wrong-length": lambda: [_with_x()[:95], _with_x() + b"\x00"],
    "order-13": _order13,
}


def _oracle(data: bytes, subgroup_check: bool):
    """What the pure-Python path answers: a point, or the error text."""
    try:
        pt = cv.g2_from_compressed(data)
    except ValueError as e:
        return str(e)
    if subgroup_check and pt is not None and not cv.g2_in_subgroup(pt):
        return "signature not in G2 subgroup"
    return pt


def _decode(data: bytes, subgroup_check: bool):
    try:
        return api.Signature.from_bytes(data, subgroup_check).point
    except api.BlsError as e:
        return str(e)


def _decodes():
    return api.signature_decodes_total()


@pytest.mark.parametrize("case", sorted(_DECODE_CASES))
def test_signature_decode_matches_oracle(case):
    """Every encoding decodes to the oracle's point, or raises BlsError
    with the oracle's words, with and without the subgroup check; the
    native library takes every encoding that reaches a square root."""
    encs = _DECODE_CASES[case]()
    assert api._native_g2() is cpu_backend
    before = _decodes().get("native")
    for data in encs:
        for check in (False, True):
            assert _decode(data, check) == _oracle(data, check), (case, check)
    structural = {"infinity", "infinity-noncanonical", "uncompressed",
                  "wrong-length"}
    rooted = 0 if case in structural else 2 * len(encs)
    assert _decodes().get("native") - before == rooted
    if case == "order-13":
        for data in encs:
            assert isinstance(_decode(data, False), tuple)
            assert _decode(data, True) == "signature not in G2 subgroup"


@pytest.fixture
def no_native_library(monkeypatch):
    """The native library fails to load, as on a host with no toolchain."""
    def fail(name):
        raise OSError("no toolchain")

    monkeypatch.setattr(cpu_backend, "_lib", None)
    monkeypatch.setattr(cpu_backend, "load", fail)
    api._native_g2.cache_clear()
    yield
    api._native_g2.cache_clear()


def test_signature_decode_falls_back_to_the_oracle(no_native_library):
    encs = _valid_sigs()[:2] + _order13()
    want = [cv.g2_from_compressed(d) for d in encs]
    before = {r: _decodes().get(r) for r in ("native", "python")}
    assert [api.Signature.from_bytes(d, subgroup_check=False).point
            for d in encs] == want
    assert api._native_g2() is None
    assert _decodes().get("python") - before["python"] == len(encs)
    assert _decodes().get("native") == before["native"]
