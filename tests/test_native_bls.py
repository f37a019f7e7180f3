"""Differential tests: native C++ batch verifier (native/src/blscpu.cpp)
vs the pure-Python oracle — the bit-agreement contract of VERDICT r2 #2
("both backends bit-agree on the KATs"). The oracle itself is pinned to
external known-answer vectors in test_known_answers.py, so agreement here
chains the native path to the same ground truth."""

import os
import secrets

import pytest

from lighthouse_tpu.crypto.bls import api
from lighthouse_tpu.crypto.bls import curves as cv
from lighthouse_tpu.crypto.bls import fields as f
from lighthouse_tpu.crypto.bls import hash_to_curve as h2c
from lighthouse_tpu.crypto.bls.constants import R

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
