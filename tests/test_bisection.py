"""Poisoned-batch bisection: find_invalid_sets isolates culprits in log2
passes (SURVEY.md §7.3 item 4)."""

from lighthouse_tpu.crypto.bls import api as bls


def _make_sets(n, bad_indices=()):
    sets = []
    for i in range(n):
        sk = bls.SecretKey(5000 + i)
        msg = bytes([i + 1]) * 32
        sig = sk.sign(msg if i not in bad_indices else b"\xbb" * 32)
        sets.append(bls.SignatureSet(
            signature=bls.Signature(point=sig.point, subgroup_checked=True),
            signing_keys=[sk.public_key()],
            message=msg,
        ))
    return sets


def test_clean_batch_returns_empty():
    calls = []
    orig = bls.verify_signature_sets

    def counting(sets, backend=None):
        calls.append(len(sets))
        return orig(sets, backend=backend)

    bls_verify, bls.verify_signature_sets = bls.verify_signature_sets, counting
    try:
        assert bls.find_invalid_sets(_make_sets(8)) == []
        assert calls == [8]  # one batch call, no splitting
    finally:
        bls.verify_signature_sets = bls_verify


def test_single_poison_isolated_in_log_passes():
    calls = []
    orig = bls.verify_signature_sets

    def counting(sets, backend=None):
        calls.append(len(sets))
        return orig(sets, backend=backend)

    bls.verify_signature_sets = counting
    try:
        out = bls.find_invalid_sets(_make_sets(8, bad_indices={5}))
        assert out == [5]
        # 1 full + 2 per level x log2(8) = 7 calls, far below 8 per-item + 1
        assert len(calls) <= 7
    finally:
        bls.verify_signature_sets = orig


def test_multiple_poisons_found():
    out = bls.find_invalid_sets(_make_sets(9, bad_indices={0, 4, 8}))
    assert out == [0, 4, 8]


def test_all_bad():
    out = bls.find_invalid_sets(_make_sets(3, bad_indices={0, 1, 2}))
    assert out == [0, 1, 2]


def test_device_bisection_stays_on_the_root_shape(monkeypatch):
    """The device backend's bisection stages every sub-batch at the root
    batch's buckets, so isolating a culprit compiles no new shape."""
    from lighthouse_tpu.crypto.bls import cpu_backend
    from lighthouse_tpu.ops import backend as be

    seen = []

    def fake_impl(sets, sharded, floors=(1, 1, 1)):
        seen.append((len(sets), floors))
        return cpu_backend.verify_signature_sets_cpu(sets)

    monkeypatch.setattr(be, "_verify_tpu_impl", fake_impl)
    sets = _make_sets(8, bad_indices={5})
    assert bls.find_invalid_sets(sets, backend="tpu") == [5]
    assert [n for n, _ in seen] == [8, 4, 4, 2, 1, 1, 2]
    _, _, n_bucket, k_bucket, m_floor = be._buckets(sets, None)
    assert {f for _, f in seen} == {(n_bucket, k_bucket, m_floor)}
