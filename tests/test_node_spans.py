"""The node's spans: `observability/trace.span` reaches the profiler's
`.xplane.pb` with its args, and the beacon processor, attestation
verification and the BLS backend open their phase spans (read here from
the Chrome sink, which gets the same names and args)."""

import glob
import os

import pytest


@pytest.fixture
def global_trace():
    """The global Chrome sink on for one test, guaranteed off after."""
    from lighthouse_tpu.observability import trace

    trace.TRACER.clear()
    trace.TRACER.enable()
    yield trace.TRACER
    trace.TRACER.disable()
    trace.TRACER.clear()


def _spans(tracer, prefix=""):
    return [e for e in tracer.events()
            if e["ph"] == "X" and e["name"].startswith(prefix)]


def _end(e):
    return e["ts"] + e["dur"]


def test_span_args_reach_the_profiler_trace(tmp_path):
    """A span opened under `jax.profiler.start_trace` is a host event of
    the `.xplane.pb`, with its args and those `set` on exit as stats."""
    import jax

    from lighthouse_tpu.observability import trace

    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("bls.bisect", n=7, depth=3) as sp:
            with trace.span("bls.device_wait"):
                pass
            sp.set(calls=5, bad=1)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bls."):
                    found[e.name] = (e.start_ns, e.duration_ns,
                                     dict(e.stats))
    outer, inner = found["bls.bisect"], found["bls.device_wait"]
    assert outer[2] == {"n": 7, "depth": 3, "calls": 5, "bad": 1}
    assert outer[0] <= inner[0]
    assert inner[0] + inner[1] <= outer[0] + outer[1]


def test_span_set_reaches_the_chrome_sink(global_trace):
    from lighthouse_tpu.observability import trace

    with trace.span("bls.bisect", cat="bls", n=4) as sp:
        sp.set(calls=3, bad=0)
    ev, = _spans(global_trace, "bls.bisect")
    assert ev["cat"] == "bls"
    assert ev["args"] == {"depth": 1, "n": 4, "calls": 3, "bad": 0}


def test_bp_batch_carries_kind_n_and_depth(global_trace):
    from lighthouse_tpu.beacon_processor.processor import (
        BeaconProcessor,
        WorkEvent,
    )
    from lighthouse_tpu.common.metrics import Registry

    proc = BeaconProcessor(max_batch=3, registry=Registry())
    for i in range(5):
        proc.send(WorkEvent("gossip_attestation", i,
                            process_batch=lambda items: None))
    proc.run_until_idle()
    got = [(e["args"]["kind"], e["args"]["n"], e["args"]["depth"])
           for e in _spans(global_trace, "bp.")]
    # Pops of 3 (2 left in the queue), then 2 (none left).
    assert [e["name"] for e in _spans(global_trace, "bp.")] == \
        ["bp.batch", "bp.batch"]
    assert got == [("gossip_attestation", 3, 2),
                   ("gossip_attestation", 2, 0)]


def test_bp_item_wraps_a_single_item(global_trace):
    from lighthouse_tpu.beacon_processor.processor import (
        BeaconProcessor,
        WorkEvent,
    )
    from lighthouse_tpu.common.metrics import Registry

    seen = []
    proc = BeaconProcessor(registry=Registry())
    proc.send(WorkEvent("gossip_block", "b", process_individual=seen.append))
    proc.run_until_idle()
    ev, = _spans(global_trace, "bp.")
    assert seen == ["b"]
    assert ev["name"] == "bp.item"
    assert ev["args"]["kind"] == "gossip_block" and ev["args"]["depth"] == 0


def _attesting_chain():
    """A harness chain two blocks in, with four single-bit attestations
    of one committee (the third signed over another message), and the
    slot advanced so their votes apply."""
    from lighthouse_tpu.testing.harness import BeaconChainHarness

    harness = BeaconChainHarness(n_validators=64)
    chain = harness.chain
    harness.extend_chain(2, attest=False)
    slot = harness.current_slot
    atts = harness.make_attestations(slot)
    committee = chain.committees_at(slot).committee(slot, 0)
    singles = [harness.single_attestation(atts[0], pos, committee)
               for pos in range(4)]
    singles[2].signature = harness.keys[committee[3]].sign(
        b"\x99" * 32).to_bytes()
    harness.advance_slot()
    return harness, singles


def test_attestation_batch_phases_and_verdicts(global_trace):
    """One batch with a repeated voter (caught by the gossip checks in
    item order) and a bad signature (caught by bisection): the verdicts
    of the single loop that the two passes replaced, and one span per
    phase."""
    from lighthouse_tpu.beacon_chain import (
        AttestationError,
        VerifiedUnaggregatedAttestation,
    )

    harness, singles = _attesting_chain()
    batch = [singles[0], singles[1], singles[2], singles[1], singles[3]]
    results = harness.chain.process_attestation_batch(batch)

    kinds = [r.kind if isinstance(r, AttestationError) else
             type(r).__name__ for r in results]
    verified = "VerifiedUnaggregatedAttestation"
    assert kinds == [verified, verified, "InvalidSignature",
                     "PriorAttestationKnown", verified]
    assert all(isinstance(results[i], VerifiedUnaggregatedAttestation)
               for i in (0, 1, 4))
    spans = {e["name"]: e for e in _spans(global_trace, "")
             if e["name"].startswith(("att.", "bls.bisect"))}
    assert set(spans) == {"att.checks", "att.set_build", "bls.bisect",
                          "att.import"}
    assert spans["att.checks"]["args"]["n"] == 5
    assert spans["att.set_build"]["args"]["n"] == 4
    assert spans["att.import"]["args"]["n"] == 3
    bis = spans["bls.bisect"]["args"]
    # 4 sets, one bad at index 2: [0,4) [0,2) [2,4) [2,3) [3,4).
    assert (bis["n"], bis["calls"], bis["bad"]) == (4, 5, 1)
    order = ["att.checks", "att.set_build", "bls.bisect", "att.import"]
    for a, b in zip(order, order[1:]):
        assert _end(spans[a]) <= spans[b]["ts"]


def test_single_attestation_phases_carry_n_1(global_trace):
    harness, singles = _attesting_chain()
    harness.chain.process_attestation(singles[0])
    spans = {e["name"]: e["args"]["n"] for e in _spans(global_trace, "att.")}
    assert spans == {"att.checks": 1, "att.set_build": 1, "att.import": 1}


def _sets(n, subgroup_checked=True):
    from lighthouse_tpu.crypto.bls import api

    out = []
    for i in range(n):
        sk = api.SecretKey(7000 + i)
        msg = bytes([i + 1]) * 32
        out.append(api.SignatureSet(
            signature=api.Signature(point=sk.sign(msg).point,
                                    subgroup_checked=subgroup_checked),
            signing_keys=[sk.public_key()], message=msg))
    return out


@pytest.fixture
def fake_core(monkeypatch):
    """The engine core replaced by one that answers True at once: staging
    runs for real, nothing compiles."""
    import jax.numpy as jnp

    from lighthouse_tpu.ops import backend as be

    monkeypatch.setattr(be, "_layout", lambda: "major")
    monkeypatch.setattr(be, "_jitted_core",
                        lambda *a, **k: lambda *args: jnp.asarray(True))
    return be


@pytest.mark.parametrize("route", ["host_reject", "native", "device"])
def test_bls_batches_total_counts_each_route(route, fake_core, monkeypatch):
    from lighthouse_tpu.crypto.bls import api

    be = fake_core
    sets = _sets(2)
    if route == "host_reject":
        sets[1] = api.SignatureSet(signature=api.Signature(point=None),
                                   signing_keys=sets[1].signing_keys,
                                   message=sets[1].message)
    monkeypatch.setenv("LIGHTHOUSE_TPU_CPU_FALLBACK_MAX",
                       "16" if route == "native" else "0")
    before = {r: be.batches_total().get(r)
              for r in ("host_reject", "native", "device")}
    ok = be.verify_signature_sets_tpu(sets, sharded=False)
    assert ok is (route != "host_reject")
    after = {r: be.batches_total().get(r) for r in before}
    assert {r: after[r] - before[r] for r in before} == \
        {r: float(r == route) for r in before}


def test_staging_phases_nest_and_the_wait_follows_dispatch(global_trace,
                                                          fake_core,
                                                          monkeypatch):
    monkeypatch.setenv("LIGHTHOUSE_TPU_CPU_FALLBACK_MAX", "0")
    assert fake_core.verify_signature_sets_tpu(_sets(3, False),
                                            sharded=False) is True
    spans = _spans(global_trace, "bls.")
    names = [e["name"] for e in spans]
    phases = ["bls.stage.h2f", "bls.stage.points", "bls.stage.scalars",
              "bls.stage.transfer"]
    assert sorted(names) == sorted(
        ["bls.stage", "bls.dispatch", "bls.device_wait"] + phases)
    by = {e["name"]: e for e in spans}
    stage = by["bls.stage"]
    assert stage["args"]["n"] == 3 and stage["args"]["n_bucket"] == 4
    for name in phases:
        assert by[name]["args"]["depth"] == stage["args"]["depth"] + 1
        assert stage["ts"] <= by[name]["ts"]
        assert _end(by[name]) <= _end(stage)
    assert [by[p]["ts"] for p in phases] == sorted(by[p]["ts"]
                                                   for p in phases)
    assert _end(stage) <= by["bls.dispatch"]["ts"]
    assert _end(by["bls.dispatch"]) <= by["bls.device_wait"]["ts"]
