"""`correct` comes out false when the timed path is broken underneath.

Each case drives a whole run in this process (the look for a chip
skipped, the CPU at the cells' rehearsal sizes) with a fault planted in
the BLS seam under the program's entry:

  half        half of each call's sets left out of the check (the
              control of the bulk cell: a spot-checked, approximate
              verdict);
  flip        every verdict altered where it is produced;
  no_isolate  a failed batch rejects every attestation in it (the
              control of the gossip cell: no bisection);
  no_subgroup the device skips the G2 subgroup check of the signatures;
  memo        sets that passed in an earlier call are not verified
              again: every verdict stays right, and only
              `repeat_speedup` sees it.

A run with `--seconds 0` makes exactly one bulk call, so its draws, and
whether a fault shows, depend on the seed alone.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402


def _traffic(monkeypatch, **override):
    """The cells' traffic files with `override` on top, at the rehearsal
    sizes too."""
    from benchmark import harness

    load = harness.load_cell

    def load_cell(workload):
        spec = load(workload)
        t = spec["traffic"]
        spec["traffic"] = dict(t, **override, rehearsal=dict(
            t.get("rehearsal", {}), **override))
        return spec

    monkeypatch.setattr(harness, "load_cell", load_cell)


def _correct(workload, seed, seconds, fault=""):
    args = bench_run.parse(["--workload", workload, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", "0",
                            "--rehearse", "--fault", fault])
    return bench_run.run(args)


@pytest.fixture
def device_engine(monkeypatch):
    """Bulk calls of 8 sets go to the device engine, not the native
    answer the program gives batches of at most 16 sets."""
    monkeypatch.setenv("LIGHTHOUSE_TPU_CPU_FALLBACK_MAX", "0")


@pytest.fixture(autouse=True)
def _restore_backend(monkeypatch):
    """Each run registers its wrappers on the BLS seam: put the program's
    own functions back afterwards. Gossip runs keep the program's default
    native answer for small batches."""
    monkeypatch.delenv("LIGHTHOUSE_TPU_CPU_FALLBACK_MAX", raising=False)
    yield
    from lighthouse_tpu.crypto.bls import api
    from lighthouse_tpu.ops import backend as be

    api.register_backend("tpu", be.verify_signature_sets_tpu)
    api.register_bisect_verifier("tpu", be.pinned_verifier)


def test_bulk_sound_runs_are_correct(device_engine):
    for seed in (11, 2**33 + 5):
        res = _correct("triples-4096-distinct", seed, 0)
        assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault,seeds", [("flip", [11]),
                                         ("half", list(range(1, 13)))])
def test_bulk_fault_makes_a_run_incorrect(fault, seeds, device_engine):
    outcomes = [_correct("triples-4096-distinct", s, 0, fault)
                for s in seeds]
    assert any(not r["correct"] for r in outcomes)
    bad = [r for r in outcomes if not r["correct"]]
    assert all(r["checks"]["wrong_verdicts"]["value"] > 0 for r in bad)


@pytest.mark.parametrize("fault,seeds", [("flip", [21]),
                                         ("no_isolate", [21]),
                                         ("half", list(range(21, 27)))])
def test_gossip_fault_makes_a_run_incorrect(fault, seeds):
    """At the rehearsal size the attestations of a slot arrive within
    50 ms, so batches form and go through find_invalid_sets."""
    outcomes = [_correct("gossip-2-subnets", s, 6, fault) for s in seeds]
    assert any(not r["correct"] for r in outcomes)


def test_gossip_sound_run_is_correct():
    res = _correct("gossip-2-subnets", 2**32 + 3, 6)
    assert res["correct"], res["checks"]


class _Multiples13:
    """The program's scalar draw, each scalar a multiple of 13."""

    @staticmethod
    def randbits(k):
        import secrets

        return 13 * (secrets.randbits(k - 4) | 1)


def test_bulk_skipped_subgroup_check_makes_a_run_incorrect(
        device_engine, monkeypatch):
    """A signature plus a point of order 13 passes the pairing when the
    batch scalar of its set is a multiple of 13: one call in 13 on the
    chip, every call here. Only the subgroup check rejects it then."""
    from lighthouse_tpu.ops import backend as be

    monkeypatch.setattr(be, "secrets", _Multiples13)
    _traffic(monkeypatch, poisoned_share=1.0,
             invalid={"wrong_message": 0, "subgroup": 2},
             recheck_invalid={"subgroup": 1})
    sound = _correct("triples-4096-distinct", 31, 0)
    assert sound["correct"], sound["checks"]
    res = _correct("triples-4096-distinct", 31, 0, "no_subgroup")
    assert not res["correct"]
    assert res["checks"]["wrong_verdicts"]["value"] > 0


def test_bulk_verdict_cache_makes_a_run_incorrect(device_engine,
                                                  monkeypatch):
    _traffic(monkeypatch, poisoned_share=0.0)
    res = _correct("triples-4096-distinct", 41, 4, "memo")
    assert not res["correct"]
    assert res["checks"]["wrong_verdicts"]["value"] == 0
    assert res["checks"]["repeat_speedup"]["value"] > 0.5
