"""CPU rehearsal of chip_smoke.py: its phase functions at a tiny size.

The script's device check stays strict (no TPU -> exit non-zero), so the
rehearsal calls the phases, not main(). Phase B runs one small gossip slot
through the node's BeaconProcessor and default batch policy, valid and
poisoned; Phase C runs n=8 sets against the native verifier. Both phases
share one (8, 1) batch shape, so the engine compiles once.
"""

import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


@pytest.fixture()
def stage_timing():
    from lighthouse_tpu.observability import stages

    stages.force_timing(True)
    yield
    stages.force_timing(False)


def test_phases_b_and_c_rehearsal(stage_timing):
    prepared = [chip_smoke.slot_attestations(4000, 2) for _ in range(2)]
    assert len(prepared[0][1]) == 8
    assert chip_smoke.planned_batches(prepared[0][1]) == [(8, 4)]
    b = chip_smoke.phase_b(prepared, n_poison=1)
    assert b["valid"]["imported"] == 8 and b["valid"]["batch_sizes"] == [8]
    assert b["poisoned"]["rejected"] == b["poisoned"]["poisoned"] == [4]

    c = chip_smoke.phase_c(chip_smoke.distinct_sets(8, 1))
    assert c["valid"] == {**c["valid"], "device": True, "native": True}
    assert c["poisoned"] == {**c["poisoned"], "device": False,
                             "native": False}
    assert c["find_invalid"]["found"] == [c["poisoned_index"]]


def test_planned_batches_follow_the_default_policy(monkeypatch):
    """The compile-ahead list comes from the node's own policy: a mainnet
    slot's queue grows 128 -> 4096 and the BM m floor folds every batch's
    distinct-message bucket onto one (stage 1/3 compile once)."""

    class Att:
        def __init__(self, data):
            self.data = data

    committees = [object() for _ in range(64)]
    atts = [Att(committees[i // 244]) for i in range(64 * 244)]
    plan = chip_smoke.planned_batches(atts)
    assert [n for n, _ in plan][:6] == [128, 256, 512, 1024, 2048, 4096]
    assert sum(n for n, _ in plan) == len(atts)
    monkeypatch.delenv("LIGHTHOUSE_TPU_CPU_FALLBACK_MAX")  # node default
    shapes = chip_smoke.device_shapes(plan, 1)
    assert {m for _, _, m in shapes} == {64}
    assert {n for n, _, _ in shapes} == {128, 256, 512, 1024, 2048, 4096}


def test_refuses_without_tpu():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], cwd=ROOT,
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
