"""benchmark/program_spans on a synthetic profile (idle attribution over
nested spans on two threads, with a gap under no span), and a CPU
rehearsal of each cell with `--trace 1` that reads every per-layer
metric of the program's spans.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import program_spans as ps  # noqa: E402


def _ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start, stats=[])


def _profile():
    """Window 0..100 ns. Thread A: bp.batch 0-80 holding att.checks 5-20
    and bls.stage 30-60, which holds bls.stage.h2f 30-45. Thread B:
    att.import 50-70. Device busy 20-30, 45-50, 85-90."""
    thread_a = NS(name="a", events=[
        _ev("bench.window", 0, 100), _ev("bp.batch", 0, 80),
        _ev("att.checks", 5, 20), _ev("bls.stage", 30, 60),
        _ev("bls.stage.h2f", 30, 45), _ev("bench.batch", 1, 79),
        _ev("PjitFunction", 21, 22)])
    thread_b = NS(name="b", events=[_ev("att.import", 50, 70)])
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=[
        _ev("jit__h2g2(1)", 20, 30), _ev("jit__prepare_pairs(2)", 45, 50),
        _ev("jit__final_check(3)", 85, 90)])])
    return NS(planes=[NS(name="/host:CPU", lines=[thread_a, thread_b]),
                      device])


def test_depth_is_nesting_on_the_spans_own_thread():
    depth = {n: d for n, _, _, d in ps.program_spans(_profile())}
    assert depth == {"bp.batch": 1, "att.checks": 2, "bls.stage": 2,
                     "bls.stage.h2f": 3, "att.import": 1}


def test_idle_goes_to_the_deepest_open_span():
    """Idle 0-20, 30-45, 50-85, 90-100. 0-5 bp.batch, 5-20 att.checks,
    30-45 bls.stage.h2f, 50-60 bls.stage (depth 2 over att.import's 1),
    60-70 att.import (a tie at depth 1 with bp.batch: the later start),
    70-80 bp.batch; 80-85 and 90-100 lie under no program span."""
    out = ps.summarize(_profile())
    assert out["window_s"] == pytest.approx(100e-9)
    ns = {k: round(v * 1e9, 6) for k, v in out["idle_s"].items()}
    assert ns == {"bp.batch": 15, "att.checks": 15, "bls.stage.h2f": 15,
                  "bls.stage": 10, "att.import": 10}
    assert ps.idle_under(out, "bls.stage") == pytest.approx(25e-9)
    assert {k: round(v * 1e9, 6) for k, v in out["seconds"].items()} == {
        "bp.batch": 80, "att.checks": 15, "bls.stage": 30,
        "bls.stage.h2f": 15, "att.import": 20}
    assert set(out["count"].values()) == {1}


def test_spans_are_clipped_to_the_window():
    prof = _profile()
    prof.planes[0].lines[1].events.append(_ev("att.import", 95, 130))
    prof.planes[0].lines[1].events.append(_ev("att.checks", 150, 160))
    out = ps.summarize(prof)
    assert out["seconds"]["att.import"] == pytest.approx(25e-9)
    assert out["count"]["att.import"] == 2
    assert out["seconds"]["att.checks"] == pytest.approx(15e-9)
    # 95-100 was idle under no span; att.import now owns it.
    assert out["idle_s"]["att.import"] == pytest.approx(15e-9)


def test_reader_refuses_another_window():
    assert ps.read({}) is None
    assert ps.read({"trace": None}) is None


METRICS = {
    "gossip-2-subnets": ["att_gossip_checks_ms_per_att.gossip",
                         "att_set_build_ms_per_att.gossip",
                         "att_import_ms_per_att.gossip",
                         "bls_device_wait_ms_per_att.gossip"],
    "triples-4096-distinct": ["staging_us_per_set.bulk",
                              "idle_in_staging_share.bulk"],
}
# In-process so the result object, which a rehearsal does not print, can
# be read: the names of the metrics it holds, and the two shares to
# compare.
PROBE = """
import json, sys
sys.path.insert(0, {root!r})
from benchmark import run
r = run.run(run.parse({argv!r}))
m = r["metrics"]
print(json.dumps({{"correct": r["correct"], "present": sorted(m),
                  "shares": [m.get(k, {{}}).get("value") for k in
                             ("idle_in_staging_share.bulk",
                              "device_idle_share.bulk")]}}))
"""


@pytest.mark.parametrize("workload,seconds,env", [
    # every bulk call of 8 sets goes to the device engine, not native
    ("triples-4096-distinct", "2", {"LIGHTHOUSE_TPU_CPU_FALLBACK_MAX": "0"}),
    # every batch of two or more attestations goes to the device engine,
    # on the batch-minor layout whose distinct-message floor gives all of
    # them the m bucket the warm-up compiles
    ("gossip-2-subnets", "12", {"LIGHTHOUSE_TPU_CPU_FALLBACK_MAX": "1",
                                "LIGHTHOUSE_TPU_LAYOUT": "bm"}),
])
def test_rehearsal_reads_the_program_span_metrics(workload, seconds, env):
    argv = ["--workload", workload, "--seed", str(2**31 + 11), "--seconds",
            seconds, "--trace", "1", "--rehearse"]
    proc = subprocess.run(
        [sys.executable, "-c", PROBE.format(root=ROOT, argv=argv)],
        cwd=ROOT, capture_output=True, text=True, timeout=1800,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["correct"]
    assert set(METRICS[workload]) <= set(got["present"]), got["present"]
    if workload.startswith("triples"):
        staging_idle, idle = got["shares"]
        assert 0 <= staging_idle <= idle
