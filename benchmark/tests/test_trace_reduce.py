"""trace_reduce on a trace recorded here by a CPU run, and on a synthetic
TPU-shaped trace: busy union, idle gaps named by the benchmark's spans,
device time per executable.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import os
import sys
import time
from types import SimpleNamespace as NS

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 7), (0, 2), (1, 3), (4, 4), (6, 9)]) == \
        [(0, 3), (5, 9)]


def test_module_name_drops_program_id():
    assert tr.module_name("jit__h2g2(1234)") == "jit__h2g2"
    assert tr.module_name("jit__final_check") == "jit__final_check"


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def test_tpu_plane_busy_gaps_and_modules():
    """Window 0..100 ns; device busy 10..30 (h2g2) and 50..60 (prepare);
    the op lines are not read; host spans bench.call 0..100 and bench.bls
    40..70."""
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit__h2g2(7)", 10, 20),
                                       _ev("jit__prepare_pairs(9)", 50, 10)]),
        NS(name="XLA Ops", events=[_ev("fusion.1", 12, 8),
                                   _ev("fusion.2", 52, 5)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("bench.window", 0, 100), _ev("bench.call", 0, 100),
        _ev("bench.bls", 40, 30), _ev("other", 0, 5)])])
    red = tr.reduce(NS(planes=[host, device]),
                    priority=["bench.bls", "bench.call"])
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(30e-9)
    assert red["modules"] == pytest.approx({"jit__h2g2": 20e-9,
                                            "jit__prepare_pairs": 10e-9})
    # Gaps: 0-10 and 60-100 lie under bench.call only at their midpoints
    # (5, 80), 30-50 has its midpoint (40) in bench.bls.
    gaps = sorted((round(s * 1e9), n) for n, s in red["gaps"])
    assert gaps == [(10, "bench.call"), (20, "bench.bls"),
                    (40, "bench.call")]
    b = tr.breakdown(red, top=2)
    assert b["idle_gaps"] == [["bench.call", pytest.approx(40e-9)],
                              ["bench.bls", pytest.approx(20e-9)]]
    assert b["device_ops"] == [["jit__h2g2", pytest.approx(20e-9)],
                               ["jit__prepare_pairs", pytest.approx(10e-9)]]


def test_reduce_refuses_a_trace_without_window():
    with pytest.raises(ValueError):
        tr.reduce(NS(planes=[]))


def test_cpu_recorded_trace():
    """A real profiler trace, taken by the harness's Tracer: two jitted
    stages inside bench.call, a sleep inside bench.bls and one outside
    it; the CPU backend's op events stand for the device."""
    import shutil

    import jax
    import jax.numpy as jnp

    from benchmark.harness import Tracer

    @jax.jit
    def stage_a(x):
        return jnp.sin(x) @ x

    @jax.jit
    def stage_b(x):
        return jnp.cos(x) @ x

    x = jnp.ones((512, 512))
    stage_a(x).block_until_ready()
    stage_b(x).block_until_ready()
    tracer = Tracer(True, "test-trace-reduce")
    tracer.start()
    with jax.profiler.TraceAnnotation("bench.call"):
        stage_a(x).block_until_ready()
        time.sleep(0.2)                           # host work: bench.call
        with jax.profiler.TraceAnnotation("bench.bls"):
            stage_b(x).block_until_ready()
            time.sleep(0.05)                      # inside bench.bls
    tracer.stop()
    try:
        red = tr.reduce(tr.load(tracer.log_dir), ["bench.bls", "bench.call"])
    finally:
        shutil.rmtree(tracer.log_dir, ignore_errors=True)
    assert 0.25 <= red["window_s"] < 5
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["modules"].get("jit_stage_a", 0) > 0
    assert red["modules"].get("jit_stage_b", 0) > 0
    longest = red["gaps"][0]
    assert longest[0] == "bench.call" and longest[1] >= 0.18
    assert any(n == "bench.bls" and s >= 0.04 for n, s in red["gaps"])
    busy_plus_idle = red["busy_s"] + sum(s for _, s in red["gaps"])
    assert busy_plus_idle == pytest.approx(red["window_s"], rel=1e-6)
