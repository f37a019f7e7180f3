"""CPU rehearsal of both cells at their tiny `rehearsal` sizes, as a user
runs it, and the refusal to run for real without a TPU.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

A rehearsal prints no metric and no result line: a number from the CPU
never appears under a device metric's name.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmark", "run.py")
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       # every bulk call of 8 sets goes to the device engine, not native
       "LIGHTHOUSE_TPU_CPU_FALLBACK_MAX": "0"}


def _run(*args, env=ENV):
    return subprocess.run([sys.executable, RUN, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=1200)


@pytest.mark.parametrize("workload,seconds,env", [
    ("triples-4096-distinct", "2", ENV),
    ("gossip-2-subnets", "12", {**os.environ, "JAX_PLATFORMS": "cpu"}),
])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_runs_and_prints_no_result(workload, seconds, env, trace):
    proc = _run("--workload", workload, "--seed", str(2**31 + 7),
                "--seconds", seconds, "--trace", trace, "--rehearse",
                env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip() == ""
    tail = proc.stderr.strip().splitlines()
    checks = [ln.rstrip(")").split(": ")[1].split(" (limit ")
              for ln in tail if ln.startswith("check ")]
    assert checks and all(float(v) <= float(lim) for v, lim in checks), \
        checks
    assert "correct=True" in tail[-1]


def test_refuses_without_tpu():
    proc = _run("--workload", "triples-4096-distinct", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_refuses_an_unknown_workload():
    proc = _run("--workload", "nope", "--seed", "1", "--seconds", "1",
                "--trace", "0", "--rehearse")
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        traffic = os.path.join(ROOT, "benchmark", "traffic",
                               w["traffic"] + ".json")
        with open(traffic) as fh:
            driver = json.load(fh)["driver"]
        assert os.path.exists(os.path.join(ROOT, "benchmark", "drivers",
                                           driver + ".py"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
