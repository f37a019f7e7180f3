"""CPU-tier smoke of the bench.py shape sweep (round 6: the sweep grew
an n-cap + injectable shape list so CI can drive it at toy shapes).

The real sweep times production buckets (minutes of XLA per shape cold);
this smoke drives the SAME code path at shapes whose cores other suites
in this process already compile — it catches staging-shape drift between
bench.py and the engines (the sweep builds its own synthetic tensors),
not performance.
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import bench  # noqa: E402  (repo-root module)


def _check_rows(rows, shapes):
    assert [(r["n"], r["k"], r["distinct"]) for r in rows] == shapes
    for r in rows:
        assert "error" not in r, r
        assert r["sigs_per_sec"] > 0
        assert r["secs"] >= 0


def test_shape_sweep_major_smoke():
    from lighthouse_tpu.ops import backend as be

    shapes = [(4, 2, 4), (4, 2, 2)]
    _check_rows(bench._shape_sweep(be, shapes), shapes)


def test_shape_sweep_bm_smoke(monkeypatch):
    monkeypatch.setenv("LIGHTHOUSE_TPU_LAYOUT", "bm")
    from lighthouse_tpu.ops import backend as be

    shapes = [(8, 2, 8)]
    _check_rows(bench._shape_sweep(be, shapes), shapes)


def test_all_distinct_row_selection():
    """The first-class all-distinct metric picks the LARGEST sweep row
    with distinct == n at the headline k, skipping errored rows."""
    sweep = [
        {"n": 2048, "k": 4, "distinct": 64, "sigs_per_sec": 14000.0},
        {"n": 1024, "k": 4, "distinct": 1024, "sigs_per_sec": 3100.0},
        {"n": 4096, "k": 4, "distinct": 4096, "sigs_per_sec": 3600.0},
        {"n": 1024, "k": 1, "distinct": 1024, "sigs_per_sec": 9999.0},
        {"n": 8192, "k": 4, "distinct": 8192, "error": "OOM"},
    ]
    row = bench._all_distinct_row(sweep)
    assert (row["n"], row["sigs_per_sec"]) == (4096, 3600.0)
    assert bench._all_distinct_row(None) == {}
    assert bench._all_distinct_row([]) == {}


def test_default_sweep_cap_override(monkeypatch):
    """The default shape list is the full menu (8192 rungs included) and
    LIGHTHOUSE_TPU_BENCH_SWEEP_MAX_N caps it."""
    monkeypatch.delenv("LIGHTHOUSE_TPU_BENCH_SWEEP_MAX_N", raising=False)
    full = bench._default_sweep_shapes()
    assert (8192, 4, 8192) in full and (8192, 4, 64) in full
    monkeypatch.setenv("LIGHTHOUSE_TPU_BENCH_SWEEP_MAX_N", "1024")
    capped = bench._default_sweep_shapes()
    assert max(n for n, _, _ in capped) == 1024
    assert capped == [s for s in full if s[0] <= 1024]


def test_bench_refuses_without_tpu():
    """bench.py never falls back to the CPU: no TPU, no result line,
    non-zero exit."""
    import subprocess

    root = pathlib.Path(__file__).resolve().parents[1]
    proc = subprocess.run(
        [sys.executable, str(root / "bench.py")], cwd=root,
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
