"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh (the sharding layer is
designed for a real TPU mesh and validated here on forced host devices);
the chip path is exercised by chip_smoke.py on the chip itself. Tests
force the CPU backend both via the env var (before jax import) and the
config (after import, in case something imported jax earlier).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")
# Pytest reads the persistent compile cache (JAX_COMPILATION_CACHE_DIR when
# set, else <repo>/.jax_cache — lighthouse_tpu/ops/__init__.py) but never
# writes it: executable serialization segfaults sporadically in long
# many-module processes; scripts/warm_cache.py populates it instead.
os.environ.setdefault("LIGHTHOUSE_TPU_JAX_CACHE_READONLY", "1")
# Small batches must still exercise the JAX device kernels in tests (the
# production default routes <=16 sets to the native CPU verifier;
# tests/test_native_bls.py re-enables it explicitly).
os.environ.setdefault("LIGHTHOUSE_TPU_CPU_FALLBACK_MAX", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# The env var above is ineffective when jax was imported before this file
# runs; the config update always wins. Same for x64 (uint64 limbs would
# otherwise be silently truncated to uint32 in any test that skips ops/).
jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration test (simulator-scale)"
    )


def pytest_collection_modifyitems(config, items):
    """Run the compile-heavy kernel suites FIRST. XLA:CPU's compiler
    segfaults sporadically when large modules compile late in a LONG
    many-module process (observed repeatedly at test_ops_h2c /
    test_ops_pairing around the 50-75% mark; the same compiles succeed
    in young processes — see scripts/warm_cache.py). Stable sort keeps
    relative order within each group."""
    heavy = ("test_ops_", "test_backend", "test_bisection", "test_kzg",
             "test_sharded_bm", "test_bench_sweep")
    items.sort(
        key=lambda it: 0 if it.fspath.basename.startswith(heavy) else 1
    )
