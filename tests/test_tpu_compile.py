"""The chip's compiler accepts the batch-minor engine's stages.

Compiles two BM stages for one described TPU v5e chip (no chip attached:
the TPU compiler runs here and refuses what the chip would refuse), with
the constant multiply in its on-chip `matmul` lowering. The topology is
described inside a module fixture, never at import: only one process may
load the TPU library, and pytest's workers all import this file.
"""

import pytest

pytestmark = pytest.mark.filterwarnings("ignore")

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def chip_compile(monkeypatch):
    """Matmul lowering (what the chip runs; the CPU default is einsum) and
    no persistent cache: a described-chip executable cannot be read back
    here, so caching it would only warn on the next compile."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from lighthouse_tpu.ops.bm import limbs as blb

    monkeypatch.setattr(blb, "_MM", "matmul")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _avals(one_chip, n, k, m):
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops.bm import limbs as blb

    def sds(shape, dtype=blb.DTYPE):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s1 = (sds((2, 2, blb.L, m)),)
    s2 = (sds((k, 3, blb.L, n)), sds((3, 2, blb.L, n)),
          sds((n,), jnp.bool_), sds((n,), jnp.bool_),
          sds((n,), jnp.uint64), sds((n,), jnp.int32))
    return s1, s2


def _compile(fn, avals):
    compiled = fn.lower(*avals).compile()
    ma = compiled.memory_analysis()
    print(f"generated code {ma.generated_code_size_in_bytes} B, "
          f"temp {ma.temp_size_in_bytes} B, "
          f"args {ma.argument_size_in_bytes} B")
    assert ma.generated_code_size_in_bytes > 0
    assert (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes) < V5E_HBM_BYTES
    return compiled


def test_h2g2_stage_compiles_for_v5e(one_chip, chip_compile):
    """Stage 1 (hash-to-G2 of the distinct messages) at the m=64 floor
    every gossip batch stages at."""
    from lighthouse_tpu.ops.backend import BM_M_FLOOR
    from lighthouse_tpu.ops.bm import backend as bmb

    s1, _ = _avals(one_chip, 128, 1, BM_M_FLOOR)
    _compile(bmb._stage1_jit, s1)


def test_prepare_stage_compiles_for_v5e(one_chip, chip_compile):
    """Stage 2 (aggregation, subgroup check, scalar ladders, combine) at
    the first batch the node's default policy cuts from a gossip burst."""
    from lighthouse_tpu.ops.backend import BM_M_FLOOR
    from lighthouse_tpu.ops.bm import backend as bmb

    _, s2 = _avals(one_chip, 128, 1, BM_M_FLOOR)
    _compile(bmb._prepare_jit(BM_M_FLOOR, bmb.prep_chunk_width(128)), s2)
