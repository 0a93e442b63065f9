"""Compile-only checks of the Pallas kernels for a described TPU v5e.

The TPU compiler is installed without a chip: it compiles for a described
topology and refuses what the chip would refuse (block shapes off the
(8, 128) tiling, scalar stores to VMEM, 64-bit element types).  Nothing
runs.  The topology is described inside a fixture, never at import,
because only one process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _tpu_kernel_count(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("B,S", ((32, 1536), (256, 4096)))
def test_event_step_compiles_for_v5e(one_chip, B, S):
    from repro.kernels.event_step import event_step

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    block, mask, col = sds((B, S), jnp.float32), sds((B, S), jnp.bool_), \
        sds((B,), jnp.float32)
    step = jax.jit(lambda *a: event_step(*a, interpret=False))
    compiled = step.lower(block, block, block, block, mask, col, col,
                          sds((B,), jnp.bool_)).compile()
    assert _tpu_kernel_count(compiled) >= 1


@pytest.mark.parametrize("N,S", ((480, 128), (8, 1536)))
def test_alloc_active_set_compiles_for_v5e(one_chip, monkeypatch, N, S):
    from repro.kernels import ops

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # the wrapper picks interpret mode from the default backend (the CPU
    # here); compile the kernel path the chip takes
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    block = sds((N, S), jnp.float32)
    compiled = jax.jit(ops.alloc_active_set).lower(
        block, block, block, sds((N,), jnp.float32),
        sds((N, S), jnp.bool_)).compile()
    assert _tpu_kernel_count(compiled) >= 1



@pytest.mark.parametrize("B", (32, 256))
def test_packed_event_step_compiles_for_v5e(one_chip, B):
    """The jax engine's tick at the benchmark's shapes (S = 18, float64):
    one packed buffer in, one packed array out, one program."""
    from repro.kernels import event_core as kec
    w_in, w_out = kec.packed_widths(18)
    buf = jax.ShapeDtypeStruct((B, w_in), jnp.float64, sharding=one_chip)
    with jax.enable_x64(True):
        lowered = kec.event_step_jax_packed.lower(buf,
                                                  step=kec.event_step_jax)
        compiled = lowered.compile()
    out = lowered.out_info
    assert out.shape == (B, w_out) and out.dtype == jnp.float64
    assert compiled.memory_analysis() is not None
