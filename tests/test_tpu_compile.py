"""Compile-only checks of the jax engine's batched tick for a described
TPU v5e.

The TPU compiler is installed without a chip: it compiles for a described
topology and refuses what the chip would refuse.  Nothing runs.  The
topology is described inside a fixture, never at import, because only
one process at a time may load the TPU library.
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


@pytest.mark.parametrize("B,S", ((32, 18), (256, 18), (32, 1440),
                                 (256, 1440)))
def test_packed_event_step_compiles_for_v5e(one_chip, B, S):
    """The jax engine's tick (float64) at the benchmark's shapes (S = 18)
    and the metro deployment's (``dense-urban(n_nodes=480)``, S = 1440):
    one packed buffer in, one packed array out, one program."""
    from repro.kernels import event_core as kec
    w_in, w_out = kec.packed_widths(S)
    buf = jax.ShapeDtypeStruct((B, w_in), jnp.float64, sharding=one_chip)
    with jax.enable_x64(True):
        lowered = kec.event_step_jax_packed.lower(buf,
                                                  step=kec.event_step_jax)
        compiled = lowered.compile()
    out = lowered.out_info
    assert out.shape == (B, w_out) and out.dtype == jnp.float64
    assert compiled.memory_analysis() is not None
