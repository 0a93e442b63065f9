# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Pallas TPU kernels of the model stack and their jnp references, and
the simulator's jitted event core (:mod:`repro.kernels.event_core`, pure
jnp in float64).

``CompilerParams`` is re-exported from ``jax.experimental.pallas.tpu`` so
the kernel modules share one import.
"""
from jax.experimental.pallas.tpu import CompilerParams

__all__ = ["CompilerParams"]
