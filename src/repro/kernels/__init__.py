# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Pallas TPU kernels and their jnp/numpy references.

``CompilerParams`` is re-exported from ``jax.experimental.pallas.tpu`` so
the kernel modules share one import.  ``ROWS`` x ``LANES`` is the 32-bit
vector tile the TPU compiler lays blocks out on: the simulator kernels
take row blocks of ``ROWS`` rows over a lane dimension padded to a
multiple of ``LANES``.
"""
from jax.experimental.pallas.tpu import CompilerParams

ROWS = 8
LANES = 128

__all__ = ["CompilerParams", "LANES", "ROWS"]
