"""The paper's closed-form deadline-aware allocator as a Pallas TPU kernel.

This is the fast-timescale hot path of HAF (§III-C) scaled out TPU-natively:
one grid step per block of ``ROWS`` nodes, solving the Eq. 17–19
active-set fixed point on VMEM-resident instance vectors.  A fleet
controller batches every node's allocation into a single device call —
the paper's per-node millisecond CPU loop becomes one vectorized kernel
launch for thousands of nodes.

The active-set iteration is a fixed S-step ``fori_loop`` (the pinned set
grows monotonically, so S steps guarantee convergence).  Block layout:
node rows come in ``(ROWS, S)`` blocks (8 sublanes), capacities and
feasibility flags in ``(ROWS, 1)`` column blocks, and every reduction is
a keepdims lane reduction over the padded instance dimension (a multiple
of 128).  :func:`repro.kernels.ops.alloc_active_set` pads both dimensions
(padded rows and lanes carry ``mask = 0``).
"""
# repro: allow-file(float-dtype): this kernel is f32 BY DESIGN — it
# solves the Eq. 17-19 fixed point in TPU VMEM (f32 lanes) and is held
# to the f64 reference by tolerance-based parity tests, not the
# bit-for-bit event-schedule contract.
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ROWS, CompilerParams

EPS = 1e-9


def _alloc_kernel(psi_ref, omega_ref, floors_ref, cap_ref, mask_ref,
                  alloc_ref, feas_ref, pinned_ref, *, n_iter: int):
    psi = jnp.maximum(psi_ref[...].astype(jnp.float32), 0.0)    # [ROWS, S]
    omega = jnp.maximum(omega_ref[...].astype(jnp.float32), 0.0)
    floors = jnp.maximum(floors_ref[...].astype(jnp.float32), 0.0)
    mask = mask_ref[...] > 0
    capacity = cap_ref[...]                                     # [ROWS, 1]

    psi = jnp.where(mask, psi, 0.0)
    omega = jnp.where(mask, omega, 0.0)
    floors = jnp.where(mask, floors, 0.0)

    def rowsum(x):
        return jnp.sum(x, axis=1, keepdims=True)

    w = jnp.sqrt(omega * psi)                                   # Eq. 17
    floor_sum = rowsum(floors)
    feasible = floor_sum <= capacity + 1e-6
    scale = jnp.where(feasible, 1.0, capacity / jnp.maximum(floor_sum, EPS))
    floors_eff = floors * scale

    # the pinned set is carried as int32: Mosaic cannot carry a boolean
    # vector through a loop
    pinned0 = (w <= 0.0).astype(jnp.int32)

    def body(_, pinned_i):
        pinned = pinned_i > 0
        rem = capacity - rowsum(jnp.where(pinned, floors_eff, 0.0))
        denom = rowsum(jnp.where(pinned, 0.0, w))
        prop = w * jnp.maximum(rem, 0.0) / jnp.maximum(denom, EPS)
        return (pinned | (prop < floors_eff)).astype(jnp.int32)

    pinned = jax.lax.fori_loop(0, n_iter, body, pinned0) > 0

    rem = capacity - rowsum(jnp.where(pinned, floors_eff, 0.0))    # Eq. 19
    denom = rowsum(jnp.where(pinned, 0.0, w))
    share = w * jnp.maximum(rem, 0.0) / jnp.maximum(denom, EPS)    # Eq. 18
    alloc = jnp.where(pinned, floors_eff, share)
    alloc = jnp.where(mask, alloc, 0.0)

    alloc_ref[...] = alloc
    feas_ref[...] = feasible.astype(jnp.int32)
    pinned_ref[...] = (pinned & mask).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def alloc_active_set_ns(psi: jax.Array, omega: jax.Array, floors: jax.Array,
                        capacity: jax.Array, mask: jax.Array, *,
                        interpret: bool = False):
    """All inputs [N, S] (N a multiple of ROWS, S of 128); capacity [N, 1].

    Returns (alloc [N, S] f32, feasible [N, 1] i32, pinned [N, S] i32).
    """
    N, S = psi.shape
    kernel = functools.partial(_alloc_kernel, n_iter=S)
    row = pl.BlockSpec((ROWS, S), lambda n: (n, 0))
    col = pl.BlockSpec((ROWS, 1), lambda n: (n, 0))
    return pl.pallas_call(
        kernel,
        grid=(N // ROWS,),
        in_specs=[row, row, row, col, row],
        out_specs=[row, col, row],
        out_shape=[
            jax.ShapeDtypeStruct((N, S), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
            jax.ShapeDtypeStruct((N, S), jnp.int32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(psi, omega, floors, capacity, mask)
