"""The batched event-engine step as a Pallas TPU kernel.

One grid step per block of ``ROWS`` replicas: the kernel fuses the
per-row completion scan (masked min + first-index argmin over the padded
instance lanes) with the advance-to-next-event update (Eq. 1 stage
ordering), so one kernel launch moves the whole ``[B, S]`` block of a
``Simulator.run_batch`` tick.  The replica clocks ``t[b]``, heap heads
``t_ev[b]`` and live flags ride along as ``(ROWS, 1)`` column blocks,
making the kernel self-contained: the host only drains the per-replica
discrete events between launches.

Block layout (what the TPU compiler accepts): the replica dimension is
padded to a multiple of ``ROWS`` (8 sublanes) with dead rows
(``live = 0``, every lane unavailable), and the instance dimension to a
lane multiple (128) with unavailable lanes (``avail = 0`` -> candidate
``+inf``).  Row blocks are ``(ROWS, S_pad)``, per-row values
``(ROWS, 1)``, and every reduction is a keepdims lane reduction.

The kernel computes in the dtype of ``rem_g``; callers choose it.  Mosaic
has no 64-bit element type, so the compiled kernel takes 32-bit state;
off-TPU it runs in interpret mode (the CPU fallback used by the
equivalence tests), where it keeps float64 and is held to the same
discrete-outcome bar as the jnp backend in
:mod:`repro.kernels.event_core`.

Like every module in this package, importing it requires jax.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import LANES, ROWS, CompilerParams


def _event_step_kernel(rem_g_ref, rem_c_ref, ag_ref, ac_ref, avail_ref,
                       t_ref, tev_ref, live_ref,
                       rg_out, rc_out, started_out, tcomp_out, sid_out):
    rg = rem_g_ref[...]                               # [ROWS, S]
    rc = rem_c_ref[...]
    ag = ag_ref[...]
    ac = ac_ref[...]
    avail = avail_ref[...] > 0
    t = t_ref[...]                                    # [ROWS, 1]
    t_ev = tev_ref[...]
    live = live_ref[...] > 0

    # completion scan: a pending stage with zero rate divides to +inf and
    # can never win the min — such heads wait for a reallocation event
    dt_g = jnp.where(rg > 0.0, rg / ag, 0.0)
    dt_c = jnp.where(rc > 0.0, rc / ac, 0.0)
    cand = jnp.where(avail, t + (dt_g + dt_c), jnp.inf)
    t_comp = jnp.min(cand, axis=1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, cand.shape, 1)
    sid = jnp.min(jnp.where(cand == t_comp, lane, cand.shape[-1]),
                  axis=1, keepdims=True)

    # advance to the earlier of (completion, heap head); dead rows freeze
    t_next = jnp.minimum(t_comp, t_ev)
    dt = jnp.where(live & jnp.isfinite(t_next), t_next - t, 0.0)

    gpu_need = rg > 0.0
    run_g = avail & gpu_need & (ag > 0.0) & (dt > 0.0)
    stalled = avail & gpu_need & (ag <= 0.0)
    tg = jnp.where(run_g, jnp.minimum(dt, rg / ag), 0.0)
    rg_new = rg - jnp.where(run_g, ag * tg, 0.0)
    rem_dt = jnp.where(run_g, dt - tg, dt)
    cpu_ok = (avail & ~stalled & (rg_new <= 0.0) & (rem_dt > 0.0)
              & (rc > 0.0) & (ac > 0.0))
    tc = jnp.where(cpu_ok, jnp.minimum(rem_dt, rc / ac), 0.0)

    rg_out[...] = rg_new
    rc_out[...] = rc - jnp.where(cpu_ok, ac * tc, 0.0)
    started_out[...] = (run_g | cpu_ok).astype(jnp.int32)
    tcomp_out[...] = t_comp
    sid_out[...] = sid


@functools.partial(jax.jit, static_argnames=("interpret",))
def _event_step_call(rem_g, rem_c, alloc_g, alloc_c, avail, t, t_ev, live,
                     *, interpret: bool):
    """Kernel-native shapes: ``[B, S]`` blocks with ``B % ROWS == 0`` and
    ``S % LANES == 0``; per-row columns ``[B, 1]``."""
    B, S = rem_g.shape
    dtype = rem_g.dtype
    row = pl.BlockSpec((ROWS, S), lambda b: (b, 0))
    col = pl.BlockSpec((ROWS, 1), lambda b: (b, 0))
    return pl.pallas_call(
        _event_step_kernel,
        grid=(B // ROWS,),
        in_specs=[row, row, row, row, row, col, col, col],
        out_specs=[row, row, row, col, col],
        out_shape=[
            jax.ShapeDtypeStruct((B, S), dtype),
            jax.ShapeDtypeStruct((B, S), dtype),
            jax.ShapeDtypeStruct((B, S), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), dtype),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(rem_g, rem_c, alloc_g, alloc_c, avail, t, t_ev, live)


def event_step(rem_g, rem_c, alloc_g, alloc_c, avail, t, t_ev, live,
               interpret: bool = True):
    """Pad to the kernel's block layout and run the kernel.

    Returns ``(rem_g', rem_c', started, t_comp [B], sid [B])`` with the
    padding stripped — the same contract as
    :func:`repro.kernels.event_core.event_step_jax`.
    """
    rem_g = jnp.asarray(rem_g)
    dtype = rem_g.dtype
    B, S = rem_g.shape
    pad_b = -(-B // ROWS) * ROWS - B
    pad_s = max(-(-S // LANES) * LANES, LANES) - S

    def block(x, value=0, dt=dtype):
        x = jnp.asarray(x, dt)
        return jnp.pad(x, ((0, pad_b), (0, pad_s)), constant_values=value)

    def column(x, dt=dtype):
        return jnp.pad(jnp.asarray(x, dt), (0, pad_b))[:, None]

    # padded lanes and rows: avail=0 makes their candidates +inf and
    # live=0 freezes padded rows; alloc=1 keeps the divisions finite so
    # no NaNs leak into the lane min
    rg, rc, started, t_comp, sid = _event_step_call(
        block(rem_g), block(rem_c), block(alloc_g, 1), block(alloc_c, 1),
        block(avail, dt=jnp.int32), column(t), column(t_ev),
        column(live, jnp.int32), interpret=bool(interpret))
    return (rg[:B, :S], rc[:B, :S], started[:B, :S] > 0,
            t_comp[:B, 0], sid[:B, 0])
