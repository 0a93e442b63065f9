"""jax backend for the simulator's per-event hot pair (Eq. 1 stage model).

The fused step mirrors :class:`repro.sim.event_core.NumpyEventCore`
element-for-element in float64 — the event schedule is a chain of IEEE-754
double divisions; float32 would desync the engines within a handful of
events.  XLA may still fuse multiply-adds, so event times can differ from
the scalar/numpy pair by ulps (the bit-for-bit contract binds scalar and
numpy; this backend is held to identical discrete outcomes).  Callers
must run inside ``jax.enable_x64(True)`` (the
:class:`~repro.sim.event_core.JaxEventCore` wrapper does); the flag is
deliberately NOT flipped globally so the rest of the process keeps jax's
default dtypes.  On CPU the per-event dispatch makes
this slower than numpy; the backend exists as the accelerator-resident
growth path.  :func:`event_step_jax` is the batched form: the [S]
vectors become [B, S] blocks (B seeds of one scenario×method cell in
lockstep, one fused device call per tick), and the same expressions are
a Pallas TPU kernel in :mod:`repro.kernels.event_step` alongside
:mod:`repro.kernels.alloc_active_set` (lane reductions over the padded
instance dimension).  :func:`event_step_jax_packed` runs the batched
step on one packed buffer each way, so a tick is one transfer in and one
out.

Like every module in this package, importing it requires jax; the
simulator only imports it when ``engine="jax"`` is selected.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

INF = jnp.inf


@jax.jit
def next_completion_jax(rem_g: jax.Array, rem_c: jax.Array,
                        alloc_g: jax.Array, alloc_c: jax.Array,
                        avail: jax.Array, t: float):
    """Earliest head completion honoring GPU-then-CPU stage ordering.

    A pending stage with zero allocation divides to +inf and can never be
    the argmin — such heads wait for a reallocation event.  Returns
    ``(t_next, sid)``; ``t_next`` is +inf when nothing can complete.
    """
    with jax.named_scope("event_core.next_completion"):
        dt_g = jnp.where(rem_g > 0.0, rem_g / alloc_g, 0.0)
        dt_c = jnp.where(rem_c > 0.0, rem_c / alloc_c, 0.0)
        cand = jnp.where(avail, t + (dt_g + dt_c), INF)
        sid = jnp.argmin(cand)
        return cand[sid], sid


@jax.jit
def advance_jax(rem_g: jax.Array, rem_c: jax.Array,
                alloc_g: jax.Array, alloc_c: jax.Array,
                act: jax.Array, dt: float):
    """Fused ``advance``: progress served heads by ``dt`` without crossing
    the GPU->CPU stage boundary; stalled GPU stages freeze the head.

    Returns ``(rem_g', rem_c', started)`` — the progressed residuals and
    the mask of heads that progressed (Ψ aggregates are derived from the
    residuals by :class:`~repro.sim.cluster.ClusterState`, so no work
    deltas travel back).
    """
    with jax.named_scope("event_core.advance"):
        gpu_need = rem_g > 0.0
        run_g = act & gpu_need & (alloc_g > 0.0)
        stalled = act & gpu_need & (alloc_g <= 0.0)
        tg = jnp.where(run_g, jnp.minimum(dt, rem_g / alloc_g), 0.0)
        dg = jnp.where(run_g, alloc_g * tg, 0.0)
        rg_new = rem_g - dg
        rem_dt = jnp.where(run_g, dt - tg, dt)
        cpu_ok = (act & ~stalled & (rg_new <= 0.0) & (rem_dt > 0.0)
                  & (rem_c > 0.0) & (alloc_c > 0.0))
        tc = jnp.where(cpu_ok, jnp.minimum(rem_dt, rem_c / alloc_c), 0.0)
        dc = jnp.where(cpu_ok, alloc_c * tc, 0.0)
        return rg_new, rem_c - dc, run_g | cpu_ok


@jax.jit
def event_step_jax(rem_g: jax.Array, rem_c: jax.Array,
                   alloc_g: jax.Array, alloc_c: jax.Array,
                   avail: jax.Array, t: jax.Array, t_ev: jax.Array,
                   live: jax.Array):
    """Fused batched step over ``[B, S]`` blocks: per-row completion scan
    + advance-to-next-event, with per-replica clocks ``t[b]`` and heap
    heads ``t_ev[b]``.  Rows with ``live[b]`` down (drained replicas or
    replicas at their event budget) advance by ``dt = 0``.

    Returns ``(rem_g', rem_c', started, t_comp [B], sid [B])`` — the
    step of each lockstep tick of ``Simulator.run_batch``, whose operands
    and results cross as one packed buffer each way
    (:func:`event_step_jax_packed`).  This is the jnp form of the Pallas
    kernel in :mod:`repro.kernels.event_step`; both evaluate the
    expressions of the numpy batched core elementwise.

    The ``maximum(·, 0)`` around each work product is exact (the product
    is never negative where it is taken).  It keeps XLA:CPU from
    contracting the product and its subtraction into a fused multiply-add,
    which it does or not depending on how the surrounding program is
    fused; so the step gives the same bits inside
    :func:`event_step_jax_packed` as on its own, and on the CPU the
    numpy core's.
    """
    with jax.named_scope("event_core.step"):
        t_col = t[:, None]
        dt_g = jnp.where(rem_g > 0.0, rem_g / alloc_g, 0.0)
        dt_c = jnp.where(rem_c > 0.0, rem_c / alloc_c, 0.0)
        cand = jnp.where(avail, t_col + (dt_g + dt_c), INF)
        sid = jnp.argmin(cand, axis=1)
        t_comp = jnp.take_along_axis(cand, sid[:, None], axis=1)[:, 0]

        t_next = jnp.minimum(t_comp, t_ev)
        dt = jnp.where(live & jnp.isfinite(t_next), t_next - t, 0.0)[:, None]
        gpu_need = rem_g > 0.0
        run_g = avail & gpu_need & (alloc_g > 0.0) & (dt > 0.0)
        stalled = avail & gpu_need & (alloc_g <= 0.0)
        tg = jnp.where(run_g, jnp.minimum(dt, rem_g / alloc_g), 0.0)
        dg = jnp.where(run_g, jnp.maximum(alloc_g * tg, 0.0), 0.0)
        rg_new = rem_g - dg
        rem_dt = jnp.where(run_g, dt - tg, dt)
        cpu_ok = (avail & ~stalled & (rg_new <= 0.0) & (rem_dt > 0.0)
                  & (rem_c > 0.0) & (alloc_c > 0.0))
        tc = jnp.where(cpu_ok, jnp.minimum(rem_dt, rem_c / alloc_c), 0.0)
        dc = jnp.where(cpu_ok, jnp.maximum(alloc_c * tc, 0.0), 0.0)
        return rg_new, rem_c - dc, run_g | cpu_ok, t_comp, sid


# A batched tick's packed layout, per row: in ``rem_g | rem_c | alloc_g |
# alloc_c | avail`` (S lanes each) ``| t | t_ev | live``; out ``rem_g' |
# rem_c' | started`` (S lanes each) ``| t_comp | sid``.  Flags travel as
# 0.0/1.0 and ``sid`` as a float64 integer, both exact.
def packed_widths(S: int):
    """Row widths ``(in, out)`` of the packed buffers at ``S`` instances."""
    return 5 * S + 3, 3 * S + 2


def pack_step_inputs(buf: np.ndarray, rem_g, rem_c, alloc_g, alloc_c,
                     avail, t, t_ev, live) -> np.ndarray:
    """Copy the eight operands of a batched step into the host buffer
    ``buf`` (float64 ``[B, 5S + 3]``); returns ``buf``."""
    S = rem_g.shape[1]
    for k, a in enumerate((rem_g, rem_c, alloc_g, alloc_c, avail)):
        np.copyto(buf[:, k * S:(k + 1) * S], a)
    for k, a in enumerate((t, t_ev, live), 5 * S):
        np.copyto(buf[:, k], a)
    return buf


def unpack_step_outputs(res: np.ndarray):
    """``(rem_g', rem_c', started, t_comp, sid)`` of a packed result read
    back to the host: float64 views, a bool mask and int64 indices."""
    S = (res.shape[1] - 2) // 3
    return (res[:, :S], res[:, S:2 * S], res[:, 2 * S:3 * S] != 0.0,
            res[:, 3 * S], res[:, 3 * S + 1].astype(np.int64))


@functools.partial(jax.jit, static_argnames=("step",))
def event_step_jax_packed(buf: jax.Array, *, step):
    """One batched tick on one packed buffer each way: ``step`` (an
    eight-operand step such as :func:`event_step_jax`) on the operands
    sliced from the ``[B, 5S + 3]`` buffer ``buf``
    (:func:`pack_step_inputs`), its five results concatenated into one
    ``[B, 3S + 2]`` array of ``buf``'s dtype (:func:`unpack_step_outputs`)."""
    S = (buf.shape[1] - 3) // 5
    cols = [buf[:, k * S:(k + 1) * S] for k in range(5)]
    rg, rc, started, t_comp, sid = step(
        *cols[:4], cols[4] != 0.0, buf[:, 5 * S], buf[:, 5 * S + 1],
        buf[:, 5 * S + 2] != 0.0)
    f = buf.dtype
    return jnp.concatenate([rg, rc, started.astype(f), t_comp[:, None],
                            sid.astype(f)[:, None]], axis=1)
