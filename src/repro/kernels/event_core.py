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
instance dimension).

Like every module in this package, importing it requires jax; the
simulator only imports it when ``engine="jax"`` is selected.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

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
    single device round-trip per lockstep tick of ``Simulator.run_batch``.
    This is the jnp form of the Pallas kernel in
    :mod:`repro.kernels.event_step`; both evaluate the expressions of the
    numpy batched core elementwise.
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
        dg = jnp.where(run_g, alloc_g * tg, 0.0)
        rg_new = rem_g - dg
        rem_dt = jnp.where(run_g, dt - tg, dt)
        cpu_ok = (avail & ~stalled & (rg_new <= 0.0) & (rem_dt > 0.0)
                  & (rem_c > 0.0) & (alloc_c > 0.0))
        tc = jnp.where(cpu_ok, jnp.minimum(rem_dt, rem_c / alloc_c), 0.0)
        dc = jnp.where(cpu_ok, alloc_c * tc, 0.0)
        return rg_new, rem_c - dc, run_g | cpu_ok, t_comp, sid
