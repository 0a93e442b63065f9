"""Deadline-aware closed-form GPU/CPU allocation (paper §III-C, Eq. 13–19).

The allocation layer solves, per node n and per resource r ∈ {GPU, CPU},

    min_{x_s}  Σ_s ω_s · Ψ_s / x_s      s.t.  Σ_s x_s ≤ R_n,  x_s ≥ floor_s,

whose KKT stationarity gives the square-root workload–urgency proportional
rule  x_s ∝ √(ω_s Ψ_s)  (Eq. 17), with lower-bound (capacity-floor)
constraints handled by **active-set clipping** (Eq. 18–19): instances whose
proportional share falls below their floor are fixed at the floor and the
residual capacity is re-shared among the rest.  Because fixing a set at
floors only *increases* everyone else's share, the floored set grows
monotonically and the iteration converges in ≤ S steps.

Everything here is pure JAX (jit/vmap-friendly, fixed shapes, no Python
branching on values) so the same function:
  * runs for one node or is vmapped over nodes for the fleet-wide solve
    (:func:`allocate_cluster`),
  * is the reference the simulator's NumPy twin
    (:mod:`repro.core.allocator_np`) is tested against.

Shapes: S = number of instances (padded, fixed); masks select residents.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

EPS = 1e-9


class AllocResult(NamedTuple):
    alloc: jax.Array      # [..., S] allocated capacity per instance
    feasible: jax.Array   # [...] bool — Σ floors ≤ capacity
    floored: jax.Array    # [..., S] bool — instance pinned at its floor


def solve_resource(psi: jax.Array, omega: jax.Array, floors: jax.Array,
                   capacity: jax.Array, mask: Optional[jax.Array] = None,
                   n_iter: Optional[int] = None) -> AllocResult:
    """Closed-form active-set solve for ONE resource on ONE node.

    Args:
      psi:      [S] residual workload Ψ_s ≥ 0 (FLOPs or core-seconds).
      omega:    [S] urgency weights ω_s ≥ 0 (Eq. 14).
      floors:   [S] minimum capacities (Eq. 15); 0 for non-RAN instances.
      capacity: scalar node capacity (G_n or C_n).
      mask:     [S] bool residency; non-resident ⇒ allocation 0.
      n_iter:   active-set iterations (default S — guaranteed convergence).

    Returns AllocResult with Σ alloc ≤ capacity (up to float error).
    """
    S = psi.shape[-1]
    n_iter = S if n_iter is None else n_iter
    if mask is None:
        mask = jnp.ones((S,), bool)
    mask = mask.astype(bool)

    psi = jnp.where(mask, jnp.maximum(psi, 0.0), 0.0)
    omega = jnp.where(mask, jnp.maximum(omega, 0.0), 0.0)
    floors = jnp.where(mask, jnp.maximum(floors, 0.0), 0.0)

    w = jnp.sqrt(omega * psi)                     # Eq. 17 weights
    floor_sum = jnp.sum(floors)
    feasible = floor_sum <= capacity + 1e-6

    # Infeasible placements (paper: "current placement is infeasible wrt the
    # RAN deadline constraint"): degrade gracefully by scaling floors to fit.
    scale = jnp.where(feasible, 1.0, capacity / jnp.maximum(floor_sum, EPS))
    floors_eff = floors * scale

    # zero-weight instances can never exceed their floor => pinned from start
    pinned0 = (w <= 0.0)

    def body(_, pinned):
        rem = capacity - jnp.sum(jnp.where(pinned, floors_eff, 0.0))
        denom = jnp.sum(jnp.where(pinned, 0.0, w))
        prop = w * jnp.maximum(rem, 0.0) / jnp.maximum(denom, EPS)
        return pinned | (prop < floors_eff)

    pinned = jax.lax.fori_loop(0, n_iter, body, pinned0)

    rem = capacity - jnp.sum(jnp.where(pinned, floors_eff, 0.0))  # Eq. 19
    denom = jnp.sum(jnp.where(pinned, 0.0, w))
    share = w * jnp.maximum(rem, 0.0) / jnp.maximum(denom, EPS)   # Eq. 18
    alloc = jnp.where(pinned, floors_eff, share)
    alloc = jnp.where(mask, alloc, 0.0)
    return AllocResult(alloc=alloc, feasible=feasible, floored=pinned & mask)


def allocate_node(psi_g: jax.Array, psi_c: jax.Array, omega: jax.Array,
                  floors_g: jax.Array, floors_c: jax.Array,
                  gpu_capacity: jax.Array, cpu_capacity: jax.Array,
                  mask: Optional[jax.Array] = None
                  ) -> Tuple[AllocResult, AllocResult]:
    """Both sub-problems of Eq. 16 for one node (they decouple additively)."""
    g = solve_resource(psi_g, omega, floors_g, gpu_capacity, mask)
    c = solve_resource(psi_c, omega, floors_c, cpu_capacity, mask)
    return g, c


@jax.jit
def allocate_cluster(psi_g: jax.Array, psi_c: jax.Array, omega: jax.Array,
                     floors_g: jax.Array, floors_c: jax.Array,
                     gpu_capacity: jax.Array, cpu_capacity: jax.Array,
                     mask: jax.Array) -> Tuple[AllocResult, AllocResult]:
    """Fleet-wide allocation: everything is [N, S]; capacities are [N].
    :func:`solve_resource` vmapped over the nodes, once per resource."""
    solve = jax.vmap(solve_resource, in_axes=(0, 0, 0, 0, 0))
    g = solve(psi_g, omega, floors_g, gpu_capacity, mask)
    c = solve(psi_c, omega, floors_c, cpu_capacity, mask)
    return g, c


# --------------------------------------------------------------------------- #
# floors + urgency from request-level state (Eq. 14–15)
# --------------------------------------------------------------------------- #
def urgency(deadline_remaining: jax.Array, active: jax.Array,
            eps: float = 1e-3) -> jax.Array:
    """ω contribution per request (Eq. 14): 1/max(τ − (t−a), ε)."""
    u = 1.0 / jnp.maximum(deadline_remaining, eps)
    return jnp.where(active, u, 0.0)


def ran_floor(psi: jax.Array, min_remaining: jax.Array,
              capacity: jax.Array, has_pending: jax.Array,
              eps: float = 1e-4) -> Tuple[jax.Array, jax.Array]:
    """Capacity floor (Eq. 15) for one RAN instance's dominant resource.

    Args:
      psi:           residual RAN-only workload Ψ at (n, s).
      min_remaining: min over pending RAN-only q of (τ_q − (t−a_q) − δ − α̂_down).
      capacity:      node capacity (used to cap runaway floors).
      has_pending:   Q^r_{n,s}(t) non-empty (floor is 0 otherwise).

    Returns (floor, deadline_infeasible).
    """
    infeasible = has_pending & (min_remaining <= 0.0)
    floor = psi / jnp.maximum(min_remaining, eps)
    floor = jnp.where(has_pending, jnp.minimum(floor, capacity), 0.0)
    return floor, infeasible


# --------------------------------------------------------------------------- #
# numeric oracle (projected gradient on the true convex objective) — used by
# property tests to certify the closed form is the actual argmin of Eq. 16.
# --------------------------------------------------------------------------- #
def objective(alloc: jax.Array, psi: jax.Array, omega: jax.Array,
              mask: jax.Array) -> jax.Array:
    """Σ ω Ψ / x over resident instances with work (Eq. 16a, one resource)."""
    want = mask & (psi > 0) & (omega > 0)
    return jnp.sum(jnp.where(want, omega * psi / jnp.maximum(alloc, EPS), 0.0))


def solve_numeric(psi, omega, floors, capacity, mask=None, steps: int = 4000,
                  lr: float = 0.05):
    """Slow numeric solve of Eq. 16 (one resource) by projected gradient.

    Parameterize x = floor + softplus-free positive part via projection:
    gradient step on the objective, then project onto the simplex-with-floors
    {x ≥ floor, Σx ≤ C}. Reference-quality only; used in tests.
    """
    S = psi.shape[-1]
    if mask is None:
        mask = jnp.ones((S,), bool)
    psi = jnp.where(mask, psi, 0.0)
    omega = jnp.where(mask, omega, 0.0)
    floors = jnp.where(mask, floors, 0.0)
    want = mask & (psi * omega > 0)

    def project(x):
        x = jnp.maximum(x, floors)
        # waterfill down any excess above the floors proportionally
        excess = jnp.sum(x) - capacity
        slack = x - floors

        def cut(x):
            s = jnp.sum(slack)
            return floors + slack * jnp.maximum(capacity - jnp.sum(floors), 0.0) / jnp.maximum(s, EPS)
        return jax.lax.cond(excess > 0, cut, lambda x: x, x)

    x0 = project(jnp.where(want, capacity / jnp.maximum(jnp.sum(want), 1), floors))

    def step(x, _):
        g = jax.grad(objective)(x, psi, omega, mask)
        x = project(x - lr * capacity * g / (jnp.abs(g).max() + EPS))
        return x, None

    x, _ = jax.lax.scan(step, x0, None, length=steps)
    return x
