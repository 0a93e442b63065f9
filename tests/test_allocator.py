"""Property tests for the closed-form deadline-aware allocator (Eq. 13–19).

The paper's claim is that the active-set closed form IS the argmin of the
convex problem (16).  We certify:
  * KKT optimality vs a numeric projected-gradient solve,
  * the capacity and floor constraints as invariants under random inputs,
  * agreement between the JAX and NumPy implementations, per node and
    fleet-wide (``allocate_cluster``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                                    # hypothesis is an optional test dep:
    import hypothesis.strategies as st  # without it only the property-based
    from hypothesis import given, settings   # tests below are skipped
except ImportError:
    class _MissingStrategies:
        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _MissingStrategies()

    def given(*_a, **_k):
        return pytest.mark.skip(reason="hypothesis not installed")

    def settings(*_a, **_k):
        return lambda f: f

from repro.core import allocator
from repro.core.allocator_np import active_set_np, solve_resource_np

S = 12


def _rand_inputs(seed, feasible_floors=True):
    rng = np.random.default_rng(seed)
    psi = np.where(rng.random(S) < 0.8, rng.uniform(0, 1e14, S), 0.0)
    omega = np.where(psi > 0, rng.uniform(0.1, 1e3, S), 0.0)
    cap = rng.uniform(5e13, 3e14)
    floors = np.where(rng.random(S) < 0.4, rng.uniform(0, cap / S, S), 0.0)
    if not feasible_floors:
        floors = floors * 20.0
    mask = rng.random(S) < 0.9
    return psi, omega, floors, cap, mask


@settings(max_examples=60, deadline=None)
@given(seed=st.integers(0, 10_000), feas=st.booleans())
def test_capacity_and_floor_invariants(seed, feas):
    psi, omega, floors, cap, mask = _rand_inputs(seed, feas)
    res = allocator.solve_resource(jnp.asarray(psi), jnp.asarray(omega),
                                   jnp.asarray(floors), jnp.asarray(cap),
                                   jnp.asarray(mask))
    alloc = np.asarray(res.alloc)
    # capacity: Σ alloc ≤ cap (float32 tolerance)
    assert alloc.sum() <= cap * (1 + 1e-5) + 1e3
    # non-resident instances get nothing
    assert np.all(alloc[~mask] == 0)
    # floors respected whenever they are jointly feasible
    if bool(res.feasible):
        f = np.where(mask, floors, 0.0)
        assert np.all(alloc + cap * 1e-5 + 1e3 >= f)
    # non-negative
    assert np.all(alloc >= 0)


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_closed_form_matches_numeric_convex_solve(seed):
    """The active-set result attains the numeric optimum of Eq. 16."""
    psi, omega, floors, cap, mask = _rand_inputs(seed, True)
    res = allocator.solve_resource(jnp.asarray(psi), jnp.asarray(omega),
                                   jnp.asarray(floors), jnp.asarray(cap),
                                   jnp.asarray(mask))
    x_num = allocator.solve_numeric(jnp.asarray(psi), jnp.asarray(omega),
                                    jnp.asarray(floors), jnp.asarray(cap),
                                    jnp.asarray(mask))
    f_closed = float(allocator.objective(res.alloc, jnp.asarray(psi),
                                         jnp.asarray(omega),
                                         jnp.asarray(mask)))
    f_num = float(allocator.objective(x_num, jnp.asarray(psi),
                                      jnp.asarray(omega), jnp.asarray(mask)))
    # closed form must be at least as good as the numeric solve
    assert f_closed <= f_num * (1 + 5e-3) + 1e-9


@settings(max_examples=60, deadline=None)
@given(seed=st.integers(0, 10_000), feas=st.booleans())
def test_jax_equals_numpy(seed, feas):
    psi, omega, floors, cap, mask = _rand_inputs(seed, feas)
    res = allocator.solve_resource(jnp.asarray(psi), jnp.asarray(omega),
                                   jnp.asarray(floors), jnp.asarray(cap),
                                   jnp.asarray(mask))
    a_np, f_np, _ = solve_resource_np(psi, omega, floors, float(cap), mask)
    np.testing.assert_allclose(np.asarray(res.alloc), a_np, rtol=1e-4,
                               atol=cap * 1e-5)
    assert bool(res.feasible) == bool(f_np)


def _cluster_vs_oracle(psi, omega, floors, cap, mask):
    """Solve both resources of an [N, S] fleet with ``allocate_cluster``
    and check each node against ``solve_resource_np``; returns the GPU
    feasibility per node.  The CPU problem is the GPU one scaled to core
    counts, with its own floors."""
    N = psi.shape[0]
    psi_c, cap_c = psi * 1e-12, cap * 1e-12
    floors_c = floors[:, ::-1] * 1e-12
    g, c = allocator.allocate_cluster(
        jnp.asarray(psi), jnp.asarray(psi_c), jnp.asarray(omega),
        jnp.asarray(floors), jnp.asarray(floors_c), jnp.asarray(cap),
        jnp.asarray(cap_c), jnp.asarray(mask))
    for res, p, f, k in ((g, psi, floors, cap), (c, psi_c, floors_c, cap_c)):
        assert res.alloc.shape == (N, psi.shape[1])
        assert res.feasible.shape == (N,)
        for n in range(N):
            a_np, f_np, _ = solve_resource_np(p[n], omega[n], f[n],
                                              float(k[n]), mask[n])
            np.testing.assert_allclose(np.asarray(res.alloc[n]), a_np,
                                       rtol=1e-4, atol=k[n] * 1e-5)
            assert bool(res.feasible[n]) == bool(f_np)
    return [bool(x) for x in np.asarray(g.feasible)]


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_allocate_cluster_equals_oracle(seed):
    rng = np.random.default_rng(seed)
    N = 4
    psi = rng.uniform(0, 1e14, (N, S))
    omega = rng.uniform(0, 100, (N, S))
    cap = rng.uniform(5e13, 2e14, N)
    floors = np.where(rng.random((N, S)) < 0.3,
                      rng.uniform(0, 2e13, (N, S)), 0.0)
    mask = rng.random((N, S)) < 0.9
    _cluster_vs_oracle(psi, omega, floors, cap, mask)


@pytest.mark.parametrize("N,S_,seed", ((5, 12, 0), (13, 200, 1)))
def test_allocate_cluster_rows_equal_oracle(N, S_, seed):
    """The fleet-wide solve is the per-node solve on every row, for both
    resources, with feasible and infeasible nodes in one fleet."""
    rng = np.random.default_rng(seed)
    psi = rng.uniform(0, 1e14, (N, S_))
    omega = rng.uniform(0, 100, (N, S_))
    cap = rng.uniform(5e13, 2e14, N)
    # floors sum to about the capacity, so feasible and infeasible rows mix
    cap_share = (cap / (0.15 * S_))[:, None]
    floors = np.where(rng.random((N, S_)) < 0.3,
                      rng.uniform(0, 1, (N, S_)) * cap_share, 0.0)
    mask = rng.random((N, S_)) < 0.9
    feas = _cluster_vs_oracle(psi, omega, floors, cap, mask)
    assert any(feas) and not all(feas)


def test_sqrt_proportionality():
    """Unfloored instances follow g ∝ √(ωΨ) exactly (Eq. 17)."""
    psi = np.array([1e13, 4e13, 9e13, 0.0])
    omega = np.array([1.0, 1.0, 1.0, 0.0])
    res = allocator.solve_resource(jnp.asarray(psi), jnp.asarray(omega),
                                   jnp.zeros(4), jnp.asarray(1e14),
                                   jnp.ones(4, bool))
    a = np.asarray(res.alloc)
    w = np.sqrt(psi * omega)
    np.testing.assert_allclose(a[:3] / a[:3].sum(), w[:3] / w[:3].sum(),
                               rtol=1e-5)
    assert a[3] == 0.0
    np.testing.assert_allclose(a.sum(), 1e14, rtol=1e-5)


def test_floor_clipping_redistributes():
    """A pinned instance keeps its floor; the rest re-share (Eq. 18–19)."""
    psi = np.array([1e10, 5e13, 5e13])          # inst 0: tiny work, big floor
    omega = np.ones(3)
    floors = np.array([4e13, 0.0, 0.0])
    res = allocator.solve_resource(jnp.asarray(psi), jnp.asarray(omega),
                                   jnp.asarray(floors), jnp.asarray(1e14),
                                   jnp.ones(3, bool))
    a = np.asarray(res.alloc)
    assert a[0] == pytest.approx(4e13, rel=1e-5)          # pinned at floor
    assert a[1] == pytest.approx(a[2], rel=1e-5)          # equal √ωΨ shares
    assert a[1] + a[2] == pytest.approx(6e13, rel=1e-5)   # residual capacity


def test_infeasible_floors_scale_down():
    psi = np.array([1e13, 1e13])
    omega = np.ones(2)
    floors = np.array([8e13, 8e13])              # Σ floors = 1.6e14 > 1e14
    res = allocator.solve_resource(jnp.asarray(psi), jnp.asarray(omega),
                                   jnp.asarray(floors), jnp.asarray(1e14),
                                   jnp.ones(2, bool))
    assert not bool(res.feasible)
    assert float(np.sum(np.asarray(res.alloc))) <= 1e14 * (1 + 1e-5)


def test_generic_active_set_equal_share():
    """active_set_np with unit weights = equal share (Round-Robin baseline)."""
    w = np.ones(4)
    alloc, feas, _ = active_set_np(w, np.zeros(4), 100.0, np.ones(4, bool))
    np.testing.assert_allclose(alloc, 25.0)


@pytest.mark.parametrize("seed", range(40))
@pytest.mark.parametrize("feas", (True, False))
def test_compact_scalar_solver_matches_active_set_np(seed, feas):
    """The tiny-gather scalar solver (`_active_set_scalar`, the
    deadline-aware fast path) must agree with the property-tested vector
    implementation — and be BIT-identical to the padded row solver it
    stands in for (same expressions, same tree-ordered reductions)."""
    from repro.sim.cluster import (_active_set_rows, _active_set_scalar,
                                   _pow2_at_least)

    psi, omega, floors, cap, mask = _rand_inputs(seed, feas)
    w = np.sqrt(np.where(mask, np.maximum(psi, 0.0), 0.0)
                * np.where(mask, np.maximum(omega, 0.0), 0.0))
    ref, _, _ = active_set_np(w, np.where(mask, floors, 0.0), float(cap),
                              mask)
    # the compact path only ever sees the busy (masked-in) instances
    idx = np.nonzero(mask)[0]
    small = _active_set_scalar([float(w[i]) for i in idx],
                               [float(floors[i]) for i in idx], float(cap))
    # tolerance scales with capacity: the infeasible-floor rescale leaves
    # O(cap * 1e-16) residual dust (capacity minus the rounded floor sum)
    # that the two implementations hand to different entries; a genuinely
    # flipped pin differs by ~the whole allocation and still fails
    np.testing.assert_allclose(np.array(small), ref[idx],
                               rtol=1e-9, atol=float(cap) * 1e-12)
    # exact equality with the padded row solver, at two padded widths
    k = len(idx)
    for K in (_pow2_at_least(k), 2 * _pow2_at_least(k)):
        wr = np.zeros((1, K))
        fr = np.zeros((1, K))
        wr[0, :k] = w[idx]
        fr[0, :k] = floors[idx]
        rows = _active_set_rows(wr, fr, np.array([float(cap)]))
        np.testing.assert_array_equal(np.array(small), rows[0, :k])


@pytest.mark.parametrize("seed", range(30))
@pytest.mark.parametrize("feas", (True, False))
def test_row_solver_matches_active_set_np(seed, feas):
    """`_active_set_rows` (the padded multi-problem engine solver) must
    agree with the property-tested vector implementation row by row,
    regardless of how much zero padding the batching added."""
    from repro.sim.cluster import _active_set_rows, _pow2_at_least

    psi, omega, floors, cap, mask = _rand_inputs(seed, feas)
    w = np.sqrt(np.where(mask, np.maximum(psi, 0.0), 0.0)
                * np.where(mask, np.maximum(omega, 0.0), 0.0))
    ref, _, _ = active_set_np(w, np.where(mask, floors, 0.0), float(cap),
                              mask)
    idx = np.nonzero(mask)[0]
    k = len(idx)
    for K in (_pow2_at_least(k), 2 * _pow2_at_least(k)):   # pad-invariance
        wr = np.zeros((1, K))
        fr = np.zeros((1, K))
        wr[0, :k] = w[idx]
        fr[0, :k] = floors[idx]
        rows = _active_set_rows(wr, fr, np.array([float(cap)]))
        np.testing.assert_allclose(rows[0, :k], ref[idx],
                                   rtol=1e-9, atol=float(cap) * 1e-12)


@pytest.mark.parametrize("policy", ("equal-share", "maxweight", "market"))
def test_compact_baselines_match_full_width_reference(policy):
    """The compact busy-instances-per-node baselines must reproduce the
    historical full-[N, S] `allocator_inputs` + `active_set_np` path
    (ulp-level: tree sums vs pairwise sums)."""
    from repro.core.baselines import (EqualShareAllocation,
                                     MarketAllocation, MaxWeightAllocation)
    from repro.sim import make_scenario, workload_for
    from repro.sim.cluster import ClusterState, Job

    sc = make_scenario("paper", n_ai_requests=60)
    reqs, _ = workload_for(sc, seed=3)
    cluster = ClusterState(sc["nodes"], sc["instances"], sc["placement"],
                           sc["transport_delay"])
    # enqueue a mixed backlog across DU / CU-UP / AI instances
    for i, r in enumerate(reqs[:40]):
        if r.cls.value == "RAN":
            sid = cluster.du_of(r.cell)
            cluster.push_job(sid, Job(req=r, rem_g=max(r.du_work_g, 1.0),
                                      rem_c=0.0,
                                      abs_deadline=r.arrival + r.deadline))
        else:
            sid = sc["service_sids"][r.service][i % 2]
            cluster.push_job(sid, Job(req=r, rem_g=max(r.ai_work_g, 1.0),
                                      rem_c=max(r.ai_work_c, 0.0),
                                      abs_deadline=r.arrival + r.deadline))
    t = 0.05
    alloc_cls = {"equal-share": EqualShareAllocation,
                 "maxweight": MaxWeightAllocation,
                 "market": MarketAllocation}[policy]

    # full-width reference: the pre-compact implementation
    psi_g, psi_c, omega, fg, fc, mask = cluster.allocator_inputs(t)
    N, S = psi_g.shape
    g_ref = np.zeros((N, S))
    c_ref = np.zeros((N, S))

    def full_weights(psi_row, other_row, omega_row):
        if policy == "equal-share":
            return (psi_row > 0).astype(float)
        if policy == "market":
            return omega_row * psi_row
        out = np.zeros_like(psi_row)                       # maxweight
        w = omega_row * psi_row
        if np.any(w > 0):
            out[int(np.argmax(w))] = 1.0
        return out

    for n in range(N):
        wg = full_weights(psi_g[n], psi_c[n], omega[n])
        wc = full_weights(psi_c[n], psi_g[n], omega[n])
        g_ref[n], _, _ = active_set_np(wg, fg[n],
                                       float(cluster.gpu_capacity[n]),
                                       mask[n])
        c_ref[n], _, _ = active_set_np(wc, fc[n],
                                       float(cluster.cpu_capacity[n]),
                                       mask[n])
    g_ref = g_ref[cluster.placement, np.arange(S)]
    c_ref = c_ref[cluster.placement, np.arange(S)]

    alloc_cls().allocate(cluster, t)
    cap = float(cluster.gpu_capacity.max())
    np.testing.assert_allclose(cluster.alloc_g, g_ref, rtol=1e-9,
                               atol=cap * 1e-12)
    np.testing.assert_allclose(cluster.alloc_c, c_ref, rtol=1e-9,
                               atol=float(cluster.cpu_capacity.max()) * 1e-9)
