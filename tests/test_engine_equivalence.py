"""Cross-engine equivalence + event-core semantics regressions.

The vectorized numpy engine (default) must be bit-for-bit equivalent to
the scalar reference across every scenario family and seed: identical
``SimResult.summary()``, migration sequences, and drop sets.  The jax
backend is held to the same bar when jax is installed.  The engine names
are one registry, ``sim.event_core.ENGINES``, at every entry point.

Also pins the Eq. 1 stage-ordering fix: CPU work must not progress while
the GPU stage is stalled (the historical ``advance``/``next_completion``
divergence), and ``max_events`` truncation must be reported, not silent.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.sim import Simulator, make_scenario, paper_scenario, workload_for
from repro.sim.cluster import ClusterState, Job
from repro.sim.engine import (DeadlineAwareAllocation, SimResult,
                              StaticPlacement)
from repro.sim.event_core import (NumpyEventCore, ScalarEventCore,
                                  make_event_core)
from repro.sim.scenarios import family_names
from repro.sim.types import Request, RequestClass

SEEDS = (0, 1, 2)


def _fingerprint(res: SimResult):
    # per-request finish times pin the engines to the exact event schedule
    # (bit-for-bit), not just to the discrete fulfillment/drop outcomes;
    # NaN summary entries (absent classes) canonicalize to None so they
    # compare by value rather than NaN object identity
    summary = {k: None if isinstance(v, float) and math.isnan(v) else v
               for k, v in res.summary().items()}
    return (summary, res.n_events, res.infeasible_events,
            sorted(res.dropped),
            [(r.rid, r.finish, r.target_sid) for r in res.requests],
            [(t, a.sid, a.src, a.dst, a.category) for t, a in res.migrations])


def _run(engine: str, family: str, seed: int, method: str = "haf-static",
         drop_expired: bool = False, n_requests: int = 120,
         max_events: int = 5_000_000):
    sc = make_scenario(family, seed=0)
    reqs, _ = workload_for(sc, seed=seed, n_ai_requests=n_requests)
    from repro.eval import make_method
    placement, allocation, rr = make_method(method)
    sim = Simulator(sc, engine=engine, drop_expired=drop_expired)
    return sim.run(reqs, placement, allocation, rr_dispatch=rr,
                   max_events=max_events)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("family", family_names())
def test_numpy_matches_scalar_all_families(family, seed):
    a = _fingerprint(_run("scalar", family, seed))
    b = _fingerprint(_run("numpy", family, seed))
    assert a == b


@pytest.mark.parametrize("family", ("paper", "skewed-hetero", "node-outage"))
def test_numpy_matches_scalar_with_migrations(family):
    """Lyapunov placement migrates: the sequences must match exactly."""
    a = _run("scalar", family, 0, method="lyapunov")
    b = _run("numpy", family, 0, method="lyapunov")
    assert _fingerprint(a) == _fingerprint(b)


def test_numpy_matches_scalar_with_drops():
    a = _run("scalar", "flash-crowd", 0, drop_expired=True, n_requests=300)
    b = _run("numpy", "flash-crowd", 0, drop_expired=True, n_requests=300)
    assert _fingerprint(a) == _fingerprint(b)


# XLA may fuse multiply-adds, so the jax backend can drift by ulps in event
# times.  Usually that stays at ~1 ulp absolute, but when a realization puts
# a request's completion close to its deadline the allocation's
# work/(deadline - t) division amplifies the ulp into ~1e-5 — dense-urban's
# saturated large-AI pool hits that regime, so it gets a relative bound.
@pytest.mark.parametrize("family,finish_rtol", (("paper", 0.0),
                                                ("node-outage", 0.0),
                                                ("dense-urban", 1e-4)))
def test_jax_matches_scalar(family, finish_rtol):
    """The discrete outcomes (summary, drops, migrations, event count) must
    match exactly; finish times to ~1 ulp (or the family's drift bound)."""
    jax = pytest.importorskip("jax")
    del jax
    a = _run("scalar", family, 0)
    b = _run("jax", family, 0)
    assert _fingerprint(a)[:4] == _fingerprint(b)[:4]
    assert [(t, m.sid, m.src, m.dst) for t, m in a.migrations] == \
        [(t, m.sid, m.src, m.dst) for t, m in b.migrations]
    fa = np.array([r.finish for r in a.requests])
    fb = np.array([r.finish for r in b.requests])
    np.testing.assert_allclose(fb, fa, rtol=finish_rtol, atol=1e-9)
    assert [r.target_sid for r in a.requests] == \
        [r.target_sid for r in b.requests]


def test_unknown_engine_rejected():
    with pytest.raises(ValueError, match="unknown engine"):
        Simulator(paper_scenario(), engine="fortran")


# --------------------------------------------------------------------------- #
# batched multi-seed engine: run_batch must be discrete-outcome identical
# to per-seed solo runs (summaries, finish times, migrations, drops)
# --------------------------------------------------------------------------- #
BATCH_SEEDS = (0, 1, 2)


def _run_batch(family: str, seeds, method: str = "haf-static",
               drop_expired: bool = False, n_requests: int = 120,
               max_events: int = 5_000_000, engine: str = "numpy"):
    from repro.eval import make_method
    from repro.sim.scenarios import workload_for as wf

    sc = make_scenario(family, seed=0)
    workloads = [wf(sc, seed=s, n_ai_requests=n_requests)[0] for s in seeds]
    methods = [make_method(method) for _ in seeds]
    sim = Simulator(sc, drop_expired=drop_expired)
    return sim.run_batch(workloads, [m[0] for m in methods],
                         [m[1] for m in methods],
                         rr_dispatch=methods[0][2],
                         max_events=max_events, engine=engine)


@pytest.mark.parametrize("family", ("paper", "dense-urban", "flash-crowd",
                                    "node-outage", "spot-churn"))
def test_run_batch_matches_per_seed_numpy(family):
    solos = [_fingerprint(_run("numpy", family, s)) for s in BATCH_SEEDS]
    batch = [_fingerprint(r) for r in _run_batch(family, BATCH_SEEDS)]
    assert batch == solos


def test_run_batch_matches_with_migrations():
    """Lyapunov placement migrates AND uses a non-deadline allocator, so
    this also covers the per-replica allocation fallback path."""
    solos = [_fingerprint(_run("numpy", "skewed-hetero", s,
                               method="lyapunov")) for s in BATCH_SEEDS]
    batch = [_fingerprint(r) for r in
             _run_batch("skewed-hetero", BATCH_SEEDS, method="lyapunov")]
    assert batch == solos


def test_run_batch_fast_allocator_survives_migrations():
    """Migrations permute each replica's placement/_node_sids mid-run while
    the deadline-aware allocator keeps using the cross-replica gather (the
    fast path) — the HAF production combination.  A scripted migration
    makes the replicas' topologies diverge from epoch 1 on."""
    from repro.core.controller import ScriptedPlacement
    from repro.sim.engine import DeadlineAwareAllocation
    from repro.sim.scenarios import workload_for as wf

    sc = make_scenario("paper", seed=0)
    workloads = [wf(sc, seed=s, n_ai_requests=150)[0] for s in BATCH_SEEDS]
    script = {1: ("large0", 1), 3: ("small0", 2)}

    solos = []
    for reqs in workloads:
        res = Simulator(sc).run(reqs, ScriptedPlacement(script),
                                DeadlineAwareAllocation())
        solos.append(res)
    batch = Simulator(sc).run_batch(
        workloads,
        [ScriptedPlacement(script) for _ in BATCH_SEEDS],
        [DeadlineAwareAllocation() for _ in BATCH_SEEDS])
    assert any(len(r.migrations) >= 1 for r in solos)   # scenario really moves
    assert [_fingerprint(r) for r in batch] == \
        [_fingerprint(r) for r in solos]


def test_run_batch_matches_with_drops():
    solos = [_fingerprint(_run("numpy", "flash-crowd", s, drop_expired=True,
                               n_requests=300)) for s in BATCH_SEEDS]
    batch = [_fingerprint(r) for r in
             _run_batch("flash-crowd", BATCH_SEEDS, drop_expired=True,
                        n_requests=300)]
    assert batch == solos


def test_run_batch_b1_degenerate():
    """B=1 is the solo engine in a [1, S] coat."""
    solo = _fingerprint(_run("numpy", "paper", 0))
    batch = _run_batch("paper", (0,))
    assert len(batch) == 1
    assert _fingerprint(batch[0]) == solo


def test_run_batch_truncation_matches_per_seed():
    """Each replica hits max_events on its own clock; the truncated flag
    and the partial outcomes must match the solo runs exactly."""
    solos = [_run("numpy", "paper", s, max_events=400) for s in BATCH_SEEDS]
    batch = _run_batch("paper", BATCH_SEEDS, max_events=400)
    for solo, b in zip(solos, batch):
        assert solo.truncated and b.truncated
        assert _fingerprint(solo) == _fingerprint(b)


def test_run_batch_scalar_core_matches():
    solos = [_fingerprint(_run("numpy", "paper", s)) for s in BATCH_SEEDS]
    batch = [_fingerprint(r) for r in
             _run_batch("paper", BATCH_SEEDS, engine="scalar")]
    assert batch == solos


@pytest.mark.parametrize("family", ("paper", "dense-urban"))
def test_run_batch_jax_core_holds_the_chip_bar(family):
    """The bar the batched jax core is held to on any backend: identical
    discrete outcomes to the solo numpy runs, finish times to ~1 ulp
    (on a TPU, XLA emulates float64 and may fuse multiply-adds)."""
    pytest.importorskip("jax")
    solos = [_run("numpy", family, s) for s in BATCH_SEEDS]
    batch = _run_batch(family, BATCH_SEEDS, engine="jax")
    for solo, b in zip(solos, batch):
        assert _fingerprint(solo)[:4] == _fingerprint(b)[:4]
        fa = np.array([r.finish for r in solo.requests])
        fb = np.array([r.finish for r in b.requests])
        np.testing.assert_allclose(fb, fa, rtol=0, atol=1e-9)
        assert [r.target_sid for r in solo.requests] == \
            [r.target_sid for r in b.requests]


@pytest.mark.parametrize("family", ("paper", "dense-urban", "flash-crowd"))
def test_run_batch_jax_core_is_the_numpy_core_on_cpu(family):
    """On XLA:CPU the jax step evaluates the numpy core's IEEE-754
    expressions with no fused multiply-add: every statistic and finish
    time of a batch is the numpy batch's, bit for bit."""
    jax = pytest.importorskip("jax")
    if jax.default_backend() != "cpu":
        pytest.skip("the bit-for-bit bar holds for XLA:CPU")
    want = [_fingerprint(r) for r in _run_batch(family, BATCH_SEEDS)]
    got = [_fingerprint(r) for r in
           _run_batch(family, BATCH_SEEDS, engine="jax")]
    assert got == want


ENTRY_POINTS = ("simulator", "batched_core", "spec", "cli")


def _accepts(entry: str, engine: str) -> bool:
    """Whether ``entry`` takes ``engine`` as an engine name (False when
    it refuses the name as unknown)."""
    from repro.exp import ExperimentSpec, SpecError
    from repro.sim.event_core import make_batched_event_core
    try:
        if entry == "simulator":
            Simulator(paper_scenario(), engine=engine)
        elif entry == "batched_core":
            make_batched_event_core(engine)
        elif entry == "spec":
            ExperimentSpec(methods=("haf-static",), scenarios=("paper",),
                           engine=engine, workers=1).validate()
        else:
            from repro.eval import cli
            return engine in cli._build_parser()._option_string_actions[
                "--engine"].choices
    except (ValueError, SpecError) as err:
        assert "unknown" in str(err) and "engine" in str(err)
        return False
    return True


@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_engine_registry_is_one(entry):
    """Every entry point accepts the names of the one registry,
    ``sim.event_core.ENGINES``, and refuses any other, ``pallas`` among
    them, as an unknown engine; the CLI offers exactly the registry."""
    pytest.importorskip("jax")
    from repro.sim.event_core import DEVICE_ENGINES, ENGINES
    assert set(DEVICE_ENGINES) <= set(ENGINES)
    for engine in ENGINES:
        assert _accepts(entry, engine), engine
    assert not _accepts(entry, "pallas")
    if entry == "cli":
        from repro.eval import cli
        action = cli._build_parser()._option_string_actions["--engine"]
        assert tuple(action.choices) == ENGINES


PACKED_CASES = {          # case -> B
    "random": 32, "b_not_multiple_of_8": 13, "inf_heads": 16,
    "stalled_stages": 16, "dead_rows": 11, "unavailable_rows": 9}


def _step_inputs(case: str, B: int, S: int = 18, seed: int = 7):
    """A [B, S] block state for one tick, shaped by ``case``."""
    rng = np.random.default_rng([seed, B])
    rem_g = rng.uniform(0.0, 5.0, (B, S)) * (rng.random((B, S)) < 0.7)
    rem_c = rng.uniform(0.0, 2.0, (B, S)) * (rng.random((B, S)) < 0.8)
    alloc_g = rng.uniform(0.1, 2.0, (B, S))
    alloc_c = rng.uniform(0.1, 2.0, (B, S))
    head_mask = rng.random((B, S)) < 0.8
    reconfig_until = np.where(rng.random((B, S)) < 0.1, 1e9, 0.0)
    t = rng.uniform(0.0, 100.0, B)
    t_ev = t + rng.exponential(1.0, B)
    live = np.ones(B, bool)
    if case == "inf_heads":
        t_ev[::2] = np.inf                  # no pending event
        rem_c[1::3, :4] = np.inf            # a head that never finishes
    elif case == "stalled_stages":
        alloc_g[rng.random((B, S)) < 0.4] = 0.0
        alloc_c[rng.random((B, S)) < 0.4] = 0.0
    elif case == "dead_rows":
        live[::3] = False
    elif case == "unavailable_rows":
        head_mask[1::2] = False
    return dict(head_rem_g=rem_g, head_rem_c=rem_c,
                alloc_g=alloc_g, alloc_c=alloc_c, head_mask=head_mask,
                reconfig_until=reconfig_until,
                head_started=np.zeros((B, S), bool)), t, t_ev, live


def _bits(a):
    return np.asarray(a, np.float64).view(np.uint64)


def _numpy_core_step(state, t, t_ev, live):
    """The numpy batched core's tick on a copy of ``state``: the block
    it leaves, then ``t_comp`` and ``sid``."""
    import types

    from repro.sim.event_core import NumpyBatchedEventCore
    B, S = state["head_rem_g"].shape
    block = types.SimpleNamespace(B=B, S=S, **{k: np.copy(v) for k, v in
                                               state.items()})
    t_comp, sid = NumpyBatchedEventCore().step(block, t, t_ev, live)
    return [block.head_rem_g, block.head_rem_c, block.head_started,
            t_comp, sid]


@pytest.mark.parametrize("reference", ("eight_operand", "numpy_core"))
@pytest.mark.parametrize("case", sorted(PACKED_CASES))
def test_packed_step_is_the_unpacked_step_bit_for_bit(reference, case):
    """One packed buffer each way per tick: the block the jax core
    leaves and the ``(t_comp, sid)`` it returns are, bit for bit, what
    its eight-operand step gives on the same block (``eight_operand``),
    and on XLA:CPU what the numpy batched core's tick gives
    (``numpy_core``)."""
    import types
    jax = pytest.importorskip("jax")
    from repro.kernels import event_core as kec
    from repro.sim.event_core import make_batched_event_core
    state, t, t_ev, live = _step_inputs(case, PACKED_CASES[case])
    if reference == "eight_operand":
        avail = state["head_mask"] & (state["reconfig_until"] <= t[:, None])
        with jax.enable_x64(True):
            want = [np.asarray(o) for o in kec.event_step_jax(
                state["head_rem_g"], state["head_rem_c"], state["alloc_g"],
                state["alloc_c"], avail, t, t_ev, live)]
    else:
        if jax.default_backend() != "cpu":
            pytest.skip("the bit-for-bit bar holds for XLA:CPU")
        want = _numpy_core_step(state, t, t_ev, live)
    core = make_batched_event_core("jax")
    B, S = state["head_rem_g"].shape
    block = types.SimpleNamespace(B=B, S=S, **{k: np.copy(v) for k, v in
                                               state.items()})
    t_comp, sid = core.step(block, t, t_ev, live)
    assert np.array_equal(_bits(block.head_rem_g), _bits(want[0]))
    assert np.array_equal(_bits(block.head_rem_c), _bits(want[1]))
    assert block.head_started.dtype == np.bool_
    assert np.array_equal(block.head_started, want[2])
    assert t_comp.dtype == np.float64 and sid.dtype == np.int64
    assert np.array_equal(_bits(t_comp), _bits(want[3]))
    assert np.array_equal(sid, want[4])
    # inputs the step does not write are left as they were
    for name in ("alloc_g", "alloc_c", "head_mask", "reconfig_until"):
        assert np.array_equal(getattr(block, name), state[name])
    if case == "unavailable_rows":
        assert np.all(np.isinf(t_comp[1::2])) and not sid[1::2].any()
    if case == "dead_rows":
        dead = ~live
        assert np.array_equal(block.head_rem_g[dead],
                              state["head_rem_g"][dead])
        assert not block.head_started[dead].any()


def test_run_batch_unknown_engine_rejected():
    from repro.sim.event_core import make_batched_event_core
    with pytest.raises(ValueError, match="unknown batched engine"):
        make_batched_event_core("fortran")


def test_run_batch_policy_factories():
    """placements/allocations accept a factory f(b) -> policy."""
    solo = _fingerprint(_run("numpy", "paper", 0))
    sc = make_scenario("paper", seed=0)
    reqs, _ = workload_for(sc, seed=0, n_ai_requests=120)
    from repro.sim.engine import StaticPlacement as SP
    res = Simulator(sc).run_batch([reqs],
                                  lambda b: SP(),
                                  lambda b: DeadlineAwareAllocation())
    assert _fingerprint(res[0]) == solo
    with pytest.raises(ValueError, match="one placement per replica"):
        Simulator(sc).run_batch([reqs], [SP(), SP()],
                                [DeadlineAwareAllocation()])


# --------------------------------------------------------------------------- #
# batched agentic policies: the full HAF stack (stand-in agent + critic
# migration gating) under run_batch must stay discrete-outcome identical
# to per-seed solo runs — the slow-timescale decisions are dispatched as
# ONE batched decide per tick, so this pins the whole epoch pipeline
# (candidate features, vectorized P1-P3 scoring, [B, C, F] critic forward)
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def tiny_critic(tmp_path_factory):
    import numpy as np

    from repro.core.critic import train_critic
    from repro.core.features import FEATURE_DIM

    rng = np.random.default_rng(0)
    samples = [(rng.normal(size=FEATURE_DIM).astype(np.float32),
                rng.uniform(size=3).astype(np.float32),
                np.ones(3, np.float32)) for _ in range(40)]
    critic = train_critic(samples, epochs=30, hidden=16, seed=0)
    path = tmp_path_factory.mktemp("critic") / "tiny_critic.json"
    critic.save(str(path))
    return str(path)


def _run_haf(sc, reqs, critic_path, agent="qwen3-32b-sim"):
    from repro.core import HAFPlacement, make_agent
    from repro.core.critic import load_critic_cached

    critic = load_critic_cached(critic_path) if critic_path else None
    pol = HAFPlacement(make_agent(agent), critic=critic)
    return Simulator(sc).run(reqs, pol, DeadlineAwareAllocation())


@pytest.mark.parametrize("family", ("paper", "node-outage", "flash-crowd"))
@pytest.mark.parametrize("with_critic", (False, True),
                         ids=("agent-only", "critic-gated"))
def test_run_batch_haf_matches_solo(family, with_critic, tiny_critic):
    from repro.core import HAFPlacement, make_agent
    from repro.core.critic import load_critic_cached

    critic_path = tiny_critic if with_critic else None
    sc = make_scenario(family, seed=0)
    # the critic gate vetoes marginal splits, so the paper baseline needs a
    # deeper backlog before any migration clears the bar — keep that run
    # long enough that the "stack really migrates" guard below stays
    # meaningful (the stress families migrate already at 150)
    n_req = 250 if (with_critic and family == "paper") else 150
    workloads = [workload_for(sc, seed=s, n_ai_requests=n_req)[0]
                 for s in BATCH_SEEDS]
    solos = [_run_haf(sc, reqs, critic_path) for reqs in workloads]

    def placement(b):
        critic = load_critic_cached(critic_path) if critic_path else None
        return HAFPlacement(make_agent("qwen3-32b-sim"), critic=critic)

    batch = Simulator(sc).run_batch(workloads, placement,
                                    lambda b: DeadlineAwareAllocation())
    assert any(r.migrations for r in solos)   # the stack really migrates
    assert [_fingerprint(r) for r in batch] == \
        [_fingerprint(r) for r in solos]


def test_run_batch_haf_mixed_agents_and_critics(tiny_critic):
    """Replicas with different agents / critic configs share one batch:
    grouping by batch_key must not leak decisions across groups."""
    from repro.core import HAFPlacement, make_agent
    from repro.core.critic import load_critic_cached

    sc = make_scenario("paper", seed=0)
    workloads = [workload_for(sc, seed=s, n_ai_requests=150)[0]
                 for s in range(4)]
    configs = [("qwen3-32b-sim", None),
               ("deepseek-r1-70b-sim", None),
               ("qwen3-32b-sim", tiny_critic),
               ("deepseek-r1-70b-sim", tiny_critic)]

    solos = [_run_haf(sc, reqs, path, agent=agent)
             for reqs, (agent, path) in zip(workloads, configs)]
    placements = [
        HAFPlacement(make_agent(agent),
                     critic=load_critic_cached(path) if path else None)
        for agent, path in configs]
    batch = Simulator(sc).run_batch(
        workloads, placements, lambda b: DeadlineAwareAllocation())
    assert [_fingerprint(r) for r in batch] == \
        [_fingerprint(r) for r in solos]


# --------------------------------------------------------------------------- #
# stage-ordering semantics (Eq. 1): the fixed advance/next_completion pair
# --------------------------------------------------------------------------- #
def _mini_cluster():
    sc = paper_scenario()
    return ClusterState(sc["nodes"], sc["instances"], sc["placement"],
                        sc["transport_delay"])


def _job(rem_g=4.0, rem_c=2.0, deadline=10.0, rid=0):
    req = Request(rid=rid, cls=RequestClass.SMALL_AI, arrival=0.0,
                  deadline=deadline, cell=0)
    return Job(req=req, rem_g=rem_g, rem_c=rem_c, abs_deadline=deadline)


@pytest.mark.parametrize("core_cls", (ScalarEventCore, NumpyEventCore))
def test_stalled_gpu_stage_freezes_cpu_work(core_cls):
    """rem_g > 0 with alloc_g <= 0: NOTHING progresses and no completion is
    scheduled — the regression where CPU work progressed on heads the
    completion scan skipped."""
    cl = _mini_cluster()
    core = core_cls()
    cl.push_job(0, _job())
    cl.alloc_g[0] = 0.0
    cl.alloc_c[0] = 5.0
    t_next, sid = core.next_completion(cl, 0.0)
    assert not math.isfinite(t_next) and sid == -1
    core.advance(cl, 0.0, 1.0)
    assert cl.head_rem_g[0] == 4.0
    assert cl.head_rem_c[0] == 2.0          # CPU did NOT run ahead
    assert not cl.head_started[0]


@pytest.mark.parametrize("core_cls", (ScalarEventCore, NumpyEventCore))
def test_cpu_progresses_only_after_gpu_exhausted(core_cls):
    cl = _mini_cluster()
    core = core_cls()
    cl.push_job(0, _job(rem_g=4.0, rem_c=2.0))
    cl.alloc_g[0] = 2.0                     # GPU stage takes 2s
    cl.alloc_c[0] = 1.0                     # CPU stage takes 2s after that
    t_next, sid = core.next_completion(cl, 0.0)
    assert sid == 0 and t_next == pytest.approx(4.0)
    core.advance(cl, 0.0, 1.0)              # mid-GPU-stage
    assert cl.head_rem_g[0] == pytest.approx(2.0)
    assert cl.head_rem_c[0] == 2.0          # untouched: GPU not done
    core.advance(cl, 1.0, 2.0)              # crosses the stage boundary
    assert cl.head_rem_g[0] == pytest.approx(0.0)
    assert cl.head_rem_c[0] == pytest.approx(1.0)
    assert cl.head_started[0]


@pytest.mark.parametrize("core_cls", (ScalarEventCore, NumpyEventCore))
def test_schedule_matches_progressed_work(core_cls):
    """Advancing exactly to the reported completion time exhausts the head:
    the event schedule and the progressed work stay in sync."""
    cl = _mini_cluster()
    core = core_cls()
    cl.push_job(0, _job(rem_g=3.0, rem_c=1.5))
    cl.alloc_g[0] = 1.5
    cl.alloc_c[0] = 3.0
    t_next, sid = core.next_completion(cl, 0.0)
    core.advance(cl, 0.0, t_next)
    assert cl.head_rem_g[0] <= 1e-12
    assert cl.head_rem_c[0] <= 1e-12


def test_unavailable_instance_frozen():
    cl = _mini_cluster()
    core = NumpyEventCore()
    cl.push_job(0, _job())
    cl.alloc_g[0] = cl.alloc_c[0] = 1.0
    cl.reconfig_until[0] = 5.0              # mid-reconfiguration
    t_next, _ = core.next_completion(cl, 1.0)
    assert not math.isfinite(t_next)
    core.advance(cl, 1.0, 2.0)
    assert cl.head_rem_g[0] == 4.0 and cl.head_rem_c[0] == 2.0


def test_psi_is_tail_plus_head():
    cl = _mini_cluster()
    cl.push_job(0, _job(rem_g=4.0, rem_c=2.0, rid=0))
    cl.push_job(0, _job(rem_g=6.0, rem_c=1.0, rid=1))
    assert cl.psi_g_of(0) == pytest.approx(10.0)
    cl.alloc_g[0] = cl.alloc_c[0] = 2.0
    NumpyEventCore().advance(cl, 0.0, 1.0)  # head loses 2.0 of GPU work
    assert cl.psi_g_of(0) == pytest.approx(8.0)
    job = cl.pop_job(0)
    assert job.req.rid == 0
    assert cl.psi_g_of(0) == pytest.approx(6.0)
    assert cl.head_rem_g[0] == pytest.approx(6.0)
    assert not cl.head_started[0]           # fresh head


# --------------------------------------------------------------------------- #
# truncation + absent-class reporting
# --------------------------------------------------------------------------- #
def test_truncated_flag_on_max_events():
    sc = make_scenario("paper", seed=0)
    reqs, _ = workload_for(sc, seed=0, n_ai_requests=200)
    res = Simulator(sc).run(reqs, StaticPlacement(),
                            DeadlineAwareAllocation(), max_events=50)
    assert res.truncated
    assert res.n_events == 50
    assert res.summary()["truncated"] is True
    full = Simulator(sc).run(reqs, StaticPlacement(),
                             DeadlineAwareAllocation())
    assert not full.truncated
    assert full.summary()["truncated"] is False


def test_truncated_surfaces_in_report():
    from repro.eval import SweepSpec, build_report, expand_jobs, run_job
    spec = SweepSpec(methods=("haf-static",), scenarios=("paper",),
                     seeds=(0,), n_ai_requests=150, max_events=40)
    rows = [run_job(j) for j in expand_jobs(spec)]
    assert all(r["truncated"] for r in rows)
    report = build_report(spec, rows)
    assert report["n_truncated"] == 1
    assert report["aggregate"][0]["truncated_runs"] == 1


def test_summary_absent_class_is_nan_and_skipped_in_aggregation():
    reqs = [dataclasses.replace(
        Request(rid=i, cls=RequestClass.RAN, arrival=0.0, deadline=1.0,
                cell=0), finish=0.5) for i in range(4)]
    res = SimResult(requests=reqs, dropped=set(), migrations=[], epochs=[],
                    infeasible_events=0, n_events=4)
    s = res.summary()
    assert s["ran"] == 1.0
    assert math.isnan(s["large_ai"]) and math.isnan(s["small_ai"]) \
        and math.isnan(s["ai"])

    from repro.eval import aggregate, format_table
    row = dict(s, method="m", scenario="sc", seed=0, wall_s=0.0)
    cells = aggregate([row, dict(row, seed=1)])
    assert cells[0]["ran"] == {"mean": 1.0, "ci95": 0.0, "n": 2}
    assert cells[0]["large_ai"]["mean"] is None
    assert cells[0]["large_ai"]["n"] == 0
    table = format_table(cells)
    assert "—" in table                      # absent class, not 0.0000


def test_report_json_stays_strict_with_nan_rows(tmp_path):
    import json

    from repro.eval import SweepSpec, build_report, write_report
    row = {"method": "m", "scenario": "sc", "seed": 0, "overall": 0.5,
           "ran": float("nan"), "ai": 0.5, "large_ai": float("nan"),
           "small_ai": 0.5, "mig_large": 0, "mig_total": 0, "wall_s": 0.1,
           "truncated": False}
    report = build_report(SweepSpec(), [row])
    path = write_report(report, tmp_path / "r.json")
    loaded = json.loads(path.read_text())    # strict JSON: no NaN literals
    assert loaded["runs"][0]["ran"] is None
