"""repro.obs: the observability layer must never perturb the simulation.

Three contracts pinned here:

  * **invariance** — running with tracing + profiling + metrics enabled is
    bit-for-bit identical to running with observability off, across
    scenario families, solo and batched drivers, and engines (the hooks
    are ``is None`` checks that only *read* sim state);
  * **reconciliation** — trace counters match ``SimResult`` exactly
    (arrivals = requests, completions = requests − drops, drops,
    migrations, epochs), per replica in a batch; the final metrics sample
    reproduces ``summary()`` violation counts;
  * **hygiene** — exports are valid (Chrome trace JSON, monotone per
    replica), the obs fields stay out of the experiment identity hash so
    traced reruns resume untraced reports, and no library module under
    ``src/repro`` calls bare ``print()`` (CLIs with a ``__main__`` guard
    excepted) — diagnostics go through ``repro.obs.diag``.
"""
import json
import math
import pathlib

import pytest

from repro.eval import make_method
from repro.obs import KIND_NAMES, ObsConfig, TraceRecorder, load_jsonl
from repro.sim import Simulator, make_scenario, workload_for

FAMILIES = ("paper", "flash-crowd", "node-outage")
OBS_ON = ObsConfig(trace=True, profile=True, metrics_interval=5.0)
SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"


def _fingerprint(res):
    summary = {k: None if isinstance(v, float) and math.isnan(v) else v
               for k, v in res.summary().items()}
    return (summary, res.n_events, res.infeasible_events,
            sorted(res.dropped),
            [(r.rid, r.finish, r.target_sid) for r in res.requests],
            [(t, a.sid, a.src, a.dst) for t, a in res.migrations])


def _solo(family, engine="numpy", obs=None, method="haf", n=100):
    sc = make_scenario(family, seed=0)
    reqs, _ = workload_for(sc, seed=1, n_ai_requests=n)
    placement, allocation, rr = make_method(method)
    sim = Simulator(sc, engine=engine, drop_expired=True)
    return sim.run(reqs, placement, allocation, rr_dispatch=rr, obs=obs)


def _batched(family, engine="numpy", obs=None, method="haf", n=100, B=3):
    sc = make_scenario(family, seed=0)
    workloads = [workload_for(sc, seed=1 + s, n_ai_requests=n)[0]
                 for s in range(B)]
    rr = make_method(method)[2]
    sim = Simulator(sc, drop_expired=True)
    return sim.run_batch(workloads,
                         lambda b: make_method(method)[0],
                         lambda b: make_method(method)[1],
                         rr_dispatch=rr, engine=engine, obs=obs)


# --------------------------------------------------------------------------- #
# invariance: observability on == observability off, bit for bit
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("engine", ("numpy", "jax"))
@pytest.mark.parametrize("family", FAMILIES)
def test_obs_invariant_solo(family, engine):
    if engine == "jax":
        pytest.importorskip("jax")
    off = _solo(family, engine)
    on = _solo(family, engine, obs=OBS_ON)
    assert _fingerprint(off) == _fingerprint(on)
    assert on.trace is not None and on.profile is not None \
        and on.timeseries


@pytest.mark.parametrize("engine", ("numpy", "jax"))
@pytest.mark.parametrize("family", FAMILIES)
def test_obs_invariant_batched(family, engine):
    if engine == "jax":
        pytest.importorskip("jax")
    off = _batched(family, engine)
    on = _batched(family, engine, obs=OBS_ON)
    assert [_fingerprint(r) for r in off] == [_fingerprint(r) for r in on]


def test_obs_disabled_config_yields_no_observer():
    from repro.obs import make_observer
    assert make_observer(None) is None
    assert make_observer(ObsConfig()) is None
    res = _solo("paper", obs=ObsConfig())
    assert res.trace is None and res.profile is None \
        and res.timeseries is None


# --------------------------------------------------------------------------- #
# reconciliation: trace counters == SimResult counters, exactly
# --------------------------------------------------------------------------- #
def _assert_counts_match(res, counts):
    assert counts["arrival"] == len(res.requests)
    assert counts["completion"] == len(res.requests) - len(res.dropped)
    assert counts["drop"] == len(res.dropped)
    assert counts["migration"] == len(res.migrations)
    assert counts["epoch"] == counts["decision"]


def test_trace_reconciles_solo_with_migrations():
    res = _solo("paper", obs=OBS_ON, n=150)
    assert res.migrations, "paper+haf should migrate; workload too small"
    _assert_counts_match(res, res.trace.counts(0))


def test_trace_reconciles_solo_with_drops():
    res = _solo("flash-crowd", obs=OBS_ON, n=300)
    assert res.dropped, "flash-crowd should drop; workload too small"
    _assert_counts_match(res, res.trace.counts(0))


def test_trace_reconciles_batched_per_replica():
    results = _batched("flash-crowd", obs=OBS_ON, n=250, B=3)
    trace = results[0].trace
    assert trace is results[1].trace      # one recorder for the block
    for b, res in enumerate(results):
        _assert_counts_match(res, trace.counts(b))
    # the block totals are the per-replica sums
    total = trace.counts()
    for kind in ("arrival", "completion", "drop", "migration"):
        assert total[kind] == sum(trace.counts(b)[kind]
                                  for b in range(len(results)))


def test_metrics_final_sample_matches_summary():
    res = _solo("flash-crowd", obs=OBS_ON, n=250)
    last = res.timeseries[-1]
    vc = res.violation_counts()
    for cls in ("large_ai", "small_ai", "ran"):
        n, viol = vc[cls]
        assert last["n"][cls] == n
        assert last["viol"][cls] == viol
    assert sum(last["n"].values()) == len(res.requests)


def test_decision_ledger_predicted_and_realized():
    res = _solo("paper", obs=OBS_ON, n=150)
    decisions = res.trace.decisions
    assert decisions and len(decisions) == res.trace.counts(0)["decision"]
    committed = [d for d in decisions if d["committed"]]
    assert len(committed) == len(res.migrations)
    # every closed epoch window backfills its realized fulfillment
    closed = [d for d in decisions if d.get("realized_fulfill") is not None]
    assert closed, "no decision window was closed with realized outcomes"
    for d in decisions:
        assert "shortlist" in d and "predicted_margin" in d


# --------------------------------------------------------------------------- #
# exports: JSONL + Chrome trace
# --------------------------------------------------------------------------- #
def test_jsonl_roundtrip(tmp_path):
    res = _batched("paper", obs=OBS_ON, n=120, B=2)
    path = tmp_path / "trace.jsonl"
    res[0].trace.to_jsonl(path)
    loaded = load_jsonl(path)
    assert loaded["header"]["counts"] == res[0].trace.counts()
    by_kind = {}
    for ev in loaded["events"]:
        by_kind[ev["kind"]] = by_kind.get(ev["kind"], 0) + 1
    for kind in KIND_NAMES:
        assert by_kind.get(kind, 0) == res[0].trace.counts()[kind]


def test_chrome_export_valid_and_monotone(tmp_path):
    results = _batched("paper", obs=OBS_ON, n=120, B=3)
    path = tmp_path / "trace.chrome.json"
    results[0].trace.to_chrome(path)
    doc = json.loads(path.read_text())    # strict JSON or this raises
    events = doc["traceEvents"]
    assert events
    last_ts = {}
    for ev in events:
        assert ev["ph"] == "i" and isinstance(ev["ts"], (int, float))
        pid = ev["pid"]
        assert ev["ts"] >= last_ts.get(pid, -math.inf)
        last_ts[pid] = ev["ts"]
    assert set(last_ts) == {0, 1, 2}      # one pid per replica


def test_ring_buffer_wrap_keeps_exact_counts():
    rec = TraceRecorder(capacity=8)
    for i in range(100):
        rec.emit(0, float(i), 0, a=i)
    assert rec.counts(0)["arrival"] == 100
    assert rec.n_dropped == 92
    records = rec.records()
    assert len(records) == 8
    assert [r["t"] for r in records] == [float(i) for i in range(92, 100)]


# --------------------------------------------------------------------------- #
# experiment plumbing: identity exclusion, resume, CLI flags
# --------------------------------------------------------------------------- #
def test_obs_fields_excluded_from_identity_hash():
    from repro.exp import ExperimentSpec
    a = ExperimentSpec()
    b = a.replace(trace=True, profile=True, metrics_interval=5.0)
    assert a.identity_hash() == b.identity_hash()
    assert a.spec_hash() != b.spec_hash()


def test_resume_across_trace_toggle(tmp_path):
    from repro.exp import ExperimentSpec, run_experiment
    spec = ExperimentSpec(methods=("haf-static",), scenarios=("paper",),
                          seeds=(0,), n_ai_requests=60,
                          out=str(tmp_path / "rep.json"))
    run_experiment(spec)
    rerun = run_experiment(spec.replace(trace=True, profile=True,
                                        metrics_interval=5.0))
    assert rerun["provenance"]["resumed_rows"] == 1


def test_cli_obs_flags_reach_spec():
    from repro.eval.cli import _build_parser, build_experiment
    args = _build_parser().parse_args(
        ["--trace", "--profile", "--metrics-interval", "2.5"])
    spec = build_experiment(args)
    assert spec.trace and spec.profile and spec.metrics_interval == 2.5
    # absent flags must not override a spec file's values
    args = _build_parser().parse_args([])
    assert build_experiment(args).trace is False


def test_traced_sweep_rows_and_files(tmp_path):
    from repro.exp import ExperimentSpec, run_experiment
    spec = ExperimentSpec(methods=("haf",), scenarios=("paper",),
                          seeds=(0,), n_ai_requests=80,
                          trace=True, profile=True, metrics_interval=5.0,
                          out=str(tmp_path / "rep.json"))
    report = run_experiment(spec)
    row = report["runs"][0]
    assert row["trace_counts"]["arrival"] == row["n_requests"]
    assert row["profile"]["phases"]
    assert row["timeseries"]
    trace_path = pathlib.Path(row["trace_path"])
    assert trace_path.exists()
    assert trace_path.with_suffix("").with_suffix(".chrome.json").exists()
    agg = report["aggregate"][0]
    assert agg["profile"]["phases"] and agg["events_per_sec"]["mean"] > 0


def test_obs_cli_summary(tmp_path, capsys):
    from repro.obs.cli import main
    res = _solo("paper", obs=OBS_ON, n=120)
    path = tmp_path / "t.jsonl"
    res.trace.to_jsonl(path)
    assert main(["summary", str(path)]) == 0
    out = capsys.readouterr().out
    assert "arrival" in out and "decisions" in out
    assert main(["chrome", str(path), "-o",
                 str(tmp_path / "t.chrome.json")]) == 0
    json.loads((tmp_path / "t.chrome.json").read_text())


# --------------------------------------------------------------------------- #
# SimResult satellites: wall clock, engine tag, violation counts
# --------------------------------------------------------------------------- #
def test_simresult_wallclock_fields():
    res = _solo("paper", engine="numpy")
    assert res.wall_s > 0
    assert res.engine == "numpy"
    assert res.events_per_sec == pytest.approx(res.n_events / res.wall_s)


def test_summary_violation_counts_nan_safe():
    res = _solo("paper", n=120)
    s = res.summary()
    vc = res.violation_counts()
    assert vc["overall"][0] == len(res.requests)
    for key, (n, viol) in vc.items():
        assert s[f"n_{key}"] == n and s[f"viol_{key}"] == viol
        assert 0 <= viol <= n
    # violation counts stay integers even where the rate is NaN
    for key in ("overall", "ran", "ai", "large_ai", "small_ai"):
        assert isinstance(s[f"viol_{key}"], int)


def test_profile_phases_numpy():
    res = _solo("paper", obs=ObsConfig(profile=True))
    phases = res.profile["phases"]
    for name in ("run", "engine.step", "engine.events", "allocator.solve"):
        assert name in phases and phases[name]["total_s"] >= 0
    assert res.profile["wall_s"] > 0


def test_profile_separates_host_transfer_on_jax(monkeypatch):
    jax = pytest.importorskip("jax")
    puts = []
    real_put = jax.device_put

    def counting_put(*args, **kwargs):
        puts.append(1)
        return real_put(*args, **kwargs)

    monkeypatch.setattr(jax, "device_put", counting_put)
    off = _batched("paper", engine="jax", n=100, B=2)
    n_off = len(puts)
    on = _batched("paper", engine="jax", n=100, B=2,
                  obs=ObsConfig(profile=True))
    # profiling stages nothing the unprofiled path does not
    assert len(puts) - n_off == n_off
    assert [_fingerprint(r) for r in on] == [_fingerprint(r) for r in off]
    phases = on[0].profile["phases"]
    for name in ("core.h2d", "core.d2h"):
        assert name in phases, f"jax profile missing {name}"
        assert phases[name]["parent"] == "engine.step"
    assert "core.kernel" not in phases


# --------------------------------------------------------------------------- #
# Profiler: spans, self time, the annotation hook, tick percentiles, counters
# --------------------------------------------------------------------------- #
class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_span_nesting_and_self_time(monkeypatch):
    from repro.obs import profile
    clock = _Clock()
    monkeypatch.setattr(profile, "perf_counter", clock)
    prof = profile.Profiler()
    prof.begin("engine.tick", 0)
    prof.begin("engine.step")
    clock.t = 1.0
    prof.begin("core.h2d")
    clock.t = 3.0
    prof.end()
    prof.add("allocator.solve", 0.5)        # a closed child, timed outside
    clock.t = 4.0
    prof.end()
    prof.begin("engine.events")
    clock.t = 10.0
    prof.end()
    prof.end()
    ph = prof.report()["phases"]
    assert ph["engine.tick"]["total_s"] == 10.0
    assert ph["engine.tick"]["self_s"] == 0.0
    assert ph["engine.step"]["total_s"] == 4.0
    assert ph["engine.step"]["self_s"] == pytest.approx(4.0 - 2.0 - 0.5)
    assert ph["core.h2d"]["self_s"] == 2.0
    assert ph["engine.events"]["total_s"] == 6.0
    assert {n: p["parent"] for n, p in ph.items()} == {
        "engine.tick": None, "engine.step": "engine.tick",
        "core.h2d": "engine.step", "allocator.solve": "engine.step",
        "engine.events": "engine.tick"}
    assert prof.report()["wall_s"] == 10.0      # no "run": the root spans
    prof.begin("engine.tick", 1)
    prof.begin("engine.step")
    prof.close_open(0)
    assert prof.depth == 0
    assert ph["engine.tick"]["count"] == 1
    assert prof.report()["phases"]["engine.tick"]["count"] == 2


def test_annotation_hook_is_called_only_while_installed():
    from repro.obs import Profiler
    calls = []

    class Ann:
        def __init__(self, name, step):
            self.name, self.step = name, step

        def __enter__(self):
            calls.append(("enter", self.name, self.step))

        def __exit__(self, *exc):
            calls.append(("exit", self.name, self.step))

    prof = Profiler()
    prof.begin("engine.tick", 0)
    prof.end()
    assert calls == []
    prof.annotate = Ann
    prof.begin("engine.tick", 7)
    prof.begin("engine.step")
    prof.end()
    prof.end()
    assert calls == [("enter", "engine.tick", 7),
                     ("enter", "engine.step", None),
                     ("exit", "engine.step", None),
                     ("exit", "engine.tick", 7)]
    # a hook may decline (no device trace being recorded)
    prof.annotate = lambda name, step: None
    prof.begin("engine.tick", 8)
    prof.end()
    prof.annotate = None
    prof.begin("engine.tick", 9)
    prof.end()
    assert len(calls) == 4
    assert prof.report()["phases"]["engine.tick"]["count"] == 4


def test_tick_percentiles_are_exact():
    import numpy as np
    from repro.obs import Profiler
    rng = np.random.default_rng(3)
    ticks = rng.exponential(1e-3, 10_000)       # past the first buffer
    prof = Profiler()
    for dt in ticks:
        prof.add("engine.tick", float(dt))
    prof.add("engine.step", 1.0)                 # not a histogram span
    hist = prof.report()["hist"]
    assert list(hist) == ["engine.tick"]
    h = hist["engine.tick"]
    assert h["n"] == len(ticks)
    want = np.percentile(ticks * 1e6, [50, 90, 99])
    assert [h["p50_us"], h["p90_us"], h["p99_us"]] == list(want)
    assert h["max_us"] == ticks.max() * 1e6
    assert np.array_equal(prof.samples("engine.tick"), ticks)


def test_core_counters_match_the_array_shapes():
    pytest.importorskip("jax")
    B = 3
    results = _batched("paper", engine="jax", n=60, B=B,
                       obs=ObsConfig(profile=True))
    prof = results[0].profile
    S = len(make_scenario("paper", seed=0)["instances"])
    ticks = prof["counts"]["core.ticks"]
    assert ticks == prof["phases"]["engine.tick"]["count"] \
        == prof["hist"]["engine.tick"]["n"]
    # one packed float64 buffer each way a tick: in rem_g, rem_c, alloc_g,
    # alloc_c, avail [B, S] and t, t_ev, live [B]; out rem_g, rem_c,
    # started [B, S] and t_comp, sid [B]
    assert prof["counts"]["core.h2d_transfers"] \
        == prof["counts"]["core.d2h_transfers"] == ticks
    assert prof["counts"]["core.h2d_bytes"] == ticks * 8 * B * (5 * S + 3)
    assert prof["counts"]["core.d2h_bytes"] == ticks * 8 * B * (3 * S + 2)
    assert sum(r.n_events for r in results) <= ticks * B
    parents = {n: p["parent"] for n, p in prof["phases"].items()}
    for name in ("engine.step", "engine.events", "allocator.solve"):
        assert parents[name] == "engine.tick"
    assert parents["engine.tick"] is parents["engine.build"] \
        is parents["engine.collect"] is None


# --------------------------------------------------------------------------- #
# the epoch layer: spans under epoch.decide, counters that agree with the
# results, and the allocator's problem count
# --------------------------------------------------------------------------- #
EPOCH_SPANS = ("epoch.snapshot", "epoch.candidates", "epoch.shortlist",
               "epoch.critic", "epoch.commit")


@pytest.fixture(scope="module")
def critic_path(tmp_path_factory):
    import numpy as np
    from repro.core.critic import train_critic
    from repro.core.features import FEATURE_DIM
    rng = np.random.default_rng(1)
    samples = [(rng.normal(size=FEATURE_DIM).astype(np.float32),
                rng.uniform(size=3).astype(np.float32),
                np.ones(3, np.float32)) for _ in range(40)]
    path = tmp_path_factory.mktemp("critic") / "critic.json"
    train_critic(samples, epochs=30, hidden=16, seed=0).save(str(path))
    return str(path)


def _haf(driver, critic, obs=None, n=120, B=3):
    """HAF on the paper deployment, agent only or critic-gated, solo or
    batched; returns the replicas' results."""
    sc = make_scenario("paper", seed=0)
    workloads = [workload_for(sc, seed=1 + s, n_ai_requests=n)[0]
                 for s in range(B)]
    sim = Simulator(sc, drop_expired=True)
    if driver == "solo":
        placement, allocation, rr = make_method("haf", critic_path=critic)
        return [sim.run(workloads[0], placement, allocation,
                        rr_dispatch=rr, obs=obs)]
    return sim.run_batch(
        workloads, lambda b: make_method("haf", critic_path=critic)[0],
        lambda b: make_method("haf", critic_path=critic)[1], obs=obs)


DRIVERS_AND_CRITICS = pytest.mark.parametrize(
    "driver,with_critic", [("solo", False), ("solo", True),
                           ("batched", False), ("batched", True)])


@DRIVERS_AND_CRITICS
def test_epoch_spans_nest_under_decide(driver, with_critic, critic_path):
    results = _haf(driver, critic_path if with_critic else None,
                   obs=ObsConfig(profile=True))
    prof = results[0].profile
    parents = {n: p["parent"] for n, p in prof["phases"].items()}
    assert parents["epoch.decide"] == "engine.tick"
    for name in EPOCH_SPANS:
        if name == "epoch.critic" and not with_critic:
            assert name not in parents
            continue
        assert parents[name] == "epoch.decide", name
    decide = prof["phases"]["epoch.decide"]
    assert prof["hist"]["epoch.decide"]["n"] == decide["count"] > 0
    assert decide["total_s"] >= sum(prof["phases"][n]["total_s"]
                                    for n in EPOCH_SPANS if n in parents)


@DRIVERS_AND_CRITICS
def test_epoch_counters_agree_with_the_results(driver, with_critic,
                                               critic_path):
    results = _haf(driver, critic_path if with_critic else None,
                   obs=ObsConfig(profile=True))
    counts = results[0].profile["counts"]
    decisions = sum(len(r.epochs) for r in results)
    committed = sum(len(r.migrations) for r in results)
    assert counts["epoch.decisions"] == decisions > 0
    assert counts["epoch.committed"] == committed > 0
    assert counts["epoch.proposed"] >= \
        counts["epoch.committed"] + counts["epoch.infeasible"]
    assert 1 <= counts["epoch.groups"] <= decisions
    assert counts["epoch.candidates"] >= counts["epoch.proposed"]
    assert counts["epoch.degraded"] == sum(
        r.summary()["degraded_decisions"] for r in results) == 0
    # a decision is proposed, vetoed by the critic, or left alone
    if with_critic:
        assert counts["epoch.proposed"] + counts["epoch.vetoed"] \
            <= decisions
    else:
        assert "epoch.vetoed" not in counts
    # the snapshots carry the closing epoch's traffic
    assert counts["epoch.rate_services"] > 0
    assert counts["epoch.rate_services"] == sum(
        sum(1 for v in e.snapshot.arrival_rate.values() if v > 0)
        for r in results for e in r.epochs)
    assert counts["allocator.problems"] > 0


def test_epoch_degraded_counter_counts_the_fallbacks():
    from repro.core.agent import ExternalLLMAgent, make_agent
    from repro.core.controller import HAFPlacement
    from repro.sim.engine import DeadlineAwareAllocation
    calls = []

    def flaky(prompt):                   # every other reply is garbage
        calls.append(1)
        return "I refuse." if len(calls) % 2 else '["no-migration"]'

    placement = HAFPlacement(ExternalLLMAgent(flaky, name="flaky"),
                             fallback_agent=make_agent("qwen3-32b-sim"))
    sc = make_scenario("paper", seed=0)
    reqs, _ = workload_for(sc, seed=1, n_ai_requests=120)
    res = Simulator(sc).run(reqs, placement, DeadlineAwareAllocation(),
                            obs=ObsConfig(profile=True))
    counts = res.profile["counts"]
    assert counts["epoch.degraded"] == sum(res.degraded.values()) \
        == res.summary()["degraded_decisions"] > 0
    assert counts["epoch.decisions"] == len(res.epochs)


@pytest.mark.parametrize("driver,method", [
    ("solo", "haf-static"), ("batched", "haf-static"),
    ("batched", "lyapunov")])
def test_allocator_problems_count_every_node_solved(monkeypatch, driver,
                                                    method):
    """A full re-solve counts every node, a partial one the nodes it
    names: counted here at the allocator's own entry points, less the
    full solve each replica makes when it is built, before any tick."""
    from repro.sim import engine
    seen = []
    n_nodes = len(make_scenario("paper", seed=0)["nodes"])

    def problems(nodes):
        return n_nodes if nodes is None else len(nodes)

    real_block = engine.deadline_allocate_block

    def block(blk, t_vec, node_lists):
        seen.append(sum(problems(nodes) for nodes in node_lists))
        return real_block(blk, t_vec, node_lists)

    monkeypatch.setattr(engine, "deadline_allocate_block", block)

    def counted(allocation):
        real = allocation.allocate

        def allocate(cluster, t, nodes=None):
            seen.append(problems(nodes))
            return real(cluster, t, nodes)
        allocation.allocate = allocate
        return allocation

    obs = ObsConfig(profile=True)
    sc = make_scenario("paper", seed=0)
    workloads = [workload_for(sc, seed=1 + s, n_ai_requests=60)[0]
                 for s in range(3)]
    if driver == "solo":
        workloads = workloads[:1]
        placement, allocation, rr = make_method(method)
        res = Simulator(sc).run(workloads[0], placement, counted(allocation),
                                rr_dispatch=rr, obs=obs)
    else:
        allocations = [counted(make_method(method)[1]) for _ in workloads]
        res, *_ = Simulator(sc).run_batch(
            workloads, lambda b: make_method(method)[0],
            allocations, rr_dispatch=make_method(method)[2], obs=obs)
    built = n_nodes * len(workloads)
    assert res.profile["counts"]["allocator.problems"] == sum(seen) - built
    assert sum(seen) > built


@pytest.mark.parametrize("driver", ["solo", "batched"])
def test_profiled_critic_gated_haf_is_bit_identical(driver, critic_path):
    """The invariance tests above run agent-only HAF; the critic's span
    and veto count sit on the critic-gated path."""
    off = _haf(driver, critic_path)
    on = _haf(driver, critic_path, obs=ObsConfig(profile=True))
    assert [_fingerprint(r) for r in off] == [_fingerprint(r) for r in on]
    assert [[(e.epoch, e.snapshot.arrival_rate, e.snapshot.recent_fulfill)
             for e in r.epochs] for r in off] == \
        [[(e.epoch, e.snapshot.arrival_rate, e.snapshot.recent_fulfill)
          for e in r.epochs] for r in on]


def test_spans_reach_the_device_profiler_trace(tmp_path):
    jax = pytest.importorskip("jax")
    results = None
    jax.profiler.start_trace(str(tmp_path))
    try:
        results = _batched("paper", engine="jax", n=20, B=2,
                           obs=ObsConfig(profile=True))
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    ticks, names = [], set()
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                names.add(ev.name)
                if ev.name == "engine.tick":
                    ticks.append(dict(ev.stats)["step_num"])
    assert {"engine.build", "engine.step", "engine.events", "core.h2d",
            "core.d2h", "engine.collect"} <= names
    n = results[0].profile["counts"]["core.ticks"]
    assert sorted(ticks) == list(range(n))
    # untraced, the hook opens nothing
    from jax.profiler import TraceAnnotation
    assert not TraceAnnotation.is_enabled()


# --------------------------------------------------------------------------- #
# hygiene: no bare print() in library modules — the one-off AST walk
# that used to live here is now the `no-bare-print` rule in the
# repro.analysis invariant linter; this thin test just invokes it
# --------------------------------------------------------------------------- #
def test_no_bare_print_in_library_modules():
    from repro.analysis import analyze

    findings, n_files = analyze(rule_filter=["no-bare-print"])
    assert n_files > 0
    assert not findings, (
        "bare print() in library modules (route diagnostics through "
        f"repro.obs.diag): {[f.location for f in findings]}")
