"""Event-engine benchmark: solo cores + the batched multi-seed engine.

Eight sections recorded to ``BENCH_pr7.json``:

  * solo — scalar reference vs vectorized numpy engine on identical
    ``dense-urban`` workloads (the PR-2 comparison, kept so the
    trajectory is tracked), plus the ``paper``-family single trace where
    the tiny-gather scalar allocator fast path applies,
  * batched — ``Simulator.run_batch`` at B ∈ {1, 8, 32} seeds per block:
    aggregate events/sec vs the B=1 solo numpy engine, with the batched
    results fingerprint-checked against per-seed solo runs,
  * haf — the full agentic stack (stand-in agent + critic gating) solo vs
    batched: the slow-timescale epoch pipeline dispatches grouped
    decides, so HAF cells batch like the baselines (fingerprint-checked),
  * sweep — a small fleet sweep executed batched (one process,
    ``batch_seeds`` seeds per simulation) vs process-parallel workers:
    end-to-end wall time including worker startup and scenario builds,
  * profile — the ``repro.obs`` phase profiler over the batched paper
    family per backend (numpy / jax / pallas): per-phase wall-clock with
    host↔device transfer (``core.h2d`` + ``core.d2h``) accounted
    separately from kernel time,
  * pr4_comparison — obs-off batched HAF throughput vs the PR-4 record:
    the instrumentation hooks must not tax the uninstrumented engine
    (acceptance: within 3%),
  * memory — tracemalloc peaks for the streamed arrival path
    (``retain_requests=False`` + windowed refill) vs the materialized
    list at growing trace lengths: the streamed peak must stay flat
    (O(S + window)) while the materialized peak grows O(n); in
    ``--smoke`` the streamed 2·10^5-request peak is asserted against a
    fixed budget,
  * trace_replay (full mode only) — an uncapped 10^6-request trace
    replay with ``retain_requests=False`` and obs trace counters on:
    the run must complete untruncated and the counters must reconcile
    exactly against the streaming accumulators.

  PYTHONPATH=src python -m benchmarks.engine_bench            # full grid
  PYTHONPATH=src python -m benchmarks.engine_bench --smoke    # CI-sized
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import time
from typing import Dict, List

import numpy as np

from benchmarks import common
from repro.eval import SweepSpec, run_sweep
from repro.sim import Simulator, make_scenario, workload_for
from repro.sim.engine import DeadlineAwareAllocation, StaticPlacement
from repro.sim.scenarios.workload import workload_stream_for

BENCH_PATH = common.ROOT / "BENCH_pr7.json"
PR4_PATH = common.ROOT / "BENCH_pr4.json"

# (n_nodes, n_ai_requests): S = 3 * n_nodes for dense-urban
SOLO_SMOKE_GRID = ((36, 1500),)
SOLO_FULL_GRID = ((36, 4000), (240, 4000))
BATCH_SIZES = (1, 8, 32)
HAF_BATCH_SIZES = (1, 8)


def _canon_summary(s: Dict) -> Dict:
    """NaN -> None so absent-class entries compare by value, not by the
    accident of NaN object identity (float('nan') != float('nan'))."""
    return {k: None if isinstance(v, float) and math.isnan(v) else v
            for k, v in s.items()}


def _fingerprint(res) -> tuple:
    return (_canon_summary(res.summary()), res.n_events,
            sorted(res.dropped),
            tuple((r.rid, r.finish) for r in res.requests))


# --------------------------------------------------------------------------- #
# solo: scalar reference vs numpy engine (PR-2 comparison)
# --------------------------------------------------------------------------- #
def bench_solo_point(n_nodes: int, n_requests: int, repeats: int = 2) -> Dict:
    sc = make_scenario("dense-urban", seed=0, n_nodes=n_nodes)
    reqs, _ = workload_for(sc, seed=1, n_ai_requests=n_requests)
    point: Dict = {"family": "dense-urban", "n_nodes": n_nodes,
                   "n_instances": len(sc["instances"]),
                   "n_requests": len(reqs)}
    results = {}
    for engine in ("scalar", "numpy"):
        sim = Simulator(sc, engine=engine)
        wall = float("inf")                  # best-of-N: steady-state rate
        for _ in range(repeats):
            t0 = time.time()
            res = sim.run(reqs, StaticPlacement(), DeadlineAwareAllocation())
            wall = min(wall, time.time() - t0)
        common.check_not_truncated([res.summary()], f"engine_bench:{engine}")
        results[engine] = _fingerprint(res)
        point[engine] = {"wall_s": round(wall, 3),
                         "events": res.n_events,
                         "events_per_sec": round(res.n_events / wall, 1)}
    if results["scalar"] != results["numpy"]:
        raise RuntimeError("engine_bench: scalar and numpy engines diverged "
                           f"at n_nodes={n_nodes} — equivalence broken")
    point["speedup"] = round(point["numpy"]["events_per_sec"]
                             / point["scalar"]["events_per_sec"], 2)
    return point


# --------------------------------------------------------------------------- #
# batched: [B, S] lockstep blocks vs the B=1 solo numpy engine
# --------------------------------------------------------------------------- #
def bench_batched(n_nodes: int, n_requests: int,
                  sizes=BATCH_SIZES, verify_b: int = 8) -> Dict:
    sc = make_scenario("dense-urban", seed=0, n_nodes=n_nodes)
    max_b = max(sizes)
    workloads = [workload_for(sc, seed=1 + s, n_ai_requests=n_requests)[0]
                 for s in range(max_b)]
    sim = Simulator(sc)

    # B=1 solo baseline (the engine a classic per-job sweep runs)
    wall = float("inf")
    for _ in range(2):
        t0 = time.time()
        solo_res = sim.run(workloads[0], StaticPlacement(),
                           DeadlineAwareAllocation())
        wall = min(wall, time.time() - t0)
    common.check_not_truncated([solo_res.summary()], "engine_bench:solo")
    solo_evps = solo_res.n_events / wall

    out: Dict = {"family": "dense-urban", "n_nodes": n_nodes,
                 "n_instances": len(sc["instances"]),
                 "n_requests_per_seed": n_requests,
                 "solo_numpy_evps": round(solo_evps, 1),
                 "points": []}
    for B in sizes:
        methods = [(StaticPlacement(), DeadlineAwareAllocation())
                   for _ in range(B)]
        t0 = time.time()
        results = sim.run_batch(workloads[:B],
                                [m[0] for m in methods],
                                [m[1] for m in methods])
        bwall = time.time() - t0
        common.check_not_truncated([r.summary() for r in results],
                                   f"engine_bench:batch B={B}")
        events = sum(r.n_events for r in results)
        evps = events / bwall
        out["points"].append({"B": B, "events": events,
                              "wall_s": round(bwall, 3),
                              "events_per_sec": round(evps, 1),
                              "speedup_vs_solo": round(evps / solo_evps, 2)})
        if B == 1 and _fingerprint(results[0]) != _fingerprint(solo_res):
            raise RuntimeError("engine_bench: batched B=1 diverged from the "
                               "solo numpy engine — equivalence broken")
        if B == verify_b:
            for s in range(B):
                ref = sim.run(workloads[s], StaticPlacement(),
                              DeadlineAwareAllocation())
                if _fingerprint(results[s]) != _fingerprint(ref):
                    raise RuntimeError(
                        f"engine_bench: batched seed {1 + s} diverged from "
                        "its per-seed solo run — equivalence broken")
    out["batch_speedup_max_b"] = out["points"][-1]["speedup_vs_solo"]
    return out


# --------------------------------------------------------------------------- #
# haf: the agentic stack (agent + critic) solo vs batched epoch pipeline
# --------------------------------------------------------------------------- #
def _bench_critic():
    """A micro-critic trained on synthetic samples: the bench measures the
    epoch pipeline's throughput, not gating quality, and must stay
    self-contained (it runs before the critic_data benchmark)."""
    from repro.core.critic import train_critic
    from repro.core.features import FEATURE_DIM

    rng = np.random.default_rng(0)
    samples = [(rng.normal(size=FEATURE_DIM).astype(np.float32),
                rng.uniform(size=3).astype(np.float32),
                np.ones(3, np.float32)) for _ in range(40)]
    return train_critic(samples, epochs=30, hidden=16, seed=0)


def _haf_setup(n_requests: int, max_b: int):
    from repro.core import HAFPlacement, make_agent

    critic = _bench_critic()
    sc = make_scenario("paper", seed=0)
    workloads = [workload_for(sc, seed=1 + s, n_ai_requests=n_requests)[0]
                 for s in range(max_b)]
    sim = Simulator(sc)

    def placement(b=0):
        return HAFPlacement(make_agent(common.DEFAULT_AGENT), critic=critic)

    return sim, workloads, placement


def bench_haf(n_requests: int, sizes=HAF_BATCH_SIZES) -> Dict:
    sim, workloads, placement = _haf_setup(n_requests, max(sizes))
    solo_results = []
    wall = 0.0
    for wl in workloads:
        t0 = time.time()
        solo_results.append(sim.run(wl, placement(),
                                    DeadlineAwareAllocation()))
        wall += time.time() - t0
    common.check_not_truncated([r.summary() for r in solo_results],
                               "engine_bench:haf-solo")
    solo_evps = sum(r.n_events for r in solo_results) / wall
    out: Dict = {"family": "paper", "method": "HAF(stand-in+critic)",
                 "n_requests_per_seed": n_requests,
                 "solo_evps": round(solo_evps, 1),
                 "migrations": sum(len(r.migrations)
                                   for r in solo_results),
                 "points": []}
    for B in sizes:
        t0 = time.time()
        results = sim.run_batch(workloads[:B], placement,
                                lambda b: DeadlineAwareAllocation())
        bwall = time.time() - t0
        evps = sum(r.n_events for r in results) / bwall
        out["points"].append({"B": B, "wall_s": round(bwall, 3),
                              "events_per_sec": round(evps, 1),
                              "speedup_vs_solo": round(evps / solo_evps,
                                                       2)})
        for s in range(B):
            if _fingerprint(results[s]) != _fingerprint(solo_results[s]):
                raise RuntimeError(
                    f"engine_bench: batched HAF seed {1 + s} diverged from "
                    "its per-seed solo run — agentic equivalence broken")
    out["haf_batch_speedup"] = out["points"][-1]["speedup_vs_solo"]
    return out


# --------------------------------------------------------------------------- #
# sweep: batched single process vs process-parallel workers, end to end
# --------------------------------------------------------------------------- #
def bench_sweep(n_requests: int, n_seeds: int = 8) -> Dict:
    spec = SweepSpec(methods=("haf-static",), scenarios=("dense-urban",),
                     seeds=tuple(range(n_seeds)), n_ai_requests=n_requests,
                     workers=max(1, min(4, os.cpu_count() or 1)))
    t0 = time.time()
    rows_p = [r for r in run_sweep(spec) if r is not None]
    process_wall = time.time() - t0
    common.check_not_truncated(rows_p, "engine_bench:sweep-process")

    t0 = time.time()
    rows_b = [r for r in run_sweep(dataclasses.replace(
        spec, workers=1, batch_seeds=n_seeds)) if r is not None]
    batched_wall = time.time() - t0
    common.check_not_truncated(rows_b, "engine_bench:sweep-batched")

    if len(rows_p) != n_seeds or len(rows_b) != n_seeds:
        raise RuntimeError(
            f"engine_bench: sweep jobs failed (process {len(rows_p)}/"
            f"{n_seeds}, batched {len(rows_b)}/{n_seeds}) — wall times "
            "would compare unequal work")
    key = lambda r: (r["method"], r["scenario"], r["seed"])  # noqa: E731
    for p, b in zip(sorted(rows_p, key=key), sorted(rows_b, key=key)):
        if key(p) != key(b) or p["overall"] != b["overall"] \
                or p["n_events"] != b["n_events"]:
            raise RuntimeError("engine_bench: batched sweep rows diverged "
                               "from process-parallel rows")
    return {"n_jobs": n_seeds, "n_requests": n_requests,
            "process_workers": spec.workers,
            "process_wall_s": round(process_wall, 2),
            "batched_wall_s": round(batched_wall, 2),
            "speedup": round(process_wall / batched_wall, 2)}


def bench_solo_paper(n_requests: int) -> Dict:
    """paper-family single trace: the tiny-gather regime the scalar
    allocator fast path targets (ROADMAP solo-regression recovery)."""
    import repro.sim.cluster as cluster_mod

    sc = make_scenario("paper", seed=0)
    reqs, _ = workload_for(sc, seed=1, n_ai_requests=n_requests)
    sim = Simulator(sc)
    point: Dict = {"family": "paper", "n_requests": len(reqs)}
    saved = cluster_mod.SCALAR_GATHER_MAX
    try:
        for tag, mx in (("vector_only", -1), ("fast_path", saved)):
            cluster_mod.SCALAR_GATHER_MAX = mx
            wall = float("inf")
            for _ in range(3):
                t0 = time.time()
                res = sim.run(reqs, StaticPlacement(),
                              DeadlineAwareAllocation())
                wall = min(wall, time.time() - t0)
            point[tag] = {"wall_s": round(wall, 3),
                          "events_per_sec": round(res.n_events / wall, 1)}
    finally:
        cluster_mod.SCALAR_GATHER_MAX = saved
    point["fast_path_speedup"] = round(
        point["fast_path"]["events_per_sec"]
        / point["vector_only"]["events_per_sec"], 2)
    return point


# --------------------------------------------------------------------------- #
# profile: repro.obs phase accounting per backend (PR-6)
# --------------------------------------------------------------------------- #
def bench_profile(n_requests: int, B: int = 8,
                  engines=("numpy", "jax", "pallas")) -> Dict:
    """Per-phase wall-clock for the batched paper family on each backend.

    The device engines (jax, pallas) split the step's round trip into
    ``core.h2d`` (the call: copy in and enqueue) and ``core.d2h`` (the
    reads: wait for the device and copy back); device time itself is in
    a device trace, not here.
    """
    from repro.obs import ObsConfig

    sc = make_scenario("paper", seed=0)
    workloads = [workload_for(sc, seed=1 + s, n_ai_requests=n_requests)[0]
                 for s in range(B)]
    out: Dict = {"family": "paper", "B": B,
                 "n_requests_per_seed": n_requests, "engines": {}}
    for engine in engines:
        sim = Simulator(sc, engine="numpy" if engine == "pallas" else engine)
        try:
            results = sim.run_batch(
                workloads,
                lambda b: StaticPlacement(),
                lambda b: DeadlineAwareAllocation(),
                engine=engine,
                obs=ObsConfig(profile=True))
        except Exception as err:    # backend unavailable on this host
            out["engines"][engine] = {"error":
                                      f"{type(err).__name__}: {err}"}
            continue
        prof = results[0].profile
        phases = prof["phases"]
        host = sum(phases[k]["total_s"] for k in ("core.h2d", "core.d2h")
                   if k in phases)
        events = sum(r.n_events for r in results)
        out["engines"][engine] = {
            "wall_s": round(prof["wall_s"], 3),
            "events": events,
            "events_per_sec": round(events / max(prof["wall_s"], 1e-9), 1),
            "host_transfer_s": round(host, 4),
            "h2d_s": round(phases.get("core.h2d", {}).get("total_s", 0.0), 4),
            "d2h_s": round(phases.get("core.d2h", {}).get("total_s", 0.0), 4),
            "phases": {k: {"total_s": round(v["total_s"], 4),
                           "count": v["count"]}
                       for k, v in sorted(phases.items())},
        }
    return out


# --------------------------------------------------------------------------- #
# pr4_comparison: obs-off throughput guard against the PR-4 record
# --------------------------------------------------------------------------- #
def bench_pr4_comparison(haf: Dict) -> Dict:
    """Compare obs-off batched HAF paper-family throughput against
    ``BENCH_pr4.json`` — the observability hooks are `is None` checks on
    the hot path and must stay within 3% of the pre-obs engine.

    Raw ev/s ratios across sessions conflate hook overhead with machine
    drift (CPU co-tenancy, frequency), so the record also carries a
    drift-normalized ratio: the dense-urban StaticPlacement batched point
    is re-measured at the PR-4 scale as the drift anchor, and the HAF
    ratio is divided by the anchor ratio.  The compared HAF point is
    likewise re-measured at the PR-4 request count when the current run
    used a reduced (smoke) scale."""
    if not PR4_PATH.exists():
        return {"available": False}
    prior_all = json.loads(PR4_PATH.read_text())
    prior = prior_all["haf"]
    n_req = prior["n_requests_per_seed"]
    b_ref = max(p["B"] for p in prior["points"])
    prior_evps = next(p["events_per_sec"] for p in prior["points"]
                      if p["B"] == b_ref)
    haf_sim, haf_wls, placement = _haf_setup(n_req, b_ref)

    def run_haf() -> float:
        t0 = time.time()
        results = haf_sim.run_batch(haf_wls, placement,
                                    lambda b: DeadlineAwareAllocation())
        return sum(r.n_events for r in results) / (time.time() - t0)

    anchor = prior_all.get("batched", {})
    anchor_pt = next((p for p in anchor.get("points", [])
                      if p["B"] == b_ref), None)
    out = {"available": True, "B": b_ref, "n_requests_per_seed": n_req,
           "pr4_evps": prior_evps}
    if anchor_pt is None:
        now_evps = max(run_haf() for _ in range(2))
        out["now_evps"] = round(now_evps, 1)
        out["ratio"] = round(now_evps / prior_evps, 4)
        out["within_3pct"] = bool(out["ratio"] >= 0.97)
        return out

    # interleaved anchor/HAF pairs: each rep measures the dense-urban
    # StaticPlacement block (the drift anchor, at its PR-4 scale) and the
    # HAF block back to back, so the per-rep ratio cancels machine drift;
    # the median rep is compared to PR-4's own haf/anchor ratio
    sc = make_scenario("dense-urban", seed=0, n_nodes=anchor["n_nodes"])
    a_wls = [workload_for(sc, seed=1 + s,
                          n_ai_requests=anchor["n_requests_per_seed"])[0]
             for s in range(b_ref)]
    a_sim = Simulator(sc)

    def run_anchor() -> float:
        t0 = time.time()
        results = a_sim.run_batch(
            a_wls,
            [StaticPlacement() for _ in range(b_ref)],
            [DeadlineAwareAllocation() for _ in range(b_ref)])
        return sum(r.n_events for r in results) / (time.time() - t0)

    run_anchor(), run_haf()                 # warm-up (jit, allocator caches)
    pairs = [(run_anchor(), run_haf()) for _ in range(4)]
    # best-of-N each side: co-tenant contention only subtracts throughput,
    # so the max over interleaved reps estimates the uncontended rate
    rel_now = max(h for _, h in pairs) / max(a for a, _ in pairs)
    rel_pr4 = prior_evps / anchor_pt["events_per_sec"]
    now_evps = max(h for _, h in pairs)
    out["now_evps"] = round(now_evps, 1)
    out["ratio"] = round(now_evps / prior_evps, 4)
    out["anchor_pr4_evps"] = anchor_pt["events_per_sec"]
    out["anchor_now_evps"] = round(max(a for a, _ in pairs), 1)
    out["haf_over_anchor_pr4"] = round(rel_pr4, 4)
    out["haf_over_anchor_pr6"] = round(rel_now, 4)
    out["normalized_ratio"] = round(rel_now / rel_pr4, 4)
    out["within_3pct"] = bool(rel_now / rel_pr4 >= 0.97)
    return out


# --------------------------------------------------------------------------- #
# memory: streamed O(S + window) vs materialized O(n) arrival path (PR-7)
# --------------------------------------------------------------------------- #
MEM_SMOKE_GRID = (20_000, 200_000)
MEM_FULL_GRID = (20_000, 1_000_000)
MEM_WINDOW = 4096
# peak allocation is reached in steady state long before the trace ends, so
# the tracemalloc points cap the event loop; the stream's unprocessed tail
# is still drained (chunked) for exact accounting, so the cap never hides
# trace-length-dependent memory
MEM_EVENT_CAP = 30_000
# fixed budget for the --smoke streamed 2e5-request peak: generator chunks
# + one refill window + accumulators, independent of trace length
SMOKE_MEM_BUDGET_MB = 64.0


def _mem_scenario(n_requests: int) -> Dict:
    # hold the offered load at the n=2000 synthetic-trace baseline
    # (speedup scales arrivals): the memory question is about trace
    # LENGTH, so queue depth — and with it the allocator's working set —
    # must stay constant across grid points
    return make_scenario("trace", n_ai_requests=n_requests,
                         speedup=2000.0 / n_requests)


def _traced_peak_mb(fn) -> float:
    import gc
    import tracemalloc

    gc.collect()
    tracemalloc.start()
    try:
        fn()
        return tracemalloc.get_traced_memory()[1] / 1e6
    finally:
        tracemalloc.stop()


def bench_memory(grid=MEM_SMOKE_GRID) -> Dict:
    out: Dict = {"family": "trace", "window": MEM_WINDOW,
                 "event_cap": MEM_EVENT_CAP,
                 "smoke_budget_mb": SMOKE_MEM_BUDGET_MB, "points": []}
    for n in grid:
        sc = _mem_scenario(n)

        def run_streamed():
            stream = workload_stream_for(sc, seed=0, window=MEM_WINDOW)
            res = Simulator(sc).run(stream, StaticPlacement(),
                                    DeadlineAwareAllocation(),
                                    retain_requests=False,
                                    max_events=MEM_EVENT_CAP)
            if res.n_requests != n or res.requests:
                raise RuntimeError(
                    f"engine_bench: streamed accounting broken at n={n} "
                    f"(n_requests={res.n_requests}, "
                    f"retained={len(res.requests)})")

        def run_materialized():
            reqs = workload_stream_for(sc, seed=0).to_list()
            Simulator(sc).run(reqs, StaticPlacement(),
                              DeadlineAwareAllocation(),
                              max_events=MEM_EVENT_CAP)

        streamed = _traced_peak_mb(run_streamed)
        materialized = _traced_peak_mb(run_materialized)
        out["points"].append({
            "n_requests": n,
            "streamed_peak_mb": round(streamed, 1),
            "materialized_peak_mb": round(materialized, 1),
            "ratio": round(materialized / max(streamed, 1e-9), 1)})
    peaks = [p["streamed_peak_mb"] for p in out["points"]]
    out["streamed_peak_flat"] = bool(max(peaks) < SMOKE_MEM_BUDGET_MB)
    return out


# --------------------------------------------------------------------------- #
# trace_replay: uncapped 10^6-request streamed replay + counter
# reconciliation (full mode only — ~3e6 events through the event loop)
# --------------------------------------------------------------------------- #
def bench_trace_replay(n_requests: int = 1_000_000) -> Dict:
    from repro.obs import ObsConfig

    sc = _mem_scenario(n_requests)
    stream = workload_stream_for(sc, seed=0, window=MEM_WINDOW)
    t0 = time.time()
    res = Simulator(sc).run(stream, StaticPlacement(),
                            DeadlineAwareAllocation(),
                            retain_requests=False,
                            max_events=20_000_000,
                            obs=ObsConfig(trace=True))
    wall = time.time() - t0
    if res.truncated:
        raise RuntimeError("engine_bench: 1e6 trace replay truncated")
    counts = res.trace.counts(0)
    by_class = res.violation_counts()
    if counts["arrival"] != res.n_requests or res.n_requests != n_requests:
        raise RuntimeError(
            "engine_bench: obs arrival counter does not reconcile with the "
            f"streaming accumulators ({counts['arrival']} != "
            f"{res.n_requests} != {n_requests})")
    if counts["completion"] + counts["drop"] != counts["arrival"]:
        raise RuntimeError(
            "engine_bench: completion+drop != arrival in the 1e6 replay")
    return {"family": "trace", "n_requests": n_requests,
            "window": MEM_WINDOW, "wall_s": round(wall, 1),
            "events": res.n_events,
            "events_per_sec": round(res.n_events / wall, 1),
            "violations": by_class["overall"][1],
            "obs_counts": {k: counts[k]
                           for k in ("arrival", "completion", "drop")}}


def main(smoke: bool = False) -> Dict:
    solo_grid = SOLO_SMOKE_GRID if smoke else SOLO_FULL_GRID
    solo_points: List[Dict] = []
    for n_nodes, n_requests in solo_grid:
        p = bench_solo_point(n_nodes, n_requests)
        solo_points.append(p)
        print(f"engine,dense-urban,S={p['n_instances']},"
              f"scalar_evps={p['scalar']['events_per_sec']},"
              f"numpy_evps={p['numpy']['events_per_sec']},"
              f"speedup={p['speedup']}x", flush=True)

    solo_paper = bench_solo_paper(1500 if smoke else 4000)
    print(f"engine-solo,paper,"
          f"vector_evps={solo_paper['vector_only']['events_per_sec']},"
          f"fastpath_evps={solo_paper['fast_path']['events_per_sec']},"
          f"speedup={solo_paper['fast_path_speedup']}x", flush=True)

    batched = bench_batched(36, 1200 if smoke else 4000)
    for p in batched["points"]:
        print(f"engine-batch,dense-urban,B={p['B']},"
              f"evps={p['events_per_sec']},"
              f"speedup_vs_solo={p['speedup_vs_solo']}x", flush=True)

    haf = bench_haf(600 if smoke else 2000)
    for p in haf["points"]:
        print(f"engine-haf,paper,B={p['B']},"
              f"evps={p['events_per_sec']},"
              f"speedup_vs_solo={p['speedup_vs_solo']}x", flush=True)

    sweep = bench_sweep(400 if smoke else 1500)
    print(f"engine-sweep,dense-urban,jobs={sweep['n_jobs']},"
          f"process_wall={sweep['process_wall_s']}s,"
          f"batched_wall={sweep['batched_wall_s']}s,"
          f"speedup={sweep['speedup']}x", flush=True)

    profile = bench_profile(600 if smoke else 2000)
    for engine, p in profile["engines"].items():
        if "error" in p:
            print(f"engine-profile,paper,engine={engine},"
                  f"error={p['error']}", flush=True)
            continue
        print(f"engine-profile,paper,engine={engine},"
              f"evps={p['events_per_sec']},"
              f"host_transfer_s={p['host_transfer_s']},"
              f"h2d_s={p['h2d_s']},d2h_s={p['d2h_s']}", flush=True)

    pr4_cmp = bench_pr4_comparison(haf)
    if pr4_cmp.get("available"):
        norm = pr4_cmp.get("normalized_ratio", pr4_cmp["ratio"])
        print(f"engine-pr4cmp,paper,B={pr4_cmp['B']},"
              f"pr4_evps={pr4_cmp['pr4_evps']},"
              f"now_evps={pr4_cmp['now_evps']},"
              f"ratio={pr4_cmp['ratio']},"
              f"drift_normalized={norm}", flush=True)

    memory = bench_memory(MEM_SMOKE_GRID if smoke else MEM_FULL_GRID)
    for p in memory["points"]:
        print(f"engine-memory,trace,n={p['n_requests']},"
              f"streamed_peak_mb={p['streamed_peak_mb']},"
              f"materialized_peak_mb={p['materialized_peak_mb']},"
              f"ratio={p['ratio']}x", flush=True)

    replay = None
    if not smoke:
        replay = bench_trace_replay()
        print(f"engine-replay,trace,n={replay['n_requests']},"
              f"wall_s={replay['wall_s']},"
              f"evps={replay['events_per_sec']},"
              f"arrivals={replay['obs_counts']['arrival']}", flush=True)

    record = {
        "kind": "repro.bench.engine",
        "pr": 7,
        "smoke": smoke,
        "default_engine": "numpy",
        "solo_points": solo_points,
        "solo_paper": solo_paper,
        "batched": batched,
        "haf": haf,
        "sweep": sweep,
        "profile": profile,
        "pr4_comparison": pr4_cmp,
        "memory": memory,
        "trace_replay": replay,
    }
    BENCH_PATH.write_text(json.dumps(record, indent=2, sort_keys=True))
    print(f"# record -> {BENCH_PATH}", flush=True)
    if batched["batch_speedup_max_b"] < 3.0:
        print(f"# WARNING: batched B={BATCH_SIZES[-1]} aggregate speedup is "
              f"{batched['batch_speedup_max_b']}x (< 3x target)", flush=True)
    if haf["haf_batch_speedup"] < 1.5:
        print(f"# WARNING: batched HAF B={HAF_BATCH_SIZES[-1]} speedup is "
              f"{haf['haf_batch_speedup']}x (< 1.5x target)", flush=True)
    if sweep["speedup"] < 1.0:
        print("# WARNING: batched sweep slower than process-parallel "
              f"({sweep['batched_wall_s']}s vs {sweep['process_wall_s']}s)",
              flush=True)
    if pr4_cmp.get("available") and not pr4_cmp["within_3pct"]:
        norm = pr4_cmp.get("normalized_ratio", pr4_cmp["ratio"])
        print(f"# WARNING: obs-off batched HAF throughput is "
              f"{norm:.3f}x the PR-4 record (drift-normalized, < 0.97 — "
              f"instrumentation hooks may be taxing the engine)",
              flush=True)
    if not memory["streamed_peak_flat"]:
        print(f"# WARNING: streamed peak memory exceeds the "
              f"{SMOKE_MEM_BUDGET_MB:.0f}MB O(S+window) budget: "
              f"{[p['streamed_peak_mb'] for p in memory['points']]}MB",
              flush=True)
    return record


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced request counts (CI)")
    args = ap.parse_args()
    main(smoke=args.smoke)
