"""The epoch snapshot describes the epoch that closes.

An epoch decision reads the per-service arrival rates and the per-class
fulfilment of the window ``[t_{k-1}, t_k)``; the window is cleared only
after the snapshot is built.  Pinned three ways: the snapshot's numbers
against the requests themselves, HAF through ``run_batch`` against the
benchmark's plain reference (``bench/reference.py``, which imports none
of the program), and the solo driver against the batched one.
"""
import json
import math
import pathlib
import sys

import pytest

from repro.eval import make_method
from repro.sim import Simulator, make_scenario, workload_for
from repro.sim.types import RequestClass

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import blocks  # noqa: E402
import compare  # noqa: E402
import reference  # noqa: E402

HAF = {"name": "haf", "params": {"agent": "qwen3-32b-sim",
                                 "critic_path": None}}
SEEDS = [1000 + 7919 * i for i in range(48)]
N_REQUESTS = 60


def test_snapshot_carries_the_closing_windows_rates_and_fulfilment():
    sc = make_scenario("paper", seed=0)
    reqs, _ = workload_for(sc, seed=3, n_ai_requests=150)
    placement, allocation, rr = make_method("haf")
    res = Simulator(sc).run(reqs, placement, allocation, rr_dispatch=rr)
    assert len(res.epochs) >= 3
    packet = sc["ran_packet_delay"]
    for k, rec in enumerate(res.epochs):
        t0, t1 = rec.t - 5.0, rec.t
        # arrivals the engine admitted in the window: a RAN packet at its
        # arrival, an AI request after the RAN packet delay
        seen = {}
        for r in res.requests:
            ran = r.cls == RequestClass.RAN
            at = r.arrival + (0.0 if ran else packet)
            if t0 <= at < t1:
                key = "ran" if ran else r.service
                seen[key] = seen.get(key, 0) + 1
        snap = rec.snapshot
        assert snap.arrival_rate == {key: n / 5.0 for key, n in seen.items()}
        # outcomes recorded in the window (no drops: drop_expired is off)
        done = {}
        for r in res.requests:
            if r.finish >= 0 and t0 <= r.finish < t1:
                d = done.setdefault(r.cls.value, [0, 0])
                d[0] += int(r.fulfilled())
                d[1] += 1
        want = {cls.value: (done[cls.value][0] / done[cls.value][1]
                            if cls.value in done else 1.0)
                for cls in RequestClass}
        assert snap.recent_fulfill == want
        if k:                 # the record of the previous epoch agrees
            prev = res.epochs[k - 1]
            assert prev.fulfill == (want["LARGE_AI"], want["SMALL_AI"],
                                    want["RAN"])
    assert any(v < 1.0 for rec in res.epochs
               for v in rec.snapshot.recent_fulfill.values())


@pytest.fixture(scope="module")
def haf_block():
    """(jobs, deployment) of a block of 48 seeds of HAF on the paper
    deployment, built as the benchmark builds one."""
    cfg = json.loads((BENCH / "configs" / "paper-table1.json").read_text())
    cache = {}
    jobs = blocks.make_jobs(cfg, {"method": HAF, "n_ai_requests": N_REQUESTS,
                                  "batch": len(SEEDS)}, SEEDS, "numpy",
                            cache)
    return jobs, cache["scenario"]


@pytest.mark.parametrize("engine", ["numpy", "jax"])
def test_haf_run_batch_equals_the_reference(haf_block, engine):
    if engine == "jax":
        pytest.importorskip("jax")
    jobs, sc = haf_block
    jobs = [dict(job, engine=engine) for job in jobs]
    results = blocks.run_block(jobs)
    dep = blocks.deployment_data(sc)
    migrations = 0
    for job, res in zip(jobs, results):
        stream = blocks.job_stream(job)
        rows = blocks.request_rows(stream)
        ref = reference.simulate(dep, rows, stream.horizon,
                                 blocks.reference_method(HAF))
        bad, n_bad, gaps = compare.compare_replica(
            compare.program_outcome(res), compare.reference_outcome(ref, dep),
            rows)
        assert (bad, n_bad) == ([], 0), (job["seed"], bad)
        assert gaps.size and (abs(gaps) <= 1e-8).all(), job["seed"]
        migrations += len(res.migrations)
    assert migrations > 0


def _outcome(res):
    """Every outcome of a replica, NaN-free so that ``==`` compares it."""
    summary = {k: None if isinstance(v, float) and math.isnan(v) else v
               for k, v in res.summary().items()}
    return (summary, res.n_events, res.infeasible_events, res.truncated,
            sorted(res.dropped),
            [(r.rid, r.finish, r.target_sid) for r in res.requests],
            [(t, a.sid, a.src, a.dst) for t, a in res.migrations],
            [(e.epoch, e.t, e.fulfill, e.counts, e.snapshot.arrival_rate,
              e.snapshot.recent_fulfill) for e in res.epochs])


def test_haf_solo_run_equals_run_batch(haf_block):
    jobs, sc = haf_block
    batch = blocks.run_block(jobs)
    for job, res in zip(jobs, batch):
        placement, allocation, rr = make_method(job["method"],
                                                **job["method_params"])
        sim = Simulator(sc, epoch_interval=job["epoch_interval"])
        solo = sim.run(blocks.job_stream(job), placement, allocation,
                       rr_dispatch=rr, max_events=job["max_events"])
        assert _outcome(solo) == _outcome(res), job["seed"]
