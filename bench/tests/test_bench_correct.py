"""The comparison that decides `correct`, on the CPU at a small size.

The plain reference must agree with the program exactly; the control (the
reference with a float32 event core) and every fault the cells can have,
planted under a whole run of the harness, must come out not correct."""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import tinybench
import blocks
import compare
import reference

CELLS = ["paper-static-b32", "paper-static-b256"]
LIMITS = json.loads((tinybench.BENCH / "cells" / "static-b32.json")
                    .read_text())["limits"]
HAF = {"name": "haf", "params": {"agent": "qwen3-32b-sim",
                                 "critic_path": None}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinybench.tiny_copy(tmp_path_factory.mktemp("bench"))


def program_and_reference(root, traffic, engine):
    """(program outcome, reference outcome, request rows) per replica of a
    two-seed block of the paper deployment."""
    cfg = json.loads((root / "bench" / "configs" / "paper-table1.json")
                     .read_text())
    tr = json.loads((root / "bench" / "cells" / f"{traffic}.json")
                    .read_text())
    cache = {}
    jobs = blocks.make_jobs(cfg, tr, [5, 2**31 + 11], engine, cache)
    results = blocks.run_block(jobs)
    dep = blocks.deployment_data(cache["scenario"])
    out = []
    for job, res in zip(jobs, results):
        stream = blocks.job_stream(job)
        rows = blocks.request_rows(stream)
        ref = reference.simulate(dep, rows, stream.horizon,
                                 blocks.reference_method(tr["method"]))
        out.append((compare.program_outcome(res),
                    compare.reference_outcome(ref, dep), rows))
    return out


@pytest.mark.parametrize("traffic", ["static-b32", "static-b256"])
def test_reference_equals_the_numpy_engine_exactly(root, traffic):
    for got, want, rows in program_and_reference(root, traffic, "numpy"):
        bad, n_bad, gaps = compare.compare_replica(got, want, rows)
        assert (bad, n_bad) == ([], 0) and gaps.size and not gaps.any()


def test_reference_agent_reads_the_closing_epochs_arrivals(monkeypatch):
    """P3 of the stand-in agent scales the outage by the service's arrival
    rate over the epoch that closes, as the snapshot defines it."""
    seen = []
    pick = reference.Replica.stand_in

    def spy(self, k, t, snap):
        seen.append(dict(snap["rates"]))
        return pick(self, k, t, snap)
    monkeypatch.setattr(reference.Replica, "stand_in", spy)
    cfg = json.loads((tinybench.BENCH / "configs" / "paper-table1.json")
                     .read_text())
    cache = {}
    job, = blocks.make_jobs(cfg, {"method": HAF, "n_ai_requests": 60,
                                  "batch": 1}, [3], "numpy", cache)
    stream = blocks.job_stream(job)
    rows = blocks.request_rows(stream)
    dep = blocks.deployment_data(cache["scenario"])
    reference.simulate(dep, rows, stream.horizon,
                       blocks.reference_method(HAF))
    counts = {}
    for r in rows:                    # arrivals seen before the first epoch
        ran = r[1] == reference.RAN
        if r[2] + (0.0 if ran else dep["ran_packet"]) < 5.0:
            key = "ran" if ran else r[11]
            counts[key] = counts.get(key, 0) + 1
    assert counts["ran"] and len(counts) > 1
    assert seen[0] == {k: n / 5.0 for k, n in counts.items()}


@pytest.mark.parametrize("method,want", [
    ("haf-static", {"placement": "static"}),
    (HAF, {"placement": "stand-in", "agent": {
        "name": "qwen3-32b-sim", "seed": 0, "noise": 0.10, "ran_weight": 1.0,
        "outage_weight": 1.0, "eagerness": 0.0, "threshold": 0.25}}),
])
def test_reference_method_comes_from_the_method_table(method, want):
    assert blocks.reference_method(method) == want


def test_a_method_the_reference_lacks_is_refused():
    with pytest.raises(KeyError):
        blocks.reference_method("lyapunov")
    with pytest.raises(ValueError):
        blocks.reference_method({"name": "haf",
                                 "params": {"critic_path": "@critic"}})


@pytest.mark.parametrize("cell", CELLS)
def test_float32_control_fails_the_limit(root, cell):
    """The control: the reference's event core in float32, the precision
    below the configuration's float64, in the program's place and judged
    by the run's own comparison."""
    import control
    for seed in (7, 2**31 + 5):
        ok, numbers = control.control(root, cell, seed)
        assert not ok, numbers
        assert numbers["finish_gap_p90_s"]["value"] > \
            numbers["finish_gap_p90_s"]["limit"]


def test_perturbed_finish_times_fail_though_every_report_row_matches(root):
    """Every finish time 1e-4 of its latency late: no verdict flips."""
    got, want, rows = program_and_reference(root, "static-b32", "numpy")[0]
    arrival = np.array([r[2] for r in rows])
    done = got["finish"] >= 0
    got["finish"] = np.where(done, arrival + (got["finish"] - arrival)
                             * 1.0001, got["finish"])
    bad, n_bad, gaps = compare.compare_replica(got, want, rows)
    assert n_bad == 0, bad                    # the report rows all match
    ok, numbers = compare.judge(n_bad, [gaps], LIMITS)
    assert not ok and numbers["finish_gap_p90_s"]["value"] > 1e-9


# -- faults planted under a whole run of the harness ----------------------- #
def jitted(fn):
    import jax
    return jax.jit(fn)


def step_unchanged(kec):
    orig = kec.event_step_jax.__wrapped__

    def step(rem_g, rem_c, alloc_g, alloc_c, avail, t, t_ev, live):
        rg, rc, started, t_comp, sid = orig(rem_g, rem_c, alloc_g, alloc_c,
                                            avail, t, t_ev, live)
        return rem_g, rem_c, started & False, t_comp, sid
    return step


def step_gpu_slower(kec):
    """The answer altered where it is produced: GPU stages progress 1%
    slower inside the step."""
    orig = kec.event_step_jax.__wrapped__

    def step(rem_g, rem_c, alloc_g, alloc_c, avail, t, t_ev, live):
        return orig(rem_g, rem_c, alloc_g * 0.99, alloc_c, avail, t, t_ev,
                    live)
    return step


@pytest.mark.parametrize("fault", ["clean", "step_unchanged",
                                   "gpu_stage_slower", "half_batch"])
def test_planted_faults_come_out_not_correct(root, capsys, monkeypatch,
                                             fault):
    from repro.kernels import event_core as kec
    from repro.sim import Simulator
    if fault == "step_unchanged":
        monkeypatch.setattr(kec, "event_step_jax",
                            jitted(step_unchanged(kec)))
    elif fault == "gpu_stage_slower":
        monkeypatch.setattr(kec, "event_step_jax",
                            jitted(step_gpu_slower(kec)))
    elif fault == "half_batch":
        full = Simulator.run_batch

        def half(self, workloads, placements, allocations, **kw):
            """Half of the batch simulated, its results standing in for
            the rest."""
            h = max(len(workloads) // 2, 1)
            out = full(self, workloads[:h], placements[:h],
                       allocations[:h], **kw)
            return (out * 2)[:len(workloads)]
        monkeypatch.setattr(Simulator, "run_batch", half)
    line = tinybench.run_cell(root, "paper-static-b32", capsys)
    assert line["correct"] is (fault == "clean"), line["checks"]
    assert list(line["checks"]) == ["outcome_mismatches",
                                   "finish_gap_p90_s"]


def test_a_block_missing_replicas_is_not_correct(root, capsys, monkeypatch):
    from repro.sim import Simulator
    full = Simulator.run_batch

    def short(self, workloads, placements, allocations, **kw):
        return full(self, workloads[:1], placements[:1], allocations[:1],
                    **kw)
    monkeypatch.setattr(Simulator, "run_batch", short)
    line = tinybench.run_cell(root, "paper-static-b32", capsys)
    assert line["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_cells_run_and_are_correct(root, capsys, cell):
    line = tinybench.run_cell(root, cell, capsys)
    assert line["correct"] is True
    assert line["metrics"]["sim_events_per_s"]["unit"] == "events/s"
    assert set(line["device"]) >= {"platform", "kind", "count"}


def harness_cmd(workdir):
    return [sys.executable, str(pathlib.Path(workdir) / "bench" / "run.py"),
            "--workload", "paper-static-b32", "--seed", "3000000019",
            "--seconds", "1", "--trace", "0"]


def test_no_tpu_fails_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(harness_cmd(tinybench.REPO), env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=tinybench.REPO)
    assert proc.returncode != 0
    assert "cpu" in proc.stderr and "FAILED" in proc.stderr
    assert '"metrics"' not in proc.stdout


def test_benchmark_files_alone_fail_without_a_result(tmp_path):
    import shutil
    shutil.copy(tinybench.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(tinybench.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(harness_cmd(tmp_path), env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=tmp_path)
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout


def test_one_amplified_gap_passes_and_a_broad_shift_fails():
    """The compared number is a percentile: rounding amplified in the odd
    request passes, a gap in a tenth of the requests or more does not."""
    gaps = [np.zeros(500), np.r_[np.zeros(499), 1e-7]]
    assert compare.judge(0, gaps, LIMITS)[0]
    gaps = [np.r_[np.zeros(450), np.full(50, 2e-8)], np.zeros(400)]
    assert compare.judge(0, gaps, LIMITS)[0]
    gaps = [np.r_[np.zeros(400), np.full(100, 2e-8)], np.zeros(400)]
    assert not compare.judge(0, gaps, LIMITS)[0]
