"""Shared benchmark scaffolding on top of the repro.sim.scenarios registry
and the repro.eval fleet harness.

Scale: REPRO_FULL=1 runs the paper-scale request counts (Table I: 20k at
ρ=1.0, 15k/25k at 0.75/1.25); the default is a 4× reduced load with the
same operating points so `python -m benchmarks.run` finishes on one CPU.
REPRO_WORKERS sets the sweep parallelism (default: up to 4 processes, or
1 when REPRO_ENGINE names a device engine).
"""
from __future__ import annotations

import os
import pathlib
import pickle
import time
from typing import Dict, List, Optional

from repro.core.critic import Critic
from repro.core.datagen import harvest, samples_fingerprint
from repro.core import train_critic
from repro.eval import SweepSpec, run_sweep
from repro.exp import run_experiment, save_critic
from repro.exp.artifacts import ARTIFACTS_ENV
from repro.sim import Simulator, make_scenario, workload_for
from repro.sim.event_core import DEVICE_ENGINES

ROOT = pathlib.Path(__file__).resolve().parents[1]
ARTIFACTS = ROOT / "artifacts"
EXPERIMENTS = ROOT / "experiments"
# artifact references (@critic, ...) in benchmark specs resolve against the
# repo's store whatever the caller's cwd is
os.environ.setdefault(ARTIFACTS_ENV, str(ARTIFACTS))
FULL = os.environ.get("REPRO_FULL", "0") == "1"
ENGINE = os.environ.get("REPRO_ENGINE", "numpy")
# a device engine runs in the one process that holds the chip
WORKERS = int(os.environ.get("REPRO_WORKERS",
                             1 if ENGINE in DEVICE_ENGINES
                             else max(1, min(4, os.cpu_count() or 1))))

# paper request counts (Table I / §IV-3); default = /4 for CPU runtime
REQUESTS = {0.75: 15000, 1.0: 20000, 1.25: 25000} if FULL else \
           {0.75: 3750, 1.0: 5000, 1.25: 6250}

DEFAULT_AGENT = "qwen3-32b-sim"

_scenarios: Dict[str, Dict] = {}


def scenario(name: str = "paper", **params) -> Dict:
    """Registry scenario, cached per (name, params)."""
    key = name + repr(sorted(params.items()))
    if key not in _scenarios:
        _scenarios[key] = make_scenario(name, **params)
    return _scenarios[key]


def workload(rho: float, seed: int = 0):
    return workload_for(scenario(), seed=seed, rho=rho,
                        n_ai_requests=REQUESTS[rho])[0]


def get_critic(retrain: bool = False) -> Critic:
    """The frozen critic artifact (trained offline once, reused everywhere)."""
    path = critic_path()
    if path.exists() and not retrain:
        return Critic.load(str(path))
    print("# training critic (offline phase: exploration + counterfactual "
          "probes + supervised regression)...", flush=True)
    samples = harvest(scenario(), verbose=False)
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    with open(ARTIFACTS / "critic_samples.pkl", "wb") as f:
        pickle.dump(samples, f)
    critic = train_critic(samples, epochs=2000, seed=0)
    save_critic(critic, path, families=("paper",),
                data_hash=samples_fingerprint(samples),
                meta={"epochs": 2000, "n_samples": len(samples),
                      "trainer": "benchmarks.common.get_critic"})
    return critic


def critic_path() -> pathlib.Path:
    return ARTIFACTS / "critic.json"


def simulator(engine: Optional[str] = None) -> Simulator:
    return Simulator(scenario(), epoch_interval=5.0,
                     engine=engine or ENGINE)


def check_not_truncated(rows, where: str) -> None:
    """Benchmarks must fail loudly on partial runs: a table built from a
    simulation that hit ``max_events`` mid-trace is not a reproduction."""
    bad = [r for r in rows if r.get("truncated")]
    if bad:
        names = [f"{r.get('method', '?')}@{r.get('scenario', '?')}"
                 f"#s{r.get('seed', '?')}" for r in bad]
        raise RuntimeError(
            f"{where}: {len(bad)} run(s) hit max_events and returned "
            f"truncated results: {', '.join(names)} — raise max_events")


def experiment_rows(spec, where: str, verbose: bool = False) -> List[Dict]:
    """Run an :class:`repro.exp.ExperimentSpec` and return completed rows.

    The stamped report (provenance: spec hashes, scenario + critic
    fingerprints, backend info) is written to ``spec.out``; benchmarks
    recompute rather than resume so a printed table is never stale.
    """
    report = run_experiment(spec, resume=False, verbose=verbose)
    rows = list(report["runs"])
    check_not_truncated(rows, where)
    return rows


def sweep(methods, scenarios, seeds=(0,), workers: Optional[int] = None,
          **kw) -> List[Dict]:
    """Run a policies × scenarios × seeds grid through repro.eval.

    Returns only completed rows: failed jobs (None slots, already reported
    by run_sweep) are dropped so callers can print/post-process directly.
    """
    spec = SweepSpec(methods=tuple(methods), scenarios=tuple(scenarios),
                     seeds=tuple(seeds), engine=kw.pop("engine", ENGINE),
                     workers=WORKERS if workers is None else workers, **kw)
    rows = [r for r in run_sweep(spec) if r is not None]
    check_not_truncated(rows, "sweep")
    return rows


def run_method(name: str, placement, allocation, requests,
               rr_dispatch: bool = False) -> Dict[str, float]:
    """Single in-process run (ablations that hold live policy objects)."""
    t0 = time.time()
    res = simulator().run(requests, placement, allocation,
                          rr_dispatch=rr_dispatch)
    s = res.summary()
    s["wall_s"] = time.time() - t0
    s["method"] = name
    check_not_truncated([s], name)
    return s


def csv_row(table: str, s: Dict) -> str:
    return (f"{table},{s['method']},overall={s['overall']:.4f},"
            f"ran={s['ran']:.4f},ai={s['ai']:.4f},"
            f"large={s['large_ai']:.4f},small={s['small_ai']:.4f},"
            f"mig={s['mig_large']}/{s['mig_total']},"
            f"wall_s={s['wall_s']:.1f}")
