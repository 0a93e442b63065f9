"""Blocks of the window: B seeds of one (deployment, method), realized and
run as the sweep runs them, from the program's public helpers.

The assembly the sweep keeps private (realizing a job's workload stream,
``repro.eval.sweep._job_stream``) is copied here, so that the yardstick
does not move with the program.  This module also turns the program's
scenario and requests into the plain data the reference reads, and a
traffic file's method into the reference's parameters.
"""
from __future__ import annotations

import hashlib
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
CATEGORY = {"DU": 0, "CUUP": 1, "LARGE_AI": 2, "SMALL_AI": 3}
REQUEST_CLASS = {"RAN": 0, "LARGE_AI": 1, "SMALL_AI": 2}


def block_seeds(run_seed: int, block: int, B: int):
    """Seeds of block ``block`` of a run with ``--seed run_seed``: a
    function of the two alone, so every block can be recomputed."""
    out = []
    for b in range(B):
        h = hashlib.sha256(f"{run_seed}:{block}:{b}".encode()).digest()
        out.append(int.from_bytes(h[:4], "little"))
    return out


def make_jobs(config: dict, traffic: dict, seeds, engine: str,
              scenario_cache: dict):
    """The block's jobs, expanded from the program's declarative
    experiment spec, each with the deployment attached (built once per
    process: ``scenario_cache`` holds it across blocks)."""
    from repro.exp import ExperimentSpec
    jobs = ExperimentSpec(
        name="bench", methods=(traffic["method"],),
        scenarios=(config["scenario"],), seeds=tuple(seeds),
        n_ai_requests=traffic["n_ai_requests"], engine=engine,
        batch=max(traffic["batch"], 1), workers=1).expand()
    if "scenario" not in scenario_cache:
        from repro.eval.sweep import attach_scenarios
        attach_scenarios(jobs)
        scenario_cache["scenario"] = jobs[0]["scenario"]
    for job in jobs:
        job["scenario"] = scenario_cache["scenario"]
    return jobs


def job_stream(job: dict):
    """The job's workload, materialized as the sweep feeds it to the
    engine (copied from ``repro.eval.sweep._job_stream``)."""
    from repro.sim.scenarios import workload_stream_for
    stream = workload_stream_for(job["scenario"], seed=job["seed"],
                                 n_ai_requests=job.get("n_ai_requests"),
                                 rho=job.get("rho"),
                                 window=job.get("window") or None)
    return stream.materialize()


def run_block(jobs, obs=None):
    """Run the block through the program's entry, ``Simulator.run_batch``.
    Returns one ``SimResult`` per job, requests retained."""
    from repro.eval.policies import make_method
    from repro.sim import Simulator
    base = jobs[0]
    workloads = [job_stream(job) for job in jobs]
    methods = [make_method(job["method"], **job["method_params"])
               for job in jobs]
    sim = Simulator(base["scenario"], epoch_interval=base["epoch_interval"],
                    engine=base["engine"])
    return sim.run_batch(workloads, [m[0] for m in methods],
                         [m[1] for m in methods], rr_dispatch=methods[0][2],
                         max_events=base["max_events"], obs=obs)


def reference_method(method) -> dict:
    """The reference's plain parameters for a method of a traffic file
    (``"haf-static"`` or ``{"name": "haf", "params": {"agent": ...}}``),
    from the benchmark's own table ``bench/methods.json``."""
    table = json.loads((HERE / "methods.json").read_text())
    if isinstance(method, str):
        method = {"name": method}
    name, params = method["name"], dict(method.get("params", {}))
    if name not in table:
        raise KeyError(f"the reference has no method {name!r}; "
                       f"known: {sorted(table)}")
    entry = table[name]
    if entry["placement"] == "static":
        if params:
            raise ValueError(f"{name!r} takes no parameters: {params}")
        return {"placement": "static"}
    agent = params.pop("agent", "qwen3-32b-sim")
    seed = params.pop("seed", entry["agent_seed"])
    if params.pop("critic_path", None) or params:
        raise ValueError(f"the reference runs {name!r} with a stand-in "
                         f"agent and nothing else: {method}")
    return {"placement": entry["placement"],
            "agent": dict(entry["agents"][agent], name=agent, seed=seed)}


# -- plain data for the reference ----------------------------------------- #
def deployment_data(sc: dict) -> dict:
    """The deployment as lists of numbers (no program objects)."""
    if sc.get("outages") or sc.get("churn") or sc.get("autoscale"):
        raise ValueError("the reference models no faults or autoscaling")
    nodes, insts = sc["nodes"], sc["instances"]
    return {
        "gpu": [float(n.gpu_flops) for n in nodes],
        "cpu": [float(n.cpu_cores) for n in nodes],
        "vram": [float(n.vram_bytes) for n in nodes],
        "cat": [CATEGORY[i.category.value] for i in insts],
        "weight": [float(i.weight_bytes) for i in insts],
        "reconfig_s": [float(i.reconfig_s) for i in insts],
        "cell": [int(i.cell) for i in insts],
        "arch": [str(i.arch) for i in insts],
        "movable": [bool(i.movable) for i in insts],
        "placement": [int(p) for p in sc["placement"]],
        "service_sids": {k: [int(s) for s in v]
                         for k, v in sc["service_sids"].items()},
        "delta": float(sc["transport_delay"]),
        "ran_packet": float(sc["ran_packet_delay"]),
    }


def request_rows(stream):
    """The request table in emission order: (rid, class, arrival,
    deadline, cell, du_g, du_c, cuup_c, ai_g, ai_c, kv, service)."""
    return [(int(r.rid), REQUEST_CLASS[r.cls.value], float(r.arrival),
             float(r.deadline), int(r.cell), float(r.du_work_g),
             float(r.du_work_c), float(r.cuup_work_c), float(r.ai_work_g),
             float(r.ai_work_c), float(r.kv_bytes), str(r.service))
            for chunk in stream.chunks() for r in chunk]


def digest(obj) -> str:
    """Short digest of plain data (canary of the inputs' generators)."""
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()
                          ).hexdigest()[:16]
