"""Benchmark of the batched simulator sweep on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a deployment
(``bench/configs/<config>.json``) under a traffic mix
(``bench/cells/<traffic>.json``: the method, the engine, the seeds per
block and the AI requests per seed).  Set-up builds the deployment,
compiles the cell's shapes and runs one short warm block.  The window then
runs whole blocks back to back, each B fresh seeds drawn from ``--seed``
and the block's index, through the program's own entry
(``Simulator.run_batch``); no block starts after ``--seconds``.  After the window a sample of the replicas is
run again through the plain reference (``bench/reference.py``) on the
host and compared (``bench/compare.py``).

``--trace 0`` reports the end-to-end metrics; ``--trace 1`` profiles the
window's phases, traces the first block on the device and reports the
per-layer metrics, each read by ``bench/metrics/<metric>.py``.  The last
line of standard output is one JSON object; the compared numbers and
their limits are the last lines of standard error.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(root: pathlib.Path, name: str):
    """(BENCHMARK.json, cell entry, config file, traffic file)."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / config["file"]).read_text())
    traffic = json.loads((root / "bench" / "cells"
                          / f"{cell['traffic']}.json").read_text())
    return bench, cell, cfg, traffic


def metric_reader(root: pathlib.Path, name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileCounter:
    """Counts programs traced or compiled by JAX while ``active``."""

    def __init__(self, jax):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, duration: float, **_) -> None:
        if self.active and event in ("/jax/core/compile/jaxpr_trace_duration",
                                     "/jax/core/compile/"
                                     "backend_compile_duration"):
            self.count += 1


def core_cache_sizes() -> dict:
    from repro.kernels import event_core as kec
    return {f.__name__: f._cache_size() for f in
            (kec.event_step_jax, kec.next_completion_jax, kec.advance_jax)}


def check_inputs(cfg: dict, scenario: dict) -> list:
    """Compare the generators' output on the config's canary with the
    digests the config file records; returns what changed."""
    import blocks
    canary = cfg["canary"]
    dep = blocks.deployment_data(scenario)
    job = {"scenario": scenario, "seed": canary["seed"],
           "n_ai_requests": canary["n_ai_requests"], "rho": None}
    rows = blocks.request_rows(blocks.job_stream(job))
    changed = []
    if blocks.digest(dep) != canary["deployment_digest"]:
        changed.append("deployment")
    if blocks.digest(rows) != canary["workload_digest"]:
        changed.append("workload")
    return changed


def run(argv=None, require_chip: bool = True, root: pathlib.Path = ROOT
        ) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = T_PROCESS if root == ROOT else time.perf_counter()

    bench, cell, cfg, traffic = load_cell(root, args.workload)
    sys.path.insert(0, str(root / "src"))
    import jax
    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = device_info(jax)
    if require_chip and (dev["platform"] != "tpu"
                         or dev["count"] < cell["chips"]):
        say(f"# FAILED: the cell needs {cell['chips']} TPU chip(s); JAX "
            f"finds {dev['count']} {dev['platform']} device(s) "
            f"({dev['kind']})")
        return 2

    import numpy as np
    import blocks
    import compare
    import reference
    from repro.obs import ObsConfig

    B = traffic["batch"]
    engine = traffic["engine"]
    cache: dict = {}
    # the warm block runs as the window will, profiled where it is traced,
    # so that the window finds every program it calls compiled
    obs = ObsConfig(profile=True) if args.trace else None
    with jax.profiler.TraceAnnotation("bench.setup"):
        blocks.make_jobs(cfg, traffic, [0], engine, cache)
        scenario = cache["scenario"]
        changed = check_inputs(cfg, scenario)
        warm = dict(traffic, n_ai_requests=traffic["warm_requests"])
        blocks.run_block(blocks.make_jobs(cfg, warm,
                                          blocks.block_seeds(args.seed, -1, B),
                                          engine, cache), obs)
    say(f"# set-up: {cell['name']} S={len(scenario['instances'])} B={B} "
        f"engine={engine} device={dev}")

    counter = CompileCounter(jax)
    sizes0 = core_cache_sizes()
    trace_dir = None
    ran = []                                  # (jobs, results) per block
    setup_s = time.perf_counter() - t_start
    counter.active = True
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < args.seconds:
        jobs = blocks.make_jobs(cfg, traffic,
                                blocks.block_seeds(args.seed, len(ran), B),
                                engine, cache)
        if args.trace and not ran:
            trace_dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench.block"):
                results = blocks.run_block(jobs, obs)
            jax.profiler.stop_trace()
        else:
            results = blocks.run_block(jobs, obs)
        ran.append((jobs, results))
    window_s = time.perf_counter() - t0
    counter.active = False
    sizes1 = core_cache_sizes()
    say(f"# window: {len(ran)} blocks in {window_s} s; programs compiled "
        f"inside the window: {counter.count} (jit cache sizes before "
        f"{sizes0}, after {sizes1})")
    stats = dev_memory(jax)
    events = sum(r.n_events for _, results in ran for r in results)
    attempted = sum(len(results) for _, results in ran)
    truncated = sum(r.truncated for _, results in ran for r in results)

    # -- correct: a sample of replicas against the plain reference ------- #
    dep = blocks.deployment_data(scenario)
    method = blocks.reference_method(traffic["method"])
    pool = [(j, b) for j, (_, results) in enumerate(ran)
            for b in range(len(results))]
    rng = np.random.default_rng([args.seed, 0x5EED])
    longest = max(pool, key=lambda jb: ran[jb[0]][1][jb[1]].n_events)
    rest = [p for p in pool if p != longest]
    k = min(traffic["check_replicas"], len(pool)) - 1
    picks = [longest] + [rest[i] for i in
                         sorted(rng.choice(len(rest), k, replace=False))]
    # a replica whose result never came back, or that stopped at the
    # event budget, is a mismatched outcome whether sampled or not
    missing = sum(len(jobs) - len(results) for jobs, results in ran)
    mismatches, gaps, failed = missing + truncated, [], missing + truncated
    if missing or truncated:
        say(f"# MISMATCH: of the window's replicas {missing} returned no "
            f"result and {truncated} were truncated")
    t_ref = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.reference"):
        for j, b in picks:
            job, res = ran[j][0][b], ran[j][1][b]
            stream = blocks.job_stream(job)
            rows = blocks.request_rows(stream)
            ref = reference.simulate(dep, rows, stream.horizon, method,
                                     job["epoch_interval"])
            bad, n_bad, g = compare.compare_replica(
                compare.program_outcome(res),
                compare.reference_outcome(ref, dep), rows)
            for line in bad[:5]:
                say(f"# MISMATCH block {j} seed {job['seed']}: {line}")
            mismatches += n_bad
            failed += bool(n_bad)
            gaps.append(g)
    widest = max((float(g.max()) for g in gaps if g.size), default=0.0)
    say(f"# reference: {len(picks)} of {len(pool)} replicas in "
        f"{time.perf_counter() - t_ref} s; widest finish-time gap {widest} s")
    correct, numbers = compare.judge(mismatches, gaps, traffic["limits"])
    if changed:
        numbers["inputs_changed"] = {"value": len(changed), "limit": 0}
        correct = False
        say(f"# the generators' canary output changed: {changed}")

    line = {"correct": bool(correct), "attempted": attempted,
            "failed": int(failed), "metrics": {}, "device": dict(dev)}
    if stats is not None:
        line["device"]["memory_peak_bytes"] = stats
    if args.trace:
        from trace_reduce import load_xplane, reduce_trace
        path = next(pathlib.Path(trace_dir.name).rglob("*.xplane.pb"))
        reduced = reduce_trace(load_xplane(str(path)), "bench.block")
        trace_dir.cleanup()
        line["device"]["busy_s"] = reduced["busy_s"]
        line["device"]["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
        phases = {}
        for _, results in ran:
            for name, ph in results[0].profile["phases"].items():
                phases[name] = phases.get(name, 0.0) + ph["total_s"]
        ctx = {"phases": phases, "events": events,
               "traced_events": sum(r.n_events for r in ran[0][1]),
               "busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
               "S": len(scenario["instances"]), "device_kind": dev["kind"],
               "root": root}
        for m in bench["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            value = metric_reader(root, m["name"])(ctx)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
    else:
        line["metrics"]["sim_events_per_s"] = {"value": events / window_s,
                                               "unit": "events/s"}
        line["metrics"]["setup_s"] = {"value": setup_s, "unit": "s"}
    line["checks"] = numbers
    for name, v in numbers.items():
        say(f"check {name}: {v['value']} (limit {v['limit']})")
    print(json.dumps(line), flush=True)
    return 0


def dev_memory(jax):
    """Peak bytes in use on the fullest device, where the backend says."""
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(run())
