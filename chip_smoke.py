"""Smoke run of the batched simulator sweep on one TPU chip.

    python chip_smoke.py                          # TPU host: exits 0
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny # CPU rehearsal: exits 1

Phases, each printing its own ``#`` lines:

1. device — ``jax.devices()``.  Anything but a TPU fails the run; with
   ``--tiny`` the other phases still run, at toy size, but the run ends
   with ``"ok": false`` and exit code 1 all the same.
2. sweep — the main path, ``repro.exp.run_experiment``: the metro
   deployment ``dense-urban(n_nodes=480)`` (480 cells, S = 1440
   instances) under ``haf-static`` and ``haf`` (stand-in agent, no critic)
   for seeds 0..31 at 1000 AI requests each, ``engine="jax"``, ``batch=32``,
   ``workers=1``.  The same spec with ``engine="numpy"`` (pinned
   bit-identical to ``scalar``) is the host reference; it runs at the same
   time in a child process that is held to the CPU.  No job may fail,
   truncate or fall back, and every jax row must equal its reference row
   on every discrete outcome: the summary fields (per-class counts and
   violations, fulfilment, migrations), ``n_events``, ``n_requests`` and
   ``infeasible_events``.

The last line of standard output is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``.  Reports go to
``--out`` (default ``smoke_out/`` in the checkout).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# size of phase 2: (n_nodes, seeds, batch, AI requests per seed)
FULL = (480, 32, 32, 1000)
TINY = (12, 4, 4, 60)

TIMING_KEYS = ("wall_s", "engine_wall_s", "events_per_sec", "engine")
HAF = {"name": "haf", "label": "haf",
       "params": {"agent": "qwen3-32b-sim", "critic_path": None}}


def say(msg: str) -> None:
    print(msg, flush=True)


def sweep_spec(engine: str, size, out: pathlib.Path):
    from repro.exp import ExperimentSpec
    n_nodes, n_seeds, batch, n_req = size
    return ExperimentSpec(
        name=f"chip-smoke-{engine}",
        methods=("haf-static", HAF),
        scenarios=(f"dense-urban(n_nodes={n_nodes})",),
        seeds=tuple(range(n_seeds)), n_ai_requests=n_req,
        engine=engine, batch=batch, workers=1,
        out=str(out / f"sweep_{engine}.json"))


def run_sweep_checked(spec):
    """Run ``spec`` fresh; raise unless every row ran to its end."""
    from repro.exp import run_experiment
    t0 = time.perf_counter()
    report = run_experiment(spec, resume=False, verbose=False)
    wall = time.perf_counter() - t0
    rows = report["runs"]
    problems = []
    if report["n_failed"]:
        problems.append(f"{report['n_failed']} job(s) failed")
    if report["n_truncated"]:
        problems.append(f"{report['n_truncated']} row(s) truncated")
    fallbacks = [r for r in rows if r.get("batch_fallback")]
    if fallbacks:
        problems.append(f"{len(fallbacks)} batch_fallback row(s)")
    expected = len(spec.methods) * len(spec.seeds)
    if len(rows) != expected:
        problems.append(f"{len(rows)} rows, expected {expected}")
    if problems:
        raise RuntimeError(f"engine={spec.engine}: " + "; ".join(problems))
    return report, wall


def reference_main(args) -> int:
    """Child process: the numpy host reference of phase 2, on the CPU."""
    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    out = pathlib.Path(args.out)
    report, wall = run_sweep_checked(
        sweep_spec("numpy", TINY if args.tiny else FULL, out))
    result = {"wall_s": wall, "rows": report["runs"]}
    pathlib.Path(args.reference).write_text(json.dumps(result))
    return 0


def start_reference(args, out: pathlib.Path):
    """Start the numpy reference in a child process held to the CPU, so
    it never touches the chip this process holds."""
    path = out / "reference.json"
    log = open(out / "reference.log", "w")
    cmd = [sys.executable, str(ROOT / "chip_smoke.py"),
           "--reference", str(path), "--out", str(out)]
    if args.tiny:
        cmd.append("--tiny")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                            env=env, cwd=str(ROOT))
    log.close()
    return proc, path


def finish_reference(proc, path: pathlib.Path, out: pathlib.Path,
                     timeout: float):
    rc = proc.wait(timeout=timeout)
    if rc != 0:
        tail = (out / "reference.log").read_text()[-3000:]
        raise RuntimeError(f"numpy reference process exited {rc}:\n{tail}")
    return json.loads(path.read_text())


# --------------------------------------------------------------------------- #
def phase_device(args):
    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    say(f"# phase 1 device: platform={dev['platform']} "
        f"kind={dev['kind']!r} count={dev['count']} devices={devices}")
    if dev["platform"] != "tpu":
        msg = f"no TPU: JAX runs on {dev['platform']!r}"
        if not args.tiny:
            raise RuntimeError(msg)
        say(f"# phase 1 FAILED ({msg}); --tiny rehearses the other phases "
            "and the run still ends ok=false")
    return dev


def warm_jax_step(B: int, S: int) -> float:
    """Compile the jax engine's [B, S] step (float64) before the sweep;
    returns the seconds it took."""
    import jax
    import numpy as np
    from repro.kernels.event_core import event_step_jax
    f = np.zeros((B, S), np.float64)
    m = np.zeros((B, S), bool)
    v = np.zeros(B, np.float64)
    with jax.enable_x64(True):
        t0 = time.perf_counter()
        out = event_step_jax(f, f, f, f, m, v, v, np.zeros(B, bool))
        jax.block_until_ready(out)
    return time.perf_counter() - t0


def phase_sweep(args, dev, out: pathlib.Path):
    from repro.sim import make_scenario
    size = TINY if args.tiny else FULL
    n_nodes, n_seeds, batch, n_req = size
    S = len(make_scenario("dense-urban", n_nodes=n_nodes)["instances"])
    say(f"# phase 2 sweep: dense-urban(n_nodes={n_nodes}) S={S} "
        f"methods=haf-static,haf(stand-in, no critic) seeds=0..{n_seeds - 1} "
        f"batch={batch} n_ai_requests={n_req}")
    proc, ref_path = start_reference(args, out)
    try:
        compile_s = warm_jax_step(batch, S)
        say(f"# phase 2 engine=jax on {dev['kind']}: compile_s={compile_s}")
        report, wall = run_sweep_checked(sweep_spec("jax", size, out))
        backend = report["provenance"]["backend"]
        if backend.get("device", {}).get("platform") != dev["platform"]:
            raise RuntimeError(f"report names device {backend.get('device')}"
                               f", the run found {dev}")
        from repro.kernels.event_core import event_step_jax
        say(f"# phase 2 engine=jax on {dev['kind']}: wall_s={wall} "
            f"rows={len(report['runs'])} compiled_steps="
            f"{event_step_jax._cache_size()} (1: no compile inside the sweep)")
        ref = finish_reference(proc, ref_path, out, timeout=1200)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    say(f"# phase 2 engine=numpy on host cpu (reference process): "
        f"compile_s=0 (nothing compiled) wall_s={ref['wall_s']}")
    for cell in report["aggregate"]:
        say(f"# phase 2 cell {cell['method']}: overall="
            f"{cell['overall']['mean']} mig_total={cell['mig_total']['mean']}")

    ref_rows = {(r["method"], r["seed"]): r for r in ref["rows"]}
    mismatches = []
    for row in report["runs"]:
        want = ref_rows.get((row["method"], row["seed"]))
        if want is None:
            mismatches.append(f"{row['method']} seed={row['seed']}: no "
                              "reference row")
            continue
        keys = (set(row) | set(want)) - set(TIMING_KEYS)
        diff = sorted(k for k in keys if row.get(k) != want.get(k))
        if diff:
            mismatches.append(
                f"{row['method']} seed={row['seed']}: " + ", ".join(
                    f"{k} jax={row.get(k)!r} numpy={want.get(k)!r}"
                    for k in diff))
    n = len(report["runs"])
    say(f"# phase 2 equivalence: {n - len(mismatches)}/{n} jax rows equal "
        "their numpy rows on every discrete outcome")
    for m in mismatches:
        say(f"# phase 2 MISMATCH {m}")
    if mismatches:
        raise RuntimeError(f"{len(mismatches)} of {n} jax rows differ from "
                           "the numpy reference")
    return {"S": S, "jax_compile_s": compile_s, "jax_wall_s": wall,
            "numpy_wall_s": ref["wall_s"], "rows": n}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes for a CPU rehearsal (never ok)")
    ap.add_argument("--out", default=str(ROOT / "smoke_out"),
                    help="report directory [default: smoke_out/]")
    ap.add_argument("--reference", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.reference:
        return reference_main(args)

    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    dev = None
    summary = {"ok": False}
    try:
        from repro.jax_cache import enable_compile_cache
        summary["compile_cache"] = enable_compile_cache()
        dev = phase_device(args)
        summary["device"] = dev
        summary["sweep"] = phase_sweep(args, dev, out)
        summary["ok"] = dev["platform"] == "tpu" and not args.tiny
    except Exception as err:                # noqa: BLE001 — reported below
        traceback.print_exc()
        summary["error"] = f"{type(err).__name__}: {err}"
        say(f"# FAILED: {summary['error'][:2000]}")
    (out / "smoke.json").write_text(json.dumps(summary, indent=2))
    print(json.dumps({"ok": summary["ok"], "device": dev}), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
