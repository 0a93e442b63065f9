"""Helpers of the benchmark's CPU tests: a copy of the benchmark with its
cells cut to a size the CPU runs in seconds, and a helper that runs the
harness in-process with the look for a chip skipped."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_REQUESTS = {"static-b32": 15, "static-b256": 8}


def tiny_copy(dest: pathlib.Path) -> pathlib.Path:
    """``BENCHMARK.json`` and ``bench/`` under ``dest``, every cell at two
    seeds a block and a few dozen requests, every replica compared."""
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, n in TINY_REQUESTS.items():
        path = dest / "bench" / "cells" / f"{name}.json"
        t = json.loads(path.read_text())
        t.update(n_ai_requests=n, batch=min(t["batch"], 2), warm_requests=5,
                 check_replicas=50)
        path.write_text(json.dumps(t))
    return dest


def canary_digests(cfg: dict) -> dict:
    import blocks
    cache: dict = {}
    blocks.make_jobs(cfg, {"method": "haf-static", "n_ai_requests": 10,
                           "batch": 1}, [0], "numpy", cache)
    sc = cache["scenario"]
    job = {"scenario": sc, "seed": cfg["canary"]["seed"],
           "n_ai_requests": cfg["canary"]["n_ai_requests"], "rho": None}
    return {"deployment_digest": blocks.digest(blocks.deployment_data(sc)),
            "workload_digest": blocks.digest(
                blocks.request_rows(blocks.job_stream(job)))}


def run_cell(root: pathlib.Path, cell: str, capsys, seed: int = 2**31 + 7,
             seconds: float = 0.3, trace: int = 0) -> dict:
    """Run the harness on the CPU; returns its last line as a dict."""
    import run
    rc = run.run(["--workload", cell, "--seed", str(seed), "--seconds",
                  str(seconds), "--trace", str(trace)],
                 require_chip=False, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-5:]
    return json.loads(out[-1])
