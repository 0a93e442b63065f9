"""The control of the `correct` check: the plain reference with its event
core in float32, the precision below the float64 the configurations
state, put in the program's place and compared exactly as a run compares
the program.

    python3 bench/control.py --workload <cell> --seed <n> [--seed <m> ...]

For each seed it realizes the replicas of the window's first blocks (the
seeds a run with that ``--seed`` simulates), compares as many as a run
does through the run's own comparison (``compare.compare_replica`` and
``compare.judge``), and prints one JSON line per seed.  The smallest
``finish_gap_p90_s`` over three seeds or more is the upper reading a
cell's limit is set below.  It runs on the host alone.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def control(root: pathlib.Path, workload: str, seed: int):
    """``(correct, numbers)`` of the control for one seed: the replicas of
    the first blocks of a run with ``--seed seed``, as many as a run
    compares, the float32 reference's outcome in the program's place,
    judged by the run's own comparison and limits."""
    import numpy as np
    import blocks
    import compare
    import reference
    from run import load_cell

    _, _, cfg, traffic = load_cell(root, workload)
    B, k = traffic["batch"], traffic["check_replicas"]
    method = blocks.reference_method(traffic["method"])
    cache: dict = {}
    jobs, block = [], 0
    while len(jobs) < k:
        jobs += blocks.make_jobs(cfg, traffic,
                                 blocks.block_seeds(seed, block, B),
                                 traffic["engine"], cache)
        block += 1
    dep = blocks.deployment_data(cache["scenario"])
    mismatches, gaps = 0, []
    for job in jobs[:k]:
        stream = blocks.job_stream(job)
        rows = blocks.request_rows(stream)
        ref, ctl = (compare.reference_outcome(
            reference.simulate(dep, rows, stream.horizon, method,
                               job["epoch_interval"], dtype), dep)
            for dtype in (np.float64, np.float32))
        _, n_bad, g = compare.compare_replica(ctl, ref, rows)
        mismatches += n_bad
        gaps.append(g)
    return compare.judge(mismatches, gaps, traffic["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    for seed in args.seed:
        ok, numbers = control(HERE.parent, args.workload, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": ok, "checks": numbers}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
