"""Benchmark aggregator — one section per paper table/figure + the roofline
report and the scenario-fleet sweep.  Prints CSV lines
(``table,method,metric=...``).

  PYTHONPATH=src python -m benchmarks.run             # reduced-scale (CPU)
  REPRO_FULL=1 PYTHONPATH=src python -m benchmarks.run  # paper-scale counts
  PYTHONPATH=src python -m benchmarks.run --only fleet --smoke   # CI mode
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=(None, "table2", "table3", "fig2", "roofline",
                             "alloc", "fleet", "engine", "critic", "spec",
                             "chaos", "lint"))
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI mode (tiny request counts, 1 seed; the "
                         "engine bench still records BENCH_pr7.json and "
                         "the critic harvest+holdout path still runs)")
    ap.add_argument("--trace", action="store_true",
                    help="record repro.obs event/decision traces for the "
                         "spec smoke sweep (JSONL + Chrome trace next to "
                         "its report)")
    ap.add_argument("--profile", action="store_true",
                    help="per-phase wall-clock profiling on the spec smoke "
                         "sweep (the engine bench always profiles its own "
                         "section)")
    args = ap.parse_args()
    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    t0 = time.time()

    from benchmarks import common
    print(f"# scenario: 6 nodes, requests={common.REQUESTS} "
          f"(REPRO_FULL={'1' if common.FULL else '0'}, "
          f"workers={common.WORKERS})", flush=True)

    if args.only in (None, "lint"):
        # fastest tier first: the repro.analysis invariant linter must
        # report a clean tree (determinism / obs zero-overhead /
        # identity-hash / dtype contracts) — see docs/analysis.md
        from repro.analysis import analyze, rule_names
        findings, n_files = analyze()
        for f in findings:
            print(f.format())
        if findings:
            raise RuntimeError(
                f"repro.analysis: {len(findings)} invariant finding(s) "
                "in src/repro (see above)")
        print(f"# lint: 0 findings over {n_files} files "
              f"({len(rule_names())} rules)", flush=True)
    if args.only in (None, "engine"):
        from benchmarks import engine_bench
        record = engine_bench.main(smoke=args.smoke)
        if args.smoke:
            # CI guard: the profile section must carry a real per-phase
            # table for every backend that ran (host transfer split out)
            engines = record.get("profile", {}).get("engines", {})
            ran = {e: p for e, p in engines.items() if "error" not in p}
            bad = [e for e, p in ran.items() if not p.get("phases")]
            if not ran or bad:
                raise RuntimeError(
                    "BENCH_pr7.json profile section lacks per-phase "
                    f"tables (ran={sorted(ran)}, empty={bad})")
            dev = [e for e in ran if e in ("jax", "pallas")]
            missing = [e for e in dev
                       if not {"core.h2d", "core.d2h"}
                       <= set(ran[e]["phases"])]
            if missing:
                raise RuntimeError(
                    "device engines missing h2d/d2h round-trip phase "
                    f"accounting: {missing}")
            # CI guard: the streamed arrival path must hold its fixed
            # O(S + window) peak-memory budget at every grid point
            # (includes the 2e5-request streamed run)
            mem = record.get("memory", {})
            if not mem.get("streamed_peak_flat"):
                peaks = [p.get("streamed_peak_mb")
                         for p in mem.get("points", [])]
                raise RuntimeError(
                    "streamed peak memory exceeded the "
                    f"{mem.get('smoke_budget_mb')}MB budget: {peaks}MB "
                    "(O(S + window) contract broken)")
    if args.only in (None, "alloc"):
        from benchmarks import alloc_microbench
        alloc_microbench.main()
    if args.only in (None, "critic"):
        from benchmarks import critic_data
        critic_data.main(smoke=args.smoke)
    if args.only in (None, "spec"):
        # the checked-in experiment specs must stay loadable + expandable;
        # in --smoke mode one also runs end-to-end through the CLI
        from benchmarks import common
        from repro.eval import cli as eval_cli
        for name in ("paper_table3.toml", "load_sweep.toml",
                     "trace_sweep.toml"):
            rc = eval_cli.main(["--spec", str(common.EXPERIMENTS / name),
                                "--validate"])
            if rc:
                raise RuntimeError(f"spec validate failed: {name} (rc={rc})")
        if args.smoke:
            obs_flags = (["--trace"] if args.trace else []) \
                + (["--profile"] if args.profile else [])
            rc = eval_cli.main(
                ["--spec", str(common.EXPERIMENTS / "paper_table3.toml"),
                 "--smoke", "--no-resume", "--workers", "1",
                 "--out", str(common.ARTIFACTS / "spec_smoke.json")]
                + obs_flags)
            if rc:
                raise RuntimeError(f"spec smoke run failed (rc={rc})")
    if args.only in (None, "table3"):
        from benchmarks import table3_baselines
        table3_baselines.main()
    if args.only in (None, "table2"):
        from benchmarks import table2_critic_ablation
        table2_critic_ablation.main()
    if args.only in (None, "fig2"):
        from benchmarks import fig2_load_sweep
        fig2_load_sweep.main()
    if args.only in (None, "fleet"):
        from benchmarks import fleet_sweep
        fleet_sweep.main(smoke=args.smoke)
    if args.only in (None, "chaos"):
        # fault-injection tier: spot churn + a 35%-flaky LLM endpoint;
        # asserts zero crashed jobs, nonzero degraded decisions, and
        # exact trace reconciliation
        from benchmarks import chaos_smoke
        chaos_smoke.main(smoke=args.smoke)
    if args.only in (None, "roofline"):
        from benchmarks import roofline_report
        roofline_report.main()

    print(f"# total wall time: {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
