"""The harness is driven by data: a cell, a configuration and a per-layer
metric added as new files, with new ``BENCHMARK.json`` entries, are found
by name with no edit to a file the benchmark already has."""
import json

import tinybench


def test_new_cell_config_and_metric_are_found_by_name(tmp_path, capsys):
    root = tinybench.tiny_copy(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = {"name": "paper-rho", "source": "test", "scenario":
           "paper(rho=0.8)", "number_format": "float64",
           "canary": {"seed": 1, "n_ai_requests": 20}}
    cfg["canary"].update(tinybench.canary_digests(cfg))
    (root / "bench" / "configs" / "paper-rho.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "cells" / "static-b3.json").write_text(json.dumps({
        "method": "haf-static", "engine": "jax", "batch": 3,
        "n_ai_requests": 30, "warm_requests": 5, "check_replicas": 4,
        "limits": {"outcome_mismatches": 0, "finish_gap_p90_s": 1e-9}}))
    (root / "bench" / "metrics" / "events_per_block.py").write_text(
        "def read(ctx):\n    return ctx['traced_events']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "paper-rho", "source": "test",
                             "file": "bench/configs/paper-rho.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "paper-rho-static-b3",
                               "config": "paper-rho",
                               "traffic": "static-b3", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "events_per_block",
                               "unit": "events", "better": "higher",
                               "source": "program_counter", "layer": "test",
                               "moves": "sim_events_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    line = tinybench.run_cell(root, "paper-rho-static-b3", capsys)
    assert line["correct"] is True
    assert line["attempted"] % 3 == 0 and line["attempted"] >= 3
    import run
    assert run.metric_reader(root, "events_per_block")(
        {"traced_events": 7}) == 7
    assert before == {p: p.read_bytes() for p in before}
