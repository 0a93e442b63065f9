"""The program's spans against the device trace (``bench/spans.py``), on a
small recorded trace: once with the device on the host's clock, once with
a device clock of its own."""
import json

import pytest

import tinybench
import spans  # noqa: E402  (tinybench puts bench/ on the path)

# a traced block of 1000 ns; two ticks, each a step (core.h2d, core.d2h)
# and an event pass; the step program ran inside each tick's round trip,
# and another program at the end; a runtime event on the main thread is
# no program span
TRACE = {
    "main": [["bench.block", 0, 1000, None],
             ["engine.tick", 100, 300, 0],
             ["engine.step", 100, 200, None],
             ["core.h2d", 120, 40, None],
             ["PjitFunction(event_step_jax)", 125, 25, None],
             ["core.d2h", 170, 120, None],
             ["engine.events", 300, 80, None],
             ["engine.tick", 500, 300, 1],
             ["engine.step", 500, 200, None],
             ["core.h2d", 520, 40, None],
             ["core.d2h", 570, 120, None],
             ["engine.events", 700, 80, None]],
    "modules": [["jit_event_step_jax(7)", 180, 40],
                ["jit_event_step_jax(7)", 580, 40],
                ["jit_other(3)", 900, 50]],
    "ops": [["fusion", 180, 20], ["fusion.1", 200, 20],
            ["fusion", 580, 20], ["fusion.1", 600, 20],
            ["copy", 900, 50]],
}
SHIFT = 10**12


def shifted(trace, by):
    return dict(trace,
                modules=[[n, s + by, d] for n, s, d in trace["modules"]],
                ops=[[n, s + by, d] for n, s, d in trace["ops"]])


def test_step_executions_inside_their_ticks_on_the_host_clock():
    got = spans.clock_check(TRACE, "bench.block")
    assert got["ticks"] == 2
    assert got["executions_recorded"] == got["executions_in_window"] == 2
    assert got["inside"] == got["inside_after_offset"] == 2
    assert got["offset_ns"] == 0.0
    assert got["tick_steps"] == [0, 1]


def test_a_device_clock_of_its_own_is_found_and_taken_off():
    got = spans.clock_check(shifted(TRACE, SHIFT), "bench.block")
    assert got["inside"] == 0
    # each execution fits its tick for shifts in [SHIFT - 70, SHIFT + 60]
    assert SHIFT - 70 <= got["offset_ns"] <= SHIFT + 60
    assert got["median_offset_ns"] == SHIFT - 5
    assert got["executions_in_window"] == 2
    assert got["inside_after_offset"] == 2


@pytest.mark.parametrize("by,offset", [(0, 0.0), (SHIFT, SHIFT)])
def test_idle_time_by_innermost_program_span(by, offset):
    got = spans.idle_by_span(shifted(TRACE, by), "bench.block", offset)
    # busy [180, 220) + [580, 620) + [900, 950): 130 of 1000 ns
    assert got["idle_s"] == pytest.approx(870e-9)
    assert dict(got["idle_by_span"]) == pytest.approx({
        "bench.block": 350e-9, "core.d2h": 160e-9, "engine.events": 160e-9,
        "engine.step": 80e-9, "core.h2d": 80e-9, "engine.tick": 40e-9})
    assert got["program_share"] == pytest.approx(520 / 870)


def test_innermost_span_segments_cover_the_window():
    segs = spans.innermost(TRACE["main"], 0, 1000, "bench.block")
    assert segs[0] == (0, 100, "bench.block")
    assert (120, 160, "core.h2d") in segs and (160, 170, "engine.step") in segs
    assert sum(b - a for a, b, _ in segs) == 1000
    assert all(a < b for a, b, _ in segs)


def test_tick_stats_from_the_profiler():
    counts = {"core.ticks": 4, "core.h2d_bytes": 4 * 1000,
              "core.d2h_bytes": 4 * 500}
    got = spans.tick_stats([1e-3, 2e-3, 3e-3, 4e-3], counts, events=6, B=2)
    assert got["tick_p50_us"] == pytest.approx(2500.0)
    assert got["core_transfer_bytes_per_event"] == pytest.approx(1000.0)
    assert got["tick_occupancy_share"] == pytest.approx(75.0)


def test_the_spans_tool_runs_a_cell_on_the_cpu(tmp_path, capsys):
    root = tinybench.tiny_copy(tmp_path)
    rc = spans.main(["--workload", "paper-static-b256", "--seed",
                     str(2**31 + 11)], require_chip=False, root=root)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    traced = out["blocks"]["traced"]
    assert traced["counts"]["core.ticks"] == out["clock"]["ticks"] > 0
    assert out["blocks"]["untraced"]["events"] > 0
    assert 0 < traced["tick_occupancy_share"] <= 100
