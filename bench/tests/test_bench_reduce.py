"""The reduction from a profiler trace to the device metrics, on a small
recorded trace, and the byte count and peak table of the roofline share."""
import pathlib

import pytest

import tinybench
import trace_reduce

# a traced block of 1000 ns on the host; device ops at [100, 300),
# [250, 400) overlapping it, [600, 700) and one op that straddles the
# block's end; the host was inside a transfer from 420 to 580
TRACE = {
    "host": [["bench.block", 0, 1000],
             ["TransferToDevice", 420, 160],
             ["PjitFunction(event_step_jax)", 410, 300]],
    "devices": {"/device:TPU:0": [["fusion.1", 100, 200],
                                  ["copy.2", 250, 150],
                                  ["fusion.1", 600, 100],
                                  ["fusion.3", 950, 200]]},
}


def test_busy_is_the_union_of_device_op_intervals():
    got = trace_reduce.reduce_trace(TRACE, "bench.block")
    # union: [100, 400) + [600, 700) + [950, 1000) = 300 + 100 + 50
    assert got["busy_s"] == pytest.approx(450e-9)
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(300e-9)]
    assert dict(got["device_ops"])["fusion.3"] == pytest.approx(50e-9)


def test_idle_gaps_are_named_by_the_innermost_host_event():
    gaps = trace_reduce.reduce_trace(TRACE, "bench.block")["idle_gaps"]
    # gaps: [400, 600) 200 ns, [700, 950) 250, [0, 100) 100
    assert [g[1] for g in gaps] == pytest.approx([250e-9, 200e-9, 100e-9])
    assert gaps[1][0] == "TransferToDevice"
    assert gaps[0][0] == "bench.block"


def test_busy_averages_over_devices():
    two = dict(TRACE, devices={"/device:TPU:0": [["a", 0, 1000]],
                               "/device:TPU:1": [["a", 0, 500]]})
    assert trace_reduce.reduce_trace(two, "bench.block")["busy_s"] \
        == pytest.approx(750e-9)


def test_a_device_clock_of_its_own_is_aligned_to_the_block():
    offset = {plane: [[n, s + 10**12, d] for n, s, d in ops]
              for plane, ops in TRACE["devices"].items()}
    got = trace_reduce.reduce_trace(dict(TRACE, devices=offset),
                                    "bench.block")
    # aligned so that the first op starts with the block: [0, 200),
    # [150, 300), [500, 600), [850, 1050) clipped: 300 + 100 + 150
    assert got["busy_s"] == pytest.approx(550e-9)
    assert got["window_s"] == pytest.approx(1000e-9)


def test_a_trace_without_device_ops_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace(dict(TRACE, devices={}), "bench.block")


def load_metric(name):
    import run
    return run.metric_reader(tinybench.REPO, name)


def test_roofline_share_from_bytes_and_peak():
    read = load_metric("event_core_roofline_share")
    ctx = {"busy_s": 1e-3, "traced_events": 1000, "S": 1440,
           "device_kind": "TPU v5 lite", "root": tinybench.REPO}
    # 1000 events x 1440 lanes x 50 bytes at 819 GB/s, over 1 ms busy
    assert read(ctx) == pytest.approx(100 * 1000 * 1440 * 50 / 819e9 / 1e-3)
    assert read(dict(ctx, busy_s=None)) is None


def test_an_unknown_device_kind_raises():
    read = load_metric("event_core_roofline_share")
    with pytest.raises(KeyError, match="no published peaks"):
        read({"busy_s": 1e-3, "traced_events": 1, "S": 18,
              "device_kind": "cpu", "root": tinybench.REPO})


@pytest.mark.parametrize("name,phases,want", [
    ("engine_events_us_per_event", {"engine.events": 2.0}, 2e6 / 400),
    ("engine_step_us_per_event", {"engine.step": 1.0}, 1e6 / 400),
    ("allocator_solve_us_per_event", {"allocator.solve": 0.4}, 1e3),
    ("allocator_solve_us_per_event", {"epoch.decide": 0.04}, None),
    ("core_transfer_us_per_event", {"core.h2d": 0.1, "core.d2h": 0.3}, 1e3),
    ("engine_events_us_per_event", {}, None),
    ("core_transfer_us_per_event", {"core.kernel": 1.0}, None),
])
def test_phase_readers(name, phases, want):
    got = load_metric(name)({"phases": phases, "events": 400})
    assert got == (None if want is None else pytest.approx(want))


def test_device_busy_and_idle_readers():
    ctx = {"busy_s": 0.25, "window_s": 1.0, "traced_events": 5000}
    assert load_metric("device_busy_us_per_event")(ctx) == pytest.approx(50)
    assert load_metric("device_idle_share")(ctx) == pytest.approx(75.0)
    assert load_metric("device_idle_share")({"busy_s": None}) is None


def test_peak_table_names_its_source():
    import json
    peaks = json.loads((pathlib.Path(tinybench.BENCH) / "peaks.json")
                       .read_text())
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peaks["TPU v5 lite"]["source"]
