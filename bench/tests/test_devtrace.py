import json
import os

import pytest

from bench import devtrace

MS = 1_000_000  # ns


def _trace():
    # a 10 ms traced window; two programs, one with overlapping ops
    host = [("bench.traced_window", 0, 10 * MS),
            ("bench.session_step", 0, 6 * MS),
            ("bench.decode_step", 1 * MS, 5 * MS),
            ("bench.idle", 7 * MS, 10 * MS)]
    modules = [("jit_decode_block(12)", 1 * MS, 3 * MS), ("jit_gather_slots(3)", 4 * MS, 5 * MS)]
    ops = [("fusion.1", 1 * MS, 2 * MS), ("fusion.2", int(1.5 * MS), 3 * MS),
           ("copy.3", 4 * MS, 5 * MS), ("late", 9 * MS, 12 * MS)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "host": host}


def test_busy_is_the_union_of_op_intervals_clipped_to_the_window():
    red = devtrace.reduce(_trace())
    assert red["window_s"] == pytest.approx(0.010)
    # [1, 3] + [4, 5] + [9, 10] (clipped) = 4 ms
    assert red["busy_s"] == pytest.approx(0.004)
    assert red["idle_share"] == pytest.approx(0.6)


def test_idle_gaps_are_named_by_the_innermost_harness_span():
    red = devtrace.reduce(_trace())
    # idle 0..1, 3..4 and 5..9 ms; the last is cut at 6 and 7 ms
    assert red["idle_gaps"][0] == ["bench.idle", pytest.approx(0.002)]          # 7..9
    rest = sorted((name, round(s * 1e3, 6)) for name, s in red["idle_gaps"][1:])
    assert rest == [("bench.decode_step", 1.0),          # 3..4
                    ("bench.session_step", 1.0),         # 0..1
                    ("bench.session_step", 1.0),         # 5..6
                    ("outside harness spans", 1.0)]      # 6..7


def test_ops_are_named_by_their_program_and_programs_are_timed():
    red = devtrace.reduce(_trace())
    ops = dict(red["device_ops"])
    assert ops["jit_decode_block/fusion.2"] == pytest.approx(0.0015)
    assert ops["jit_gather_slots/copy.3"] == pytest.approx(0.001)
    assert red["program_s"]["jit_decode_block"] == pytest.approx(0.002)
    assert red["program_calls"]["jit_decode_block"] == 1


def test_no_device_events_reduces_to_nothing():
    tr = _trace()
    tr["devices"]["/device:TPU:0"]["ops"] = []
    assert devtrace.reduce(tr) is None


RECORDED = os.path.join(os.path.dirname(__file__), "data", "tpu_decode_trace.json")


def test_recorded_tpu_trace():
    """300 ms of a trace recorded on one TPU v5e in the decode cell (with
    float32 weights): the reduction's busy time never exceeds the window,
    the decode layer's program is found, and gaps are attributed."""
    with open(RECORDED) as f:
        ex = json.load(f)
    red = devtrace.reduce(ex)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["program_calls"].get("jit_decode_block", 0) > 0
    assert all(name.startswith("bench.") or name == "outside harness spans"
               for name, _ in red["idle_gaps"])
