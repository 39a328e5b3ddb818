"""The reduction from a profiler trace to busy, idle, per-op and exposed
collective time: on hand-made intervals, and on a small trace recorded on
the chip (data/*.textproto, cut from a run of each cell)."""
import glob
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_total_and_intersect():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    assert tr.total(merged) == 6
    assert tr.intersect(merged, [[2, 6], [7, 20]]) == [[2, 3], [5, 6], [7, 8]]


def test_busy_idle_and_per_op_sums():
    # two executions of one program, 0-100 and 100-200; ops cover 150 of 200
    modules = [("jit_step", 0, 100), ("jit_step", 100, 100), ("jit_tiny", 300, 1)]
    ops = [("fusion.1", 0, 50), ("fusion.2", 40, 30), ("fusion.1", 100, 80),
           ("fusion.9", 300, 1)]                      # outside the window
    d = tr.reduce_device(ops, modules)
    assert (d["lo"], d["hi"], d["steps"]) == (0, 200, 2)
    assert d["busy_ns"] == 70 + 80                    # 0-70 (overlap once), 100-180
    assert d["op_ns"] == {"fusion.1": 130, "fusion.2": 30}
    assert d["gaps"] == [(70, 100), (180, 200)]
    assert d["exposed_collective_ns"] == 0


def test_exposed_collective_time_is_what_no_other_op_covers():
    modules = [("jit_step", 0, 100)]
    ops = [("all-reduce.3", 10, 40),                  # 10-50
           ("fusion.1", 0, 30),                       # hides 10-30
           ("all-gather-start.1", 60, 10),            # 60-70, nothing beside it
           ("fusion.2", 80, 20)]
    d = tr.reduce_device(ops, modules)
    assert d["exposed_collective_ns"] == 20 + 10
    assert d["busy_ns"] == 50 + 10 + 20


def test_reduce_takes_the_worst_device_and_labels_gaps_by_host_span():
    trace = {"devices": {
        "/device:TPU:0": {"modules": [("jit_step", 0, 100)],
                          "ops": [("fusion.1", 0, 90)]},
        "/device:TPU:1": {"modules": [("jit_step", 0, 100)],
                          "ops": [("fusion.1", 0, 50)]}},
        "host": [("bench.wait", 40, 50), ("bench.dispatch", 90, 20)]}
    r = tr.reduce(trace)
    assert r["devices"] == 2 and r["steps"] == 1
    assert r["busy_s"] == pytest.approx(70e-9) and r["window_s"] == pytest.approx(100e-9)
    assert r["idle_pct_worst"] == pytest.approx(50.0)
    assert r["idle_gaps"] == [["bench.wait", pytest.approx(50e-9)]]
    assert r["device_ops"] == [["fusion.1", pytest.approx(70e-9)]]


def test_a_trace_without_a_device_plane_reduces_to_nothing():
    assert tr.reduce({"devices": {}, "host": [("bench.wait", 0, 5)]}) is None


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA, "*.textproto"))),
                         ids=os.path.basename)
def test_recorded_trace(path):
    from jax.profiler import ProfileData
    with open(path) as f:
        loaded = tr.load(ProfileData.from_text_proto(f.read()))
    (device,) = loaded["devices"].values()
    r = tr.reduce(loaded)
    assert r["steps"] >= 3 and r["devices"] == 1
    ops = [e for e in device["ops"]]
    # busy is a union: never more than the window, never more than the sum
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] <= sum(d for _, _, d in ops) / 1e9
    assert r["idle_pct_worst"] == pytest.approx(100 * (1 - r["busy_s"] / r["window_s"]))
    assert sum(s for _, s in r["device_ops"]) >= r["busy_s"] * 0.999
    assert all(name.startswith("bench.") or name == "no bench span"
               for name, _ in r["idle_gaps"])


def test_recorded_gpt_trace_reads_what_it_read_when_it_was_cut():
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "gpt3-1p3b_1chip_3steps.textproto")) as f:
        r = tr.reduce(tr.load(ProfileData.from_text_proto(f.read())))
    # the union of the 5214 operations' intervals, summed by hand (numpy) when
    # the trace was cut: 139,335,340 ns busy in a window of 139,802,790 ns
    assert r["steps"] == 3
    assert r["busy_s"] == pytest.approx(0.13933534, rel=1e-9)
    assert r["window_s"] == pytest.approx(0.13980279, rel=1e-9)
    assert r["idle_pct_worst"] == pytest.approx(0.33436, abs=1e-4)
    # the AdamW update of the 50304 x 2048 embedding leads, at 4.38 ms a step
    name, seconds = r["device_ops"][0]
    assert name.startswith("fusion.1254 (bf16[50304,2048]")
    assert seconds / 3 == pytest.approx(0.004384, rel=1e-3)
    assert r["idle_gaps"][0] == ["bench.wait", pytest.approx(9.2003e-05)]
