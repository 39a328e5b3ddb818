"""Set-up from inside the program, all on the CPU: the set-up spans' records
and `<name>_sec` counters, every compile request counted by phase, the
programs a cache miss compiled, the discovery pass by op, and the timeline's
bound.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.profiler import compile_events as ce
from paddle_tpu.profiler import metrics, setup_timeline

REG = metrics.get_registry()
# what a steady call may move: the running counts its span carries (PR 25)
STEADY = {"dispatch.ops_total", "to_static.launches_total",
          "to_static.undonated_launches_total",
          "to_static.grad_path_launches_total",
          "to_static.diverted_calls_total"}


def counters():
    return REG.snapshot()["counters"]


def moved(before):
    return {k: v - before.get(k, 0.0) for k, v in counters().items()
            if v != before.get(k, 0.0)}


def fresh_jit():
    """A jitted function no cache has seen: a constant of its own."""
    c = float(int.from_bytes(os.urandom(4), "little"))
    return (lambda: jax.jit(lambda v: jnp.tanh(v) * c)), jnp.ones((3, 5))


def mlp_step():
    paddle.seed(0)
    model = paddle.nn.Sequential(paddle.nn.Linear(12, 16), paddle.nn.ReLU(),
                                 paddle.nn.Linear(16, 4))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())

    @paddle.jit.to_static
    def train_step(x, y):
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = paddle.to_tensor(np.ones((6, 12), "float32"))
    y = paddle.to_tensor(np.arange(6).reshape(6, 1) % 4)
    return train_step, x, y


@pytest.fixture(scope="module")
def warm():
    """(step, x, y, the records its first three calls left, what they moved
    in the registry)."""
    before, at = counters(), len(setup_timeline())
    step, x, y = mlp_step()
    for _ in range(3):
        step(x, y)
    return step, x, y, setup_timeline()[at:], moved(before)


def label(record):
    program = record["attrs"].get("program")
    return record["name"] + (f"{{{program}}}" if program else "")


# ---------------------------------------------------------------------------
# records and their counters

def test_first_three_calls_leave_their_phases_in_order(warm):
    *_, records, _ = warm
    names = [label(r) for r in records]
    assert names[0] == "to_static.discover"
    assert names[-1] == "to_static.compile{donating}"   # no plain twin before it
    assert set(names[1:-1]) == {"to_static.probe"}
    for r in records:
        assert r["parent"] is None and r["start"] < r["end"]
        assert r["attrs"]["fn"].endswith("train_step")
    assert [a["end"] <= b["start"] for a, b in zip(records, records[1:])] \
        == [True] * (len(records) - 1)
    assert records[0]["attrs"]["ops"] > 0


@pytest.mark.parametrize("name", ["to_static.discover", "to_static.probe",
                                  "to_static.compile"])
def test_seconds_counter_is_the_sum_of_its_records(warm, name):
    *_, records, got = warm
    spent = sum(r["end"] - r["start"] for r in records if r["name"] == name)
    assert got[name + "_sec"] == pytest.approx(spent, abs=1e-9) and spent > 0


def test_the_steps_programs_are_requests_of_the_compile_phase(warm):
    *_, records, got = warm
    assert got['compile.requests_total{phase="compile"}'] == 1
    assert got["to_static.compiles_total"] == 1
    assert records[-1]["requests"] == 1
    assert got['compile.backend_sec{phase="compile"}'] \
        == pytest.approx(got["to_static.backend_compile_sec"])
    assert got['compile.requests_total{phase="discover"}'] \
        == records[0]["requests"] > 0


def test_the_import_is_a_record_and_a_counter():
    (record,) = [r for r in setup_timeline() if r["name"] == "runtime.import"]
    assert record["end"] - record["start"] > 0 and record["parent"] is None
    # the registry may have been emptied by an earlier test of this process
    assert REG.counter_value("runtime.import_sec") in (
        0.0, pytest.approx(record["end"] - record["start"]))


def test_a_span_inside_another_names_its_parent():
    at = len(setup_timeline())
    with ce.setup_span("to_static.discover", fn="outer"):
        with ce.setup_span("to_static.probe", op="k") as inner:
            inner["attrs"]["candidates"] = 3
    outer, inner = setup_timeline()[at:]
    assert inner["parent"] == at and outer["parent"] is None
    assert inner["attrs"] == {"op": "k", "candidates": 3}
    assert outer["start"] <= inner["start"] <= inner["end"] <= outer["end"]


# ---------------------------------------------------------------------------
# every compile request, by phase

def test_a_jit_is_counted_under_the_open_span_or_as_the_callers():
    build, v = fresh_jit()
    before = counters()
    with ce.setup_span("to_static.discover", fn="t") as record:
        build()(v).block_until_ready()
    assert moved(before)['compile.requests_total{phase="discover"}'] == 1
    assert record["requests"] == 1
    before = counters()
    build()(v).block_until_ready()       # this file is no part of the package
    got = moved(before)
    assert got['compile.requests_total{phase="user"}'] == 1
    assert got['compile.backend_sec{phase="user"}'] > 0
    assert not any(k.startswith("to_static.") for k in got)


def test_the_packages_own_eager_compiles_are_the_eager_phase():
    before = counters()
    t = paddle.to_tensor(np.ones((3, 7, 11), "float32"))
    (t * 1.75 + t).numpy()               # shapes no other test uses
    got = moved(before)
    assert got['compile.requests_total{phase="eager"}'] >= 1
    assert 'compile.requests_total{phase="user"}' not in got


def test_a_first_miss_and_a_second_hit_in_the_persistent_cache():
    if not jax.config.jax_compilation_cache_dir:
        pytest.skip("no persistent cache in this process")
    build, v = fresh_jit()
    with ce.setup_span("to_static.probe", fn="t") as first:
        build()(v).block_until_ready()
    before = counters()
    with ce.setup_span("to_static.probe", fn="t") as second:
        build()(v).block_until_ready()   # another function object, same program
    assert (first["requests"], first["misses"]) == (1, 1)
    assert [name for name, _ in first["missed_programs"]] == ["jit(<lambda>)"]
    assert (second["requests"], second["misses"]) == (1, 0)
    got = moved(before)
    assert got['compile.cache_hits_total{phase="probe"}'] == 1
    assert got['compile.cache_load_sec{phase="probe"}'] > 0
    assert 'compile.cache_misses_total{phase="probe"}' not in got


MISS = [("event", ce._MISS), ("duration", ce._BACKEND, 1.5, "jit(pure_fn)")]
HIT = [("event", ce._HIT),
       ("duration", "/jax/compilation_cache/compile_time_saved_sec", 9.0, None),
       ("duration", ce._LOAD, 0.25, None),
       ("duration", ce._BACKEND, 0.5, "jit(pure_fn)")]


def emit(events):
    """jax's events of one compile request, in the order jax emits them."""
    for kind, event, *rest in events:
        if kind == "event":
            ce._on_event(event)
        else:
            ce._on_duration(event, rest[0], fun_name=rest[1])


@pytest.mark.parametrize("events,hits,misses,load_s,named", [
    (MISS, 0, 1, 0.0, [["jit(pure_fn)", 1.5]]),
    (HIT, 1, 0, 0.25, []),
    (HIT + MISS + HIT, 2, 1, 0.5, [["jit(pure_fn)", 1.5]]),
], ids=["miss", "hit", "hit-miss-hit"])
def test_listeners_count_hits_misses_and_name_the_missed_program(
        events, hits, misses, load_s, named):
    before = counters()
    with ce.compile_span("to_static.compile", "pure_fn", program="plain") as record:
        emit(events)
    got = moved(before)
    requests = hits + misses
    assert got['compile.requests_total{phase="compile"}'] == requests
    assert got.get('compile.cache_hits_total{phase="compile"}', 0) == hits
    assert got.get('compile.cache_misses_total{phase="compile"}', 0) == misses
    assert got.get('compile.cache_load_sec{phase="compile"}', 0.0) == load_s
    assert (record["requests"], record["misses"]) == (requests, misses)
    assert record["missed_programs"] == named
    # the watched function's own events still count for to_static
    assert got["to_static.compiles_total"] == requests
    assert got["to_static.backend_compile_sec"] \
        == got['compile.backend_sec{phase="compile"}'] == 1.5 * misses + 0.5 * hits


def test_a_miss_outside_any_span_is_named_on_the_process_wide_record():
    def names():
        (user,) = [r for r in setup_timeline() if r["name"] == "user"]
        return user["missed_programs"], user["misses"]

    named, misses = names()
    emit([("event", ce._MISS), ("duration", ce._BACKEND, 12345.678, "jit(block_grad)")])
    after, misses_after = names()
    assert misses_after == misses + 1
    assert ["jit(block_grad)", 12345.678] in after and ["jit(block_grad)", 12345.678] not in named
    assert len(after) <= ce.NAMES_BOUND


def test_a_record_keeps_the_longest_missed_programs():
    with ce.setup_span("to_static.probe", fn="t") as record:
        for i in range(ce.NAMES_BOUND + 4):
            emit([("event", ce._MISS), ("duration", ce._BACKEND, float(i), f"p{i}")])
    assert record["misses"] == ce.NAMES_BOUND + 4
    assert sorted(s for _, s in record["missed_programs"]) \
        == [float(i) for i in range(4, ce.NAMES_BOUND + 4)]


# ---------------------------------------------------------------------------
# the discovery pass by op

def test_discovery_keeps_its_slowest_ops_forward_and_backward(warm):
    *_, records, _ = warm
    ops = {row[0]: row for row in records[0]["slowest_ops"]}
    assert {"linear", "cross_entropy", "grad(linear)", "grad(cross_entropy)"} <= set(ops)
    assert ops["linear"][1] == ops["grad(linear)"][1] == 2      # calls
    assert len(ops) <= ce.NAMES_BOUND
    seconds = [row[2] for row in records[0]["slowest_ops"]]
    assert seconds == sorted(seconds, reverse=True) and seconds[-1] > 0
    # self time: the ops' seconds stay under the span's
    assert sum(seconds) <= records[0]["end"] - records[0]["start"]
    # each compile request of the span was some op's, or the pass's own
    assert sum(row[3] for row in ops.values()) <= records[0]["requests"]
    for r in records[1:]:
        assert "slowest_ops" not in r


def test_an_op_inside_an_op_is_not_counted_twice():
    timer = ce._OpTimes(ce._record("t", 0.0))

    def outer():
        timer("inner", lambda: sum(range(20000)))
        return 1

    timer("outer", outer)
    whole = timer.by_op["outer"][1] + timer.by_op["inner"][1]
    assert timer.by_op["inner"][1] > 0 and timer.by_op["outer"][1] >= 0
    assert timer.inside == [[pytest.approx(whole), 0, 0]]


def test_no_op_is_timed_outside_discovery(warm):
    step, x, y, *_ = warm
    assert ce.OP_TIMER[0] is None
    step(x, y)
    (x * 2.0).numpy()
    assert ce.OP_TIMER[0] is None


# ---------------------------------------------------------------------------
# steady calls; the bound

def test_a_hundred_steady_calls_leave_no_record_and_move_no_new_counter(warm):
    step, x, y, *_ = warm
    step(x, y)
    before, at = counters(), len(setup_timeline())
    for _ in range(100):
        loss = step(x, y)
    jax.block_until_ready(loss._val)
    assert len(setup_timeline()) == at
    got = moved(before)
    assert set(got) <= STEADY and got["to_static.launches_total"] == 100


def test_the_timeline_is_bounded_and_says_what_it_dropped(monkeypatch):
    at = len(setup_timeline())
    monkeypatch.setattr(ce, "TIMELINE_BOUND", at + 1)
    before = counters()
    with ce.setup_span("to_static.probe", fn="kept"):
        with ce.setup_span("to_static.probe", fn="dropped-1"):
            pass
    with ce.setup_span("to_static.probe", fn="dropped-2"):
        pass
    assert [r["attrs"]["fn"] for r in setup_timeline()[at:]] == ["kept"]
    got = moved(before)
    assert got["runtime.setup_records_dropped_total"] == 2
    assert got["to_static.probe_sec"] > 0   # the seconds are counted all the same
