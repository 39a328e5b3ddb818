"""The reduction from a traced run to the program's own numbers (device time
by scope, the launch and the Python round it, counters per call, idle gaps by
program span): on hand-made lists, on a hand-made serialized module, and on
three steps cut from a chip trace with the stats kept
(data/gpt3-1p3b_1chip_3steps_spans.textproto), where each new metric has to
read what it read when the trace was cut."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import harness, program_trace as pt, trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
pytestmark = pytest.mark.filterwarnings("ignore:builtin type:DeprecationWarning")


@pytest.mark.parametrize("op_name, scope", [
    ("jit(pure_fn)/jvp(linear)/dot_general", "linear"),
    ("jit(pure_fn)/transpose(jvp(sdpa))/bhqk,bhkd->bhqd/dot_general", "sdpa"),
    ("jit(pure_fn)/optimizer/jit(clip)/max", "optimizer"),
    ("jit(pure_fn)/jit(main)/transpose(jvp())/add_any", None),
    ("jit(pure_fn)/jvp(embedding)/jit(_take)/gather", "embedding"),
    ("jit(pure_fn)/add", None),
    ("mut_vals[36]", None),
])
def test_scope_of_an_op_name(op_name, scope):
    assert pt.scope_of(op_name) == scope


def test_exclusive_time_sums_to_the_union_and_the_innermost_owns_it():
    # a loop 0-100 holding two operations, one overlapping its neighbour, and
    # a separate one after a gap
    intervals = [(0, 100), (10, 30), (20, 50), (120, 130)]
    shares = pt.exclusive_ns(intervals)
    assert shares == [10 + 50, 10, 30, 10]
    assert sum(shares) == tr.total(tr.union(intervals))


def test_scope_sums_add_up_to_busy_time_and_held_time_counts_every_holder():
    modules = [("jit_step", 0, 100), ("jit_step", 100, 100)]
    ops = [("%fusion.1 = f32[8] fusion(...)", 0, 60),       # a matmul with the update in it
           ("%fusion.2 = f32[8] fusion(...)", 60, 20),
           ("%copy.3 = f32[8] copy(...)", 90, 10),          # the compiler's own
           ("%fusion.1 = f32[8] fusion(...)", 100, 60),
           ("%fusion.2 = f32[8] fusion(...)", 170, 20),
           ("%fusion.9 = f32[8] fusion(...)", 400, 5)]      # outside the window
    scopes = {"fusion.1": ("linear", "optimizer"), "fusion.2": ("optimizer",),
              "copy.3": (None,)}
    # another program's instructions of the same names are not this window's
    programs = {"jit_step": scopes, "jit_tiny": {"fusion.1": ("sdpa",)}}
    d = pt.device_by_scope(ops, modules + [("jit_tiny", 500, 3)], programs)
    assert d["steps"] == 2 and d["busy_ns"] == 170
    assert d["by_scope"] == {"linear": 120, "optimizer": 40, None: 10}
    assert sum(d["by_scope"].values()) == d["busy_ns"]
    assert d["held"]["optimizer"] == 120 + 40 and d["held"]["linear"] == 120
    assert list(d["unscoped_ops"]) == ["%copy.3 = f32[8] copy(...)"]


def hand_trace():
    """Two steps on one device, the host's two calls, and a launch on
    another thread that belongs to neither."""
    return {
        "programs": {"jit_step": {"fusion.1": ("linear",), "fusion.2": ("optimizer",)}},
        "devices": {"/device:TPU:0": {
            "modules": [("jit_step", 0, 100), ("jit_step", 100, 100)],
            "ops": [("%fusion.1 = f32[8] fusion()", 0, 80),
                    ("%fusion.2 = f32[8] fusion()", 100, 70)]}},
        "host": [("bench.dispatch", 0, 25), ("bench.wait", 30, 70),
                 ("bench.dispatch", 100, 30)],
        "spans": [("to_static.call", 2, 20, {"fn": "train_step", "launches": 7,
                                             "dispatch_ops": 40}, "python"),
                  ("to_static.launch", 10, 8, {}, "python"),
                  ("step/compute", 60, 80, {}, "python"),
                  ("to_static.call", 101, 28, {"fn": "train_step", "launches": 8,
                                               "dispatch_ops": 46}, "python"),
                  ("to_static.launch", 105, 20, {}, "python"),
                  ("to_static.launch", 300, 5, {}, "other thread")]}


def test_reduce_gives_launch_self_time_counters_and_gap_labels():
    r = pt.reduce(hand_trace())
    assert r["steps"] == 2 and r["calls"] == 2 and r["fn"] == "train_step"
    assert r["busy_ms"] == pytest.approx(75e-6)
    assert r["scope_ms"] == {"linear": pytest.approx(40e-6),
                             "optimizer": pytest.approx(35e-6)}
    assert r["unscoped_pct"] == 0.0
    assert r["launch_ms"] == pytest.approx(14e-6)              # median of 8 and 20
    assert r["python_ms"] == pytest.approx(10e-6)              # of 20-8 and 28-20
    assert r["bench_dispatch_ms"] == pytest.approx(27.5e-6)
    assert r["per_call"] == {"launches": 1.0, "dispatch_ops": 6.0}
    # the gap 80-100 lies in step/compute; 170-200 in no program span
    assert r["idle_gaps"] == [["no program span", pytest.approx(30e-9)],
                              ["step/compute", pytest.approx(20e-9)]]


def test_custom_calls_a_step_by_scope():
    """Two steps of a program whose five layers run a forward, a rerun
    forward and a backward kernel under `flash_attention`: the custom calls
    of the window, by scope, a step; a fusion, an unscoped custom call and the
    warm-up program's are not counted."""
    scopes = {f"custom-call.{i}": ("flash_attention",) for i in range(15)}
    scopes.update({"custom-call.90": ("moe_experts",), "fusion.1": ("flash_attention",),
                   "custom-call.91": (None,)})
    ops = []
    for step in (0, 1):
        at = 1000 + step * 500
        ops += [(f"%custom-call.{i} = bf16[8]{{0}} custom-call(%p)", at + i, 1)
                for i in range(15)]
        ops += [("%custom-call.90 = bf16[8]{0} custom-call(%p)", at + 20, 1),
                ("%custom-call.91 = bf16[8]{0} custom-call(%p)", at + 22, 1),
                ("%fusion.1 = bf16[8]{0} fusion(%p)", at + 21, 1)]
    modules = [("jit_pure_fn(1)", 1000, 400), ("jit_pure_fn(1)", 1500, 400),
               ("jit_warm(2)", 10, 5)]
    ops += [("%custom-call.0 = bf16[8]{0} custom-call(%p)", 12, 1)]
    d = pt.device_by_scope(ops, modules, {"jit_pure_fn(1)": scopes})
    assert d["kernels"] == {"flash_attention": 30, "moe_experts": 2}
    assert pt.device_by_scope(ops, modules, {})["kernels"] is None
    trace = dict(hand_trace(), programs={"jit_pure_fn(1)": scopes},
                 devices={"/device:TPU:0": {"modules": modules, "ops": ops}})
    r = pt.reduce(trace)
    assert r["scope_kernels"] == {"flash_attention": 15.0, "moe_experts": 1.0}
    m = {"run": {"trace": {"steps": 2}}, "program_trace": r}
    assert pt.kernels_a_layer(m, ("flash_attention",), 5) == 3.0
    assert pt.kernels_a_layer(m, ("flash_attention", "moe_experts"), 4) == 4.0
    assert pt.kernels_a_layer(m, ("linear",), 5) is None      # no kernel there
    assert pt.kernels_a_layer({"run": {"trace": None}}, ("flash_attention",), 5) is None
    assert pt.reduce(hand_trace())["scope_kernels"] == {}
    assert pt.reduce(dict(hand_trace(), programs={}))["scope_kernels"] is None


def test_the_innermost_span_labels_a_gap_that_several_cover():
    spans = [("step/compute", 0, 100, {}, "python"),
             ("to_static.call", 10, 50, {}, "python"),
             ("to_static.launch", 20, 30, {}, "python")]
    assert pt.label_gap((25, 45), spans) == "to_static.launch"
    assert pt.label_gap((25, 58), spans) == "to_static.call"
    assert pt.label_gap((200, 210), spans) == "no program span"


def test_a_trace_that_is_not_the_runs_is_refused():
    trace = hand_trace()
    mine = tr.reduce(trace)
    assert pt.reduce(trace, expected=mine)["steps"] == 2
    with pytest.raises(RuntimeError, match="not this run's trace"):
        pt.reduce(trace, expected=dict(mine, steps=20))
    with pytest.raises(RuntimeError, match="not this run's trace"):
        pt.reduce(trace, expected=dict(mine, window_s=mine["window_s"] * 2))


def test_a_program_without_the_spans_reduces_to_nothing():
    trace = dict(hand_trace(), spans=[("step/compute", 60, 80, {}, "python")])
    assert pt.reduce(trace) is None
    assert pt.reduce(dict(hand_trace(), devices={})) is None


@pytest.mark.parametrize("programs", [
    {},                                                      # the module is not in the trace
    {"jit_step": {"fusion.1": (None,), "fusion.2": (None,)}},  # a cache another tree filled
    {"jit_other": {"fusion.1": ("linear",)}},
])
def test_a_window_whose_program_has_no_scopes_reads_nothing_not_all_unscoped(programs):
    r = pt.reduce(dict(hand_trace(), programs=programs))
    assert r["scope_ms"] is None and r["held_ms"] is None and r["unscoped_pct"] is None
    assert r["scoped_program"] is None
    # the spans do not need the module
    assert r["launch_ms"] == pytest.approx(14e-6) and r["busy_ms"] == pytest.approx(75e-6)
    m = {"run": {"trace": {"steps": 2}}, "program_trace": r}
    for name in ("optimizer_ms.train", "optimizer_carrier_ms.train", "attention_ms.train",
                 "norm_ms.train", "unscoped_device_pct"):
        assert harness.load_reader("layer_metrics", name)(m) is None
    assert harness.load_reader("layer_metrics", "to_static_launch_ms.train")(m) is not None


def test_readers_return_nothing_for_an_untraced_run_and_for_a_missing_counter():
    m = {"run": {"trace": None}}
    for name in ("optimizer_ms.train", "optimizer_carrier_ms.train", "attention_ms.train",
                 "norm_ms.train", "unscoped_device_pct", "to_static_launch_ms.train",
                 "to_static_python_ms.train", "eager_ops_per_step"):
        assert harness.load_reader("layer_metrics", name)(m) is None
    # a counter is in the registry once the program has counted: a program
    # that never does (the parent of the PR that added it) reads None
    from paddle_tpu.profiler import metrics
    assert pt.counter("to_static.no_such_counter_total") is None
    metrics.get_registry().inc_counter("to_static.discover_ops_total", 65)
    assert harness.load_reader("layer_metrics", "discover_ops")(m) >= 65


def test_of_reduces_the_runs_trace_once_and_prints_the_phase_line(monkeypatch, capsys):
    loads = []
    monkeypatch.setattr(pt, "newest_trace", lambda m: "some.xplane.pb")
    monkeypatch.setattr(pt, "load", lambda path: loads.append(path) or hand_trace())
    m = {"run": {"trace": tr.reduce(hand_trace())}}
    read = harness.load_reader("layer_metrics", "optimizer_ms.train")
    assert read(m) == pytest.approx(35e-6)
    assert harness.load_reader("layer_metrics", "optimizer_carrier_ms.train")(m) \
        == pytest.approx(35e-6)
    assert harness.load_reader("layer_metrics", "attention_ms.train")(m) == 0.0
    assert loads == ["some.xplane.pb"]
    (line,) = [l for l in capsys.readouterr().out.splitlines() if l]
    printed = json.loads(line)
    assert printed["phase"] == "program_trace" and printed["scope_ms"]["linear"] > 0
    # another run's trace under the same name is refused, not read
    with pytest.raises(RuntimeError, match="not this run's trace"):
        read({"run": {"trace": dict(m["run"]["trace"], steps=3)}})


# ---------------------------------------------------------------------------
# the compiled module a trace carries

def test_a_fusion_without_metadata_takes_its_root_then_the_majority():
    # (computation id, root id, [(name, id, op_name, called computation ids, opcode)])
    computations = [
        (1, 14, [("m", 11, "jit(f)/optimizer/mul", [], "multiply"),
                 ("a", 12, "jit(f)/optimizer/add", [], "add"),
                 ("d", 13, "jit(f)/transpose(jvp(linear))/dot_general", [], "convolution"),
                 ("t", 14, "", [], "tuple")]),
        (2, 22, [("x", 21, "jit(f)/jvp(sdpa)/exp", [], "exponential"),
                 ("y", 22, "jit(f)/jvp(softmax)/neg", [], "negate")]),
        (3, 34, [("p", 31, "p", [], "parameter"),
                 ("fusion.1", 32, "", [1], "fusion"),
                 ("fusion.2", 33, "", [2], "fusion"),
                 ("fusion.3", 35, "jit(f)/jvp(linear)/dot_general", [1], "fusion"),
                 ("copy.1", 34, None, [], "copy")]),
    ]
    scopes = pt.instruction_scopes(computations)
    assert scopes["fusion.1"] == ("optimizer", "linear")     # no root scope: the majority
    assert scopes["fusion.2"] == ("softmax", "sdpa")         # no own scope: the root's
    assert scopes["fusion.3"] == ("linear", "optimizer")     # its own, and what it holds
    assert scopes["copy.1"] == (None,) and scopes["p"] == (None,)


def test_a_shared_constant_does_not_make_a_fusion_hold_its_scope():
    # XLA keeps one -inf for sdpa's reduce_max and the logits fusion's (my
    # chip run, PR 25: fusion.1257 "held" sdpa through it, 2.3 ms a step)
    computations = [
        (1, 13, [("c", 11, "jit(f)/jvp(sdpa)/reduce_max", [], "constant"),
                 ("b", 12, "jit(f)/jvp(sdpa)/broadcast_in_dim", [], "broadcast"),
                 ("d", 13, "jit(f)/jvp(linear)/dot_general", [], "convolution")]),
        (2, 21, [("fusion.1257", 21, "jit(f)/jvp(linear)/dot_general", [1], "fusion")]),
    ]
    assert pt.instruction_scopes(computations)["fusion.1257"] == ("linear",)


def message(*pairs):
    """A serialized protobuf message of (field number, value) pairs: ints
    as varints, bytes and str as length-delimited fields."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for number, value in pairs:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def test_scopes_are_read_from_the_module_in_the_traces_metadata_plane():
    def instruction(name, ident, op_name=None, calls=None):
        return message((1, name), (2, "fusion"), (35, ident),
                       *([(7, message((1, "op type"), (2, op_name)))] if op_name else []),
                       *([(38, calls)] if calls else []))
    fused = message((1, "fused_computation.7"), (5, 7), (6, 300),
                    (2, instruction("dot.1", 200, "jit(f)/transpose(jvp(fused_ffn))/dot_general")),
                    (2, instruction("mul.2", 201, "jit(f)/optimizer/mul")),
                    (2, instruction("add.4", 202, "jit(f)/optimizer/jit(clip)/add")),
                    (2, instruction("tuple.3", 300)))
    entry = message((1, "main"), (5, 9), (6, 3000),
                    # ids past one byte; called ids packed (bytes) and not (int)
                    (2, instruction("fusion.5", 906238099456, None, b"\x07")),
                    (2, instruction("fusion.6", 3000, "jit(f)/optimizer/add", 7)))
    hlo = message((1, message((1, "jit_f"), (3, fused), (3, entry))))
    plane = message((1, 3), (2, "/host:metadata"),
                    (4, message((1, 42), (2, message((1, 42), (2, "jit_f(42)"),
                                                     (5, message((1, 1), (6, hlo))))))))
    other = message((1, 1), (2, "/host:CPU"), (4, message((1, 1), (2, message((2, "x"))))))
    assert pt.programs_of(message((1, other), (1, plane))) == {"jit_f(42)": {
        "dot.1": ("fused_ffn",), "mul.2": ("optimizer",), "add.4": ("optimizer",),
        "tuple.3": (None,),
        "fusion.5": ("optimizer", "fused_ffn"), "fusion.6": ("optimizer", "fused_ffn")}}


# ---------------------------------------------------------------------------
# the recorded trace

@pytest.fixture(scope="module")
def recorded():
    """(the cut trace, the scopes of all its operations as they were read
    from the whole module when it was cut, its reduction with those)."""
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "gpt3-1p3b_1chip_3steps_spans.textproto")) as f:
        trace = pt.load(ProfileData.text_proto_to_serialized_xspace(f.read()))
    with open(os.path.join(DATA, "gpt3-1p3b_1chip_step_scopes.json")) as f:
        scopes = {k: tuple(v) for k, v in json.load(f).items()}
    (program,) = trace["programs"]
    whole = dict(trace, programs={program: scopes})
    return trace, scopes, pt.reduce(whole, expected=tr.reduce(trace))


def test_recorded_module_gives_the_scopes_that_the_whole_module_gave(recorded):
    trace, scopes, _ = recorded
    (cut,) = trace["programs"].values()       # five instructions of the entry
    named = {k: v for k, v in cut.items() if k in scopes}
    assert named == {k: scopes[k] for k in named} and len(named) == 5
    # the weight-gradient matmul is the root, the AdamW update its epilogue
    assert cut["fusion.1143"][0] == "fused_ffn" and "optimizer" in cut["fusion.1143"]
    assert cut["fusion.1254"] == ("optimizer",)
    # the head's input gradient, with the final LayerNorm's backward fused in
    assert cut["fusion.511"][0] == "linear" and "fused_residual_ln" in cut["fusion.511"]
    assert cut["copy-done.76"] == (None,)


def test_recorded_spans_keep_their_attributes(recorded):
    trace, _, reduced = recorded
    calls = pt.calls_with_launch(trace["spans"])
    assert len(calls) == 3 and all(launch is not None for _, launch in calls)
    assert [int(c[3]["launches"]) for c, _ in calls] == [450, 451, 452]
    assert reduced["fn"] == "make_step.<locals>.train_step"
    assert reduced["steps"] == 3


def test_recorded_scoped_and_unscoped_time_is_the_busy_time(recorded):
    trace, _, reduced = recorded
    assert sum(reduced["scope_ms"].values()) == pytest.approx(reduced["busy_ms"], rel=1e-12)
    assert reduced["busy_ms"] == pytest.approx(1e3 * tr.reduce(trace)["busy_s"] / 3, rel=1e-12)
    # what holds no scope is the compiler's own copies between memories
    assert all(name.startswith("copy") for name, _ in reduced["unscoped_ops"])
    for scope, ms in reduced["held_ms"].items():
        assert ms >= reduced["scope_ms"].get(scope, 0.0) * (1 - 1e-12)
    # no matmul computes attention in its epilogue: nothing but sdpa's own
    # operations holds it (the logits fusion did, through a shared constant)
    assert reduced["held_ms"]["sdpa"] == pytest.approx(reduced["scope_ms"]["sdpa"], rel=1e-12)


# what each reader read when the trace was cut (my chip run, PR 25, seed 2501)
@pytest.mark.parametrize("metric, value", [
    ("optimizer_ms.train", 4.589294333333333),
    ("optimizer_carrier_ms.train", 17.239997666666664),
    ("attention_ms.train", 3.124607333333333),
    ("norm_ms.train", 0.11081933333333332),
    ("unscoped_device_pct", 3.7505936422848474),
    ("to_static_launch_ms.train", 1.35293),
    ("to_static_python_ms.train", 0.57188),
    ("eager_ops_per_step", 0.0),
])
def test_recorded_trace_reads_what_it_read_when_it_was_cut(recorded, metric, value):
    *_, reduced = recorded
    m = {"run": {"trace": {"steps": 3}}, "program_trace": reduced}
    assert harness.load_reader("layer_metrics", metric)(m) == pytest.approx(value, rel=1e-9)
