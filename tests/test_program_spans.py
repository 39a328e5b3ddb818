"""Spans, scopes and counters inside the compiled train step, all on the CPU:
the op scopes in the step's HLO (forward and backward), the `to_static.*`
spans and StepTimer's phases on the jax profiler's host line, and the
registry counters that say what was launched, compiled and dispatched.
"""
import glob
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.jit.to_static import _flatten_tensors
from paddle_tpu.profiler import RecordEvent, metrics
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

ts = importlib.import_module("paddle_tpu.jit.to_static")   # jit.to_static is the decorator
pytestmark = pytest.mark.filterwarnings("ignore:builtin type:DeprecationWarning")
REG = metrics.get_registry()
COUNTERS = ("dispatch.ops_total", "to_static.launches_total",
            "to_static.undonated_launches_total",
            "to_static.grad_path_launches_total",
            "to_static.diverted_calls_total", "to_static.discover_ops_total",
            "to_static.compiles_total", "to_static.trace_sec",
            "to_static.lower_sec", "to_static.backend_compile_sec")


def counters():
    return {name: REG.counter_value(name) for name in COUNTERS}


def moved(before):
    return {k: v - before[k] for k, v in counters().items() if v != before[k]}


def gpt_step():
    """A two-block GPT train step as a user writes it, bf16 with float32
    AdamW masters, and a source of batches for it."""
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        max_position_embeddings=32, intermediate_size=128, dropout=0.0))
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, multi_precision=True,
                                 parameters=model.parameters())

    @paddle.jit.to_static
    def train_step(x, y):
        loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss.astype("float32")

    rng = np.random.default_rng(0)

    def batch():
        x = rng.integers(0, 256, (2, 32)).astype("int64")
        return paddle.to_tensor(x), paddle.to_tensor(np.roll(x, -1, 1))

    return train_step, batch, model


@pytest.fixture(scope="module")
def warm():
    """(step, batch, model, what the counters moved by over the step's
    first three calls: eager discovery, the donating compile, a steady step)."""
    before = counters()
    step, batch, model = gpt_step()
    after_discovery = None
    for i in range(3):
        step(*batch())
        if i == 0:
            after_discovery = moved(before)
    return step, batch, model, after_discovery, moved(before)


def host_events(trace_dir):
    """[(line, name, start, end, {stat: value})] of the trace's host planes."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(line.name, e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events]
    return out


def traced(tmp_path, body):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return host_events(str(tmp_path))


def named(events, name):
    return sorted((e for e in events if e[1] == name), key=lambda e: e[2])


def inside(events, outer):
    return [e for e in events if e[0] == outer[0] and e is not outer
            and outer[2] <= e[2] and e[3] <= outer[3]]


# ---------------------------------------------------------------------------
# scopes in the compiled program

@pytest.fixture(scope="module")
def step_text(warm):
    """The step's program as jax lowers it, with each instruction's name
    stack (the text XLA's `op_name` metadata is made from; not the compiled
    executable's, which a persistent-cache hit may have named earlier)."""
    step, batch, *_ = warm
    (prog,) = step.programs.values()
    return prog.jitted_donate.lower(
        tuple(t._val for t in prog.mutated), tuple(t._val for t in prog.ro),
        tuple(t._val for t in _flatten_tensors((batch(), {}), []))
    ).as_text(debug_info=True)


@pytest.mark.parametrize("scope", ["linear", "sdpa", "layer_norm",
                                   "fused_ffn", "embedding"])
def test_forward_and_backward_instructions_carry_the_op_scope(step_text, scope):
    assert f"/jvp({scope})/" in step_text
    assert f"/transpose(jvp({scope}))/" in step_text


def test_optimizer_update_is_scoped(step_text):
    assert "jit(pure_fn)/optimizer/" in step_text
    # the update's arithmetic is nowhere else: no sqrt outside the optimizer
    # and the norms
    bare = [line for line in step_text.splitlines()
            if '"jit(pure_fn)/sqrt"' in line]
    assert not bare


# ---------------------------------------------------------------------------
# spans on the profiler's host line

@pytest.fixture(scope="module")
def steady_trace(warm, tmp_path_factory):
    """Three steady steps, one Model.train_batch, one RecordEvent and one
    metrics export under a `jax.profiler` trace that nothing of this
    package's own profiler started."""
    step, batch, *_ = warm
    net = paddle.nn.Linear(8, 4)
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=net.parameters()),
                  paddle.nn.MSELoss())
    xb, yb = np.ones((4, 8), "float32"), np.zeros((4, 4), "float32")
    for _ in range(4):
        model.train_batch([xb], [yb])
    exporter = metrics.MetricsExporter(
        REG, interval=0, directory=str(tmp_path_factory.mktemp("export")))

    def body():
        for _ in range(3):
            loss = step(*batch())
        jax.block_until_ready(loss._val)
        model.train_batch([xb], [yb])
        with RecordEvent("user.span"):
            pass
        exporter.export_once()

    return traced(tmp_path_factory.mktemp("trace"), body)


def test_each_step_is_one_call_with_one_launch_inside(steady_trace):
    calls = [c for c in named(steady_trace, "to_static.call")
             if c[4]["fn"].endswith("train_step")]
    assert len(calls) == 3
    for call in calls:
        children = [e[1] for e in inside(steady_trace, call)
                    if e[1].startswith("to_static.")]
        assert children == ["to_static.launch"]
    # the running counters: one launch a step, nothing dispatched op by op
    launches = [int(c[4]["launches"]) for c in calls]
    assert launches == [launches[0], launches[0] + 1, launches[0] + 2]
    assert len({c[4]["dispatch_ops"] for c in calls}) == 1
    for key in ("undonated_launches", "grad_path_launches", "diverted_calls"):
        assert key in calls[0][4]


def test_steptimer_phases_share_the_host_line_and_hold_the_call(steady_trace):
    (line,) = {c[0] for c in named(steady_trace, "to_static.call")}
    (compute,) = named(steady_trace, "step/compute")
    assert compute[0] == line
    assert [e[1] for e in named(steady_trace, "step/h2d")] == ["step/h2d"]
    held = [e[1] for e in inside(steady_trace, compute)
            if e[1].startswith("to_static.")]
    assert held == ["to_static.call", "to_static.launch"]


@pytest.mark.parametrize("name", ["user.span", "metrics.export"])
def test_span_is_on_the_trace_with_no_recorder_on(steady_trace, name):
    assert len(named(steady_trace, name)) == 1


def test_first_calls_show_discovery_probes_and_the_one_compile(tmp_path):
    step, batch, _ = gpt_step()
    before = counters()
    events = traced(tmp_path, lambda: [step(*batch()) for _ in range(3)])
    (discover,) = named(events, "to_static.discover")
    assert int(discover[4]["ops"]) == moved(before)["to_static.discover_ops_total"] > 0
    assert discover[4]["fn"].endswith("train_step")
    assert named(events, "to_static.probe")
    compiles = named(events, "to_static.compile")
    # the first compiled launch is the donating one; no plain twin is built
    assert [c[4]["program"] for c in compiles] == ["donating"]
    for c in compiles:   # the launch that compiled is inside its span
        assert [e[1] for e in inside(events, c)
                if e[1].startswith("to_static.")] == ["to_static.launch"]
    assert len(named(events, "to_static.call")) == 3


def test_steady_call_opens_two_annotations_and_no_more(warm, monkeypatch):
    step, batch, *_ = warm
    x, y = batch()
    opened = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **attrs):
        opened.append((name, attrs))
        return real(name, **attrs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    before = counters()
    step(x, y)
    assert [name for name, _ in opened] == ["to_static.call", "to_static.launch"]
    # the call carries the registry's own running values, as they stood
    assert opened[0][1] == {
        "fn": opened[0][1]["fn"],
        "launches": before["to_static.launches_total"],
        "undonated_launches": before["to_static.undonated_launches_total"],
        "grad_path_launches": before["to_static.grad_path_launches_total"],
        "diverted_calls": before["to_static.diverted_calls_total"],
        "dispatch_ops": before["dispatch.ops_total"]}
    assert opened[1][1] == {}


# ---------------------------------------------------------------------------
# counters

def test_ops_are_dispatched_in_discovery_and_traces_not_in_compiled_steps(warm):
    step, batch, _, after_discovery, _ = warm
    assert after_discovery["dispatch.ops_total"] \
        == after_discovery["to_static.discover_ops_total"] > 0
    x, y = batch()            # to_tensor is not an op of the tape
    before = counters()
    for _ in range(3):
        step(x, y)
    assert moved(before) == {"to_static.launches_total": 3}


def test_first_three_calls_compile_once_and_never_launch_the_plain_program(warm):
    *_, first_three = warm
    assert first_three["to_static.compiles_total"] == 1
    assert first_three["to_static.launches_total"] == 2
    # call 2 donated what the eager pass had assigned (on the CPU, its copy)
    assert "to_static.undonated_launches_total" not in first_three
    for seconds in ("to_static.trace_sec", "to_static.lower_sec",
                    "to_static.backend_compile_sec"):
        assert first_three[seconds] > 0
    assert "to_static.grad_path_launches_total" not in first_three


def test_host_assigned_state_costs_one_copy_and_no_undonated_launch(warm):
    step, batch, model, *_ = warm
    p = model.parameters()[0]
    p.set_value(np.asarray(p._val, dtype="float32"))   # as a checkpoint load does
    assert p._donate_unsafe
    before, copies = counters(), REG.counter_value("to_static.rehomed_leaves_total")
    step(*batch())
    step(*batch())
    assert moved(before) == {"to_static.launches_total": 2}
    assert REG.counter_value("to_static.rehomed_leaves_total") - copies == 1
    assert not p._donate_unsafe


def test_pause_donation_counts_as_undonated(warm):
    step, batch, *_ = warm
    before = counters()
    with ts.pause_donation():
        step(*batch())
    assert moved(before)["to_static.undonated_launches_total"] == 1


def test_a_jit_compiled_outside_adds_nothing(warm):
    before = counters()
    jax.jit(lambda v: jnp.tanh(v) * 3.25)(jnp.ones((3, 5))).block_until_ready()
    assert moved(before) == {}


def test_diverted_call_takes_the_guarded_path_and_is_counted(warm, monkeypatch):
    step, batch, *_ = warm
    monkeypatch.setattr(ts, "_enter_fast_path", lambda: False)
    before = counters()
    loss = step(*batch())
    assert np.isfinite(float(loss.item()))
    assert moved(before) == {"to_static.launches_total": 1,
                             "to_static.diverted_calls_total": 1}


def test_outer_gradient_takes_the_grad_path():
    w = paddle.to_tensor(np.ones((4, 4), "float32"), stop_gradient=False)

    @paddle.jit.to_static
    def forward(x):
        return paddle.matmul(x, w).sum()

    x = paddle.to_tensor(np.ones((2, 4), "float32"))
    forward(x)                                   # discovery
    before = counters()
    forward(x).backward()
    got = moved(before)
    assert got["to_static.grad_path_launches_total"] == 1
    assert got["to_static.launches_total"] == 1
    assert "to_static.undonated_launches_total" not in got
    assert w.grad is not None


def test_pull_counter_is_in_snapshot_and_text_and_survives_reset():
    reg = metrics.MetricsRegistry()
    box = [0]
    reg.register_counter_fn("dispatch.ops_total", lambda: box[0])
    box[0] += 7
    assert reg.counter_value("dispatch.ops_total") == 7.0
    assert reg.snapshot()["counters"]["dispatch.ops_total"] == 7.0
    assert "paddle_tpu_dispatch_ops_total 7" in reg.prometheus_text()
    reg.reset()
    assert reg.counter_value("dispatch.ops_total") == 7.0
