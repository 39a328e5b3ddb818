"""The donation gate of a compiled step (`jit/to_static.py::_donation_gate`),
all on the CPU: what the first compiled launch and a launch after an
assignment donate, as a function of the platform that holds the state (the
TPU's side through a stub of `_platform_of`), of the taint, and of who else
holds the value; and what still runs the non-donating program.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.profiler import metrics

ts = importlib.import_module("paddle_tpu.jit.to_static")   # jit.to_static is the decorator
REG = metrics.get_registry()
PLATFORMS = ["cpu", "tpu"]


def count(name):
    return int(REG.counter_value(f"to_static.{name}_total"))


def mlp(seed=0):
    """Shapes no other test file uses: a file that counts its own eager
    compiles (test_setup_spans.py) may share a worker with this one."""
    paddle.seed(seed)
    model = paddle.nn.Sequential(paddle.nn.Linear(11, 13), paddle.nn.ReLU(),
                                 paddle.nn.Linear(13, 5))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters())

    def train_step(x, y):
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return model, train_step


def batch(i):
    rng = np.random.default_rng(i)
    return (paddle.to_tensor(rng.standard_normal((7, 11)).astype("float32")),
            paddle.to_tensor(rng.integers(0, 5, (7, 1))))


def parameters_of(model):
    return [np.asarray(p._val) for p in model.parameters()]


def pointer(value):
    return value.unsafe_buffer_pointer()


@pytest.fixture
def launches(monkeypatch):
    """[(which, pointers of the state operands)] of every launch."""
    seen = []
    real = ts.StaticFunction._launch

    def spying(self, prog, which, launch, *operands):
        state = operands[0] if which != "grad" else ()
        seen.append((which, [pointer(v) for v in state]))
        return real(self, prog, which, launch, *operands)

    monkeypatch.setattr(ts.StaticFunction, "_launch", spying)
    return seen


@pytest.fixture
def on(monkeypatch):
    """Make the gate see its state on the platform named."""
    def platform(name):
        monkeypatch.setattr(ts, "_platform_of", lambda value: name)
    return platform


def warm_step():
    """A step past its eager pass, its one compile and a steady call."""
    model, fn = mlp()
    step = paddle.jit.to_static(fn)
    for i in range(3):
        step(*batch(i))
    return model, step


# ---------------------------------------------------------------------------
# the decision itself

def tensor(value, tainted):
    t = Tensor(jnp.asarray(value))
    t._donate_unsafe = tainted   # taint-ok: the test sets the bit it reads
    return t


@pytest.mark.parametrize("platform,tainted,held,copied", [
    ("tpu", False, False, False),   # a compiled launch's own output
    ("tpu", False, True, False),    # ... whoever took a reference since
    ("tpu", True, False, False),    # device memory that nothing else holds
    ("tpu", True, True, True),      # another holder: its copy is donated
    ("cpu", False, False, False),
    ("cpu", True, False, True),     # may be a numpy buffer PJRT only imported
    ("cpu", True, True, True),
])
def test_gate_donates_a_value_or_its_copy(on, platform, tainted, held, copied):
    on(platform)
    t = tensor(np.arange(6.0, dtype="float32"), tainted)
    keep = t._val if held else None
    before = count("rehomed_leaves")
    donate, (operand,) = ts._donation_gate([t], True)
    assert donate
    assert (operand is not t._val) == copied
    assert (pointer(operand) != pointer(t._val)) == copied
    assert count("rehomed_leaves") - before == copied
    np.testing.assert_array_equal(np.asarray(operand), np.arange(6.0))
    del keep


@pytest.mark.parametrize("why", ["paused", "no_donating_twin"])
@pytest.mark.parametrize("tainted", [False, True])
def test_gate_hands_the_plain_program_the_values_as_they_are(on, why, tainted):
    on("cpu")
    t = tensor(np.ones(3, "float32"), tainted)
    before = count("rehomed_leaves")
    if why == "paused":
        with ts.pause_donation():
            donate, (operand,) = ts._donation_gate([t], True)
    else:
        donate, (operand,) = ts._donation_gate([t], False)
    assert not donate and operand is t._val
    assert count("rehomed_leaves") == before


def test_a_copy_is_placed_and_committed_as_its_value_was(on):
    on("cpu")
    free = tensor(np.ones((8, 2), "float32"), True)
    assert not free._val.committed
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    spread = tensor(np.ones((8, 2), "float32"), True)
    spread._val = jax.device_put(   # taint-ok: a fixture's placement
        spread._val, NamedSharding(mesh, PartitionSpec("x")))
    _, (a, b) = ts._donation_gate([free, spread], True)
    assert not a.committed and a.sharding == free._val.sharding
    assert b.committed and b.sharding == spread._val.sharding


def test_a_step_inside_a_trace_passes_its_tracers_through(on):
    on("tpu")
    t = tensor(np.ones(3, "float32"), True)
    real = t._val

    def body(v):
        t._val = v   # taint-ok: as pure_fn binds a tracer
        try:
            (operand,) = ts._donation_gate([t], True)[1]
            assert operand is v
        finally:
            t._val = real   # taint-ok: restored
        return v

    jax.eval_shape(body, real)


# ---------------------------------------------------------------------------
# the first compiled launch

@pytest.mark.parametrize("platform", PLATFORMS)
def test_first_compiled_launch_is_the_donating_one(on, launches, platform):
    on(platform)
    undonated, rehomed = count("undonated_launches"), count("rehomed_leaves")
    model, fn = mlp()
    step = paddle.jit.to_static(fn)
    step(*batch(0))                                # the eager pass
    (prog,) = step.programs.values()
    written = {pointer(t._val) for t in prog.mutated}
    step(*batch(1))
    step(*batch(2))
    assert [which for which, _ in launches] == ["donating", "donating"]
    assert prog.ran == {"donating"}
    assert prog.jitted._cache_size() == 0          # never traced
    assert prog.jitted_donate._cache_size() == 1
    assert count("undonated_launches") == undonated
    first = set(launches[0][1])
    if platform == "tpu":
        # the eager pass's own results, as they stand: the state held once
        assert first >= written and count("rehomed_leaves") == rehomed
    else:
        assert not first & written
        assert count("rehomed_leaves") - rehomed == len(launches[0][1])
    assert not any(t._donate_unsafe for t in prog.mutated)


@pytest.mark.parametrize("platform", PLATFORMS)
def test_three_steps_leave_the_parameters_of_an_unjitted_run(on, platform):
    on(platform)
    model, _ = warm_step()
    plain, fn = mlp()
    for i in range(3):
        fn(*batch(i))
    for got, want in zip(parameters_of(model), parameters_of(plain)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("platform", PLATFORMS)
def test_run_steps_scans_through_the_donating_program_alone(on, platform):
    on(platform)
    model, fn = mlp()
    step = paddle.jit.to_static(fn)
    xs, ys = zip(*(batch(i) for i in range(4)))
    x = paddle.to_tensor(np.stack([np.asarray(v._val) for v in xs]))
    y = paddle.to_tensor(np.stack([np.asarray(v._val) for v in ys]))
    rehomed = count("rehomed_leaves")
    step.run_steps(x, y)
    step.run_steps(x, y)
    (prog,) = step.programs.values()
    assert prog.scanned._cache_size() == 0
    assert prog.scanned_donate._cache_size() == 1
    assert (count("rehomed_leaves") == rehomed) == (platform == "tpu")
    assert all(np.isfinite(p).all() for p in parameters_of(model))


# ---------------------------------------------------------------------------
# a value assigned from the host

def test_numpy_assigned_state_is_never_donated_on_the_cpu(launches):
    """The real CPU backend: the array that `set_value` made of a numpy
    buffer may be that buffer, so its copy is donated and it is left alone."""
    model, step = warm_step()
    plain, fn = mlp()
    for i in range(3):
        fn(*batch(i))
    host = np.full((11, 13), 0.25, "float32")
    for m in (model, plain):
        m.parameters()[0].set_value(host)
    p = model.parameters()[0]
    assert p._donate_unsafe
    assigned = pointer(p._val)
    undonated, rehomed = count("undonated_launches"), count("rehomed_leaves")
    del launches[:]
    for i in range(3, 6):
        step(*batch(i))
        fn(*batch(i))
    assert [which for which, _ in launches] == ["donating"] * 3
    assert assigned not in launches[0][1]
    assert count("undonated_launches") == undonated
    assert count("rehomed_leaves") - rehomed == 1
    np.testing.assert_array_equal(host, np.full((11, 13), 0.25, "float32"))
    for got, want in zip(parameters_of(model), parameters_of(plain)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_numpy_assigned_state_is_donated_as_it_stands_off_the_cpu(on, launches):
    """Device memory that the tensor alone holds: no copy, no second
    program, whatever the value was made from."""
    on("tpu")
    model, step = warm_step()
    p = model.parameters()[0]
    p.set_value(np.full((11, 13), 0.25, "float32"))
    assigned, rehomed = pointer(p._val), count("rehomed_leaves")
    del launches[:]
    step(*batch(3))
    assert launches[0][0] == "donating" and assigned in launches[0][1]
    assert count("rehomed_leaves") == rehomed


# ---------------------------------------------------------------------------
# a value that something else holds

def share_by_set_value(p):
    other = paddle.to_tensor(np.full((11, 13), 0.5, "float32"))
    p.set_value(other)
    return other


def share_by_building_a_tensor(p):
    p.set_value(np.full((11, 13), 0.5, "float32"))
    return Tensor(p)


def share_by_detach(p):
    p.set_value(np.full((11, 13), 0.5, "float32"))
    return p.detach()


def share_by_keeping_the_array(p):
    p.set_value(jnp.full((11, 13), 0.5, "float32"))
    return p._val


@pytest.mark.parametrize("platform", PLATFORMS)
@pytest.mark.parametrize("share", [share_by_set_value, share_by_building_a_tensor,
                                   share_by_detach, share_by_keeping_the_array])
def test_a_value_with_a_second_holder_is_not_donated_under_it(
        on, launches, platform, share):
    on(platform)
    model, step = warm_step()
    p = model.parameters()[0]
    other = share(p)
    shared = getattr(other, "_val", other)
    assert shared is p._val
    rehomed = count("rehomed_leaves")
    del launches[:]
    step(*batch(3))
    step(*batch(4))
    assert [which for which, _ in launches] == ["donating", "donating"]
    assert pointer(shared) not in launches[0][1]
    assert count("rehomed_leaves") - rehomed == 1
    assert not shared.is_deleted()
    np.testing.assert_array_equal(np.asarray(shared), np.full((11, 13), 0.5))
    assert p._val is not shared and not p._donate_unsafe


@pytest.mark.parametrize("platform", PLATFORMS)
def test_a_second_holder_of_the_eager_passs_result_keeps_it(on, launches, platform):
    """The first compiled launch, with one of the values the eager pass
    wrote also held by a tensor built from it."""
    on(platform)
    model, fn = mlp()
    step = paddle.jit.to_static(fn)
    step(*batch(0))
    p = model.parameters()[0]
    assert p._donate_unsafe
    other = Tensor(p)
    want = np.asarray(other._val).copy()
    step(*batch(1))
    assert launches[0][0] == "donating"
    assert pointer(other._val) not in launches[0][1]
    np.testing.assert_array_equal(np.asarray(other._val), want)
    assert other._donate_unsafe      # it shares: held to the gate in its turn


def test_two_state_tensors_over_one_array_are_each_given_a_copy(on):
    on("tpu")
    a = tensor(np.ones(4, "float32"), True)
    b = Tensor(a)
    assert b._val is a._val and b._donate_unsafe
    _, (x, y) = ts._donation_gate([a, b], True)
    assert len({pointer(x), pointer(y), pointer(a._val)}) == 3


# ---------------------------------------------------------------------------
# what still runs the non-donating program

@pytest.mark.parametrize("platform", PLATFORMS)
def test_pause_donation_runs_the_plain_program(on, launches, platform):
    on(platform)
    model, step = warm_step()
    (prog,) = step.programs.values()
    assert "plain" not in prog.ran
    held = model.parameters()[0]._val
    undonated = count("undonated_launches")
    del launches[:]
    with ts.pause_donation():
        step(*batch(3))
    assert [which for which, _ in launches] == ["plain"]
    assert pointer(held) in launches[0][1] and not held.is_deleted()
    assert "plain" in prog.ran and prog.jitted._cache_size() == 1
    assert count("undonated_launches") - undonated == 1


@pytest.mark.parametrize("platform", PLATFORMS)
def test_flag_off_builds_no_donating_twin(on, launches, platform):
    on(platform)
    old = paddle.get_flags(["FLAGS_donate_state_buffers"])
    paddle.set_flags({"FLAGS_donate_state_buffers": False})
    try:
        undonated, rehomed = count("undonated_launches"), count("rehomed_leaves")
        _, step = warm_step()
    finally:
        paddle.set_flags(old)
    (prog,) = step.programs.values()
    assert prog.jitted_donate is prog.jitted
    assert [which for which, _ in launches] == ["plain", "plain"]
    assert count("undonated_launches") == undonated   # there is nothing to donate to
    assert count("rehomed_leaves") == rehomed


@pytest.mark.parametrize("platform", PLATFORMS)
def test_outer_gradient_runs_the_plain_program_under_vjp(on, launches, platform):
    on(platform)
    w = paddle.to_tensor(np.ones((4, 4), "float32"), stop_gradient=False)

    @paddle.jit.to_static
    def forward(x):
        return paddle.matmul(x, w).sum()

    x = paddle.to_tensor(np.ones((2, 4), "float32"))
    forward(x)
    held = w._val
    forward(x).backward()
    (prog,) = forward.programs.values()
    assert [which for which, _ in launches] == ["grad"]
    assert prog.ran == {"grad"} and prog.jitted_donate._cache_size() == 0
    assert not held.is_deleted() and w.grad is not None
