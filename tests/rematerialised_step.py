"""What one `to_static` training step over rematerialised blocks runs and
stages, for the tests of the models whose blocks are two regions of
`fleet.utils.recompute` round their mixer's core (test_kimi_linear.py,
test_deepseek_v2.py, test_lfm2_ops.py): the step is run once eagerly (the
discovery pass) and traced once, nothing is compiled."""
import re

import jax.numpy as jnp
import paddle_tpu as paddle
from benchmarks import program_trace
from paddle_tpu.jit.to_static import _flatten_tensors
from paddle_tpu.profiler import metrics


def flash_on_a_cpu(patch, min_seq=128):
    """A platform rule that says TPU, so that attention over `min_seq` keys
    and more takes the flash pair (interpreted on a CPU)."""
    from paddle_tpu.ops import attention
    patch.setattr(attention, "_platform", lambda: "tpu")
    patch.setattr(attention, "FLASH_MIN_SEQ_K", min_seq)
    patch.setattr(attention, "FLASH_MIN_SEQ_Q", min_seq)


def traced_step(model, loss_of, x, y):
    """{"names": the `op_name`s of the donating step's lowered text, "text",
    "passes": the runs of the step's body, "moved": what the registry's
    counters moved by}, the last two for the eager pass and for the traces
    of `_build` apart."""
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    bodies = []

    @paddle.jit.to_static
    def step(x, y):
        bodies.append(None)                           # one run of the body a pass
        loss = loss_of(model, x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    def counters():
        return dict(metrics.get_registry().snapshot()["counters"])

    def moved(then, now):
        return {k: now[k] - then.get(k, 0.0) for k in now}
    before = counters()
    step(x, y)                                        # the eager discovery pass
    discovered, eager = counters(), len(bodies)
    (prog,) = step.programs.values()
    step._build(prog, (x, y), {})                     # traces; compiles nothing
    after, built = counters(), len(bodies)
    text = prog.jitted_donate.lower(
        tuple(t._val for t in prog.mutated), tuple(t._val for t in prog.ro),
        tuple(t._val for t in _flatten_tensors(((x, y), {}), []))
    ).as_text(debug_info=True)
    return {"text": text,
            "names": set(re.findall(r'loc\("(jit\(pure_fn\)/[^"]*)"', text)),
            "passes": {"eager": eager, "traced": built - eager},
            "moved": {"eager": moved(before, discovered),
                      "traced": moved(discovered, after),
                      "both": moved(before, after)}}


def passes_of(names, scope):
    """Which of a rematerialised step's passes hold instructions of `scope`:
    "forward", "rerun" (a region's backward running the region again) and
    "backward"."""
    found = set()
    for n in names:
        if program_trace.scope_of(n + "/op") != scope:
            continue
        if n.startswith(f"jit(pure_fn)/jvp({scope})"):
            found.add("forward")
        elif "transpose(jvp(transpose(" in n or f"/transpose(jvp({scope}))" in n:
            found.add("backward")                 # inside a region; on the tape
        elif f"transpose(jvp(jvp({scope})))" in n:
            found.add("rerun")
    return found


def grads_by_leaf(model, names, loss):
    loss.backward()
    tensors = model.state_dict()
    return {leaf: tensors[key].grad for leaf, key in names.items()}


def assert_the_same_gradients(plain, remat, tol=1e-6, none_ok=("expert_bias",)):
    """Every leaf's gradient of the rematerialised model against the plain
    model's, by the norm of the gap over the norm."""
    for leaf, grad in plain.items():
        if grad is None:
            assert leaf.endswith(none_ok) and remat[leaf] is None, leaf
            continue
        norm = float(jnp.linalg.norm(jnp.ravel(grad._val)))
        assert norm > 0.0, leaf
        gap = float(jnp.linalg.norm(jnp.ravel(remat[leaf]._val - grad._val)))
        assert gap <= tol * norm, (leaf, gap / norm)
