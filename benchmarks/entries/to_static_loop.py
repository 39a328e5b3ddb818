"""A `@paddle.jit.to_static` train step in the user's own loop, on one chip.

The loop keeps at most two steps in flight: it dispatches step i, then waits
on the loss of step i-2, so that timing does not serialise host and device.
A step is complete when its loss is ready.

`run(ctx)` returns what run.py and the metric readers use:
  program             losses, grad_norms, grad_vectors, update_norms of the three
                      compared steps: compiled, from the seed (program.reset)
  rounds              the same of every (make_weights, batches) in ctx["rounds"];
                      a run has one, the last, and the window goes on from it
  losses              the loss of every step of the window
  compiles_in_window  requests to compile between the window's two ends
  setup_s             process start to the window's start (the reference runs after)
  eager_pass_s        the step's first call (to_static's eager discovery pass)
  compile_s           calls 2 and 3 (the plain program and its donating twin)
                      less two steady steps
  window_s, steps     the window's length and the steps completed in it
  step_intervals_s    seconds between completions of successive steps
  host_dispatch_s     per step, the host's time inside the step call (median)
  input_wait_s        per step, the loop's wait for data (entries that have one)
  collectives         collective ops in the compiled step's text (traced runs)
  trace               benchmarks/trace_reduce.py's reduction (traced runs)
"""
import collections
import glob
import os
import shutil
import time

import numpy as np

from benchmarks import harness, program, trace_reduce

SETTLE_STEPS = 6          # steady steps between the compiles and the window


def make_step(paddle, family, model, opt):
    @paddle.jit.to_static
    def train_step(x, y):
        loss = family.loss_of(model, x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss.astype("float32")
    return train_step


def compiled_text(step, x, y):
    """HLO text of the step's one compiled program (as chip_smoke.py reads it)."""
    from paddle_tpu.jit.to_static import _flatten_tensors
    (prog,) = step.programs.values()
    return prog.jitted_donate.lower(
        tuple(t._val for t in prog.mutated), tuple(t._val for t in prog.ro),
        tuple(t._val for t in _flatten_tensors(((x, y), {}), []))).compile().as_text()


def count_collectives(text):
    return sum(text.count(f" {name}(") + text.count(f" {name}-start(")
               for name in trace_reduce.COLLECTIVES)


def start_trace(trace_dir):
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # the loop's own annotations are enough
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop_trace(trace_dir):
    """Stop the profiler and reduce what it wrote."""
    import jax
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return trace_reduce.reduce(trace_reduce.load(path))


def run(ctx, build=program.build, place=lambda paddle, a: paddle.to_tensor(a)):
    """`build` and `place` are what entries/fleet_hybrid.py replaces: how the
    model is made and where a batch is put."""
    import jax
    clock = time.perf_counter
    # `model` and `opt` hold the state the comparison reads; the step drives
    # them through what the build wrapped them in, if anything
    paddle, model, opt, *wrapped = build(ctx)
    step = make_step(paddle, ctx["family"], *(wrapped or (model, opt)))
    stream, emit = ctx["stream"], ctx["emit"]

    def call(x, y):
        t = clock()
        loss = step(place(paddle, x), place(paddle, y))
        jax.block_until_ready(loss._val)
        return float(loss.item()), clock() - t

    def compared(x, y):
        """(loss, whether the step ran the window's program: see
        program.ran_donating)."""
        held, requests = model.parameters()[0]._val, ctx["events"].requests
        loss, _ = call(x, y)
        return loss, program.ran_donating(held, ctx["events"].requests - requests)

    # the step's first three calls: to_static's eager discovery pass, the
    # plain compile and its donating twin
    warm = [call(x, y) for x, y in ctx["rounds"][0][1]]
    call_s = [seconds for _, seconds in warm]

    def compare_round(make_weights, batches):
        """From the seed again, the three steps the reference followed,
        through the donating program that the window drives."""
        program.reset(ctx, model, opt, make_weights)
        prog = {"losses": [], "steps_off_the_window_program": 0}
        for i, (x, y) in enumerate(batches):
            loss, on_program = compared(x, y)
            prog["losses"].append(loss)
            prog["steps_off_the_window_program"] += not on_program
            if i == 0:
                prog["grad_norms"], prog["grad_vectors"] = \
                    program.first_gradient(ctx, model, opt)
        prog["update_norms"] = program.update_norms(ctx, model, opt, make_weights)
        return prog

    rounds = [compare_round(*r) for r in ctx["rounds"]]
    prog = rounds[-1]

    def drive(until=None, steps=None):
        """Train until the clock passes `until` or for `steps` steps."""
        inflight = collections.deque()
        done, losses, dispatch = [], [], []
        t0 = clock()
        while True:
            with jax.profiler.TraceAnnotation("bench.input"):
                x, y = stream.next()
                x, y = place(paddle, x), place(paddle, y)
            t = clock()
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                loss = step(x, y)
            dispatch.append(clock() - t)
            inflight.append(loss._val)
            if len(inflight) > 2:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    losses.append(jax.block_until_ready(inflight.popleft()))
                done.append(clock())
            if (until is not None and clock() >= until) or \
                    (steps is not None and len(dispatch) >= steps):
                break
        with jax.profiler.TraceAnnotation("bench.wait"):
            for value in inflight:
                losses.append(jax.block_until_ready(value))
                done.append(clock())
        return t0, done, [float(v) for v in losses], dispatch

    _, settle_done, _, _ = drive(steps=SETTLE_STEPS)
    steady = (settle_done[-1] - settle_done[1]) / (len(settle_done) - 2)

    requests = ctx["events"].requests
    t0 = clock()
    setup_s = t0 - ctx["t_process"]
    t0, done, losses, dispatch = drive(until=t0 + ctx["seconds"])
    compiles = ctx["events"].requests - requests

    out = {
        "program": prog, "rounds": rounds, "losses": losses,
        "compiles_in_window": compiles,
        "setup_s": setup_s, "eager_pass_s": call_s[0],
        "compile_s": call_s[1] + call_s[2] - 2 * steady,
        "window_s": done[-1] - t0, "steps": len(done),
        "step_intervals_s": [b - a for a, b in zip(done, done[1:])],
        "host_dispatch_s": float(np.median(dispatch)),
        "input_wait_s": None, "collectives": None,
        "trace": None,
    }
    emit("train", setup_s=setup_s, call_seconds=call_s, steady_step_s=steady,
         steps=out["steps"], window_s=out["window_s"],
         interval_samples=len(out["step_intervals_s"]),
         longest_intervals_s=harness.longest_intervals(out["step_intervals_s"]),
         eager_first_loss=warm[0][0], first_loss=prog["losses"][0],
         last_loss=losses[-1])
    if ctx["trace"]:
        start_trace(ctx["trace_dir"])
        drive(steps=ctx["job"]["trace_steps"])
        out["trace"] = stop_trace(ctx["trace_dir"])
        x, y = ctx["rounds"][-1][1][0]
        text = compiled_text(step, place(paddle, x), place(paddle, y))
        out["collectives"] = count_collectives(text)
        emit("compiled_step", collectives=out["collectives"],
             tpu_custom_calls=text.count("tpu_custom_call"))
    return out
