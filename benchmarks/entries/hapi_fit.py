"""`paddle.Model.fit` at its defaults over a DataLoader, on one chip.

What a fine-tuning script does: `Model.prepare(optimizer, loss)`, then
`fit(dataset, batch_size=...)` with `steps_per_execution=1`, the
`InputPrefetcher`, and the loss fetched to the host in every step. `fit`
owns the loop, so the benchmark sees it through a callback: a step is
complete when `on_train_batch_end` is called. One `fit` call carries the run
from the first step to the last: the step's first three calls (the eager
pass and both compiles), then, with the state put back to the seed
(program.reset), the three steps the reference followed, the settling steps,
the window, and in a traced run the traced steps. The dataset ends its
stream once the callback says so, and with it the epoch.

Returns the keys entries/to_static_loop.py lists.
"""
import time

from benchmarks import harness, program
from benchmarks.entries.to_static_loop import SETTLE_STEPS, start_trace, stop_trace

WARM = 3   # the step's first calls: the eager pass, the plain compile, its donating twin


def run(ctx):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.profiler import steptimer
    clock = time.perf_counter
    _, network, opt = program.build(ctx)
    cfg, job, stream, emit = ctx["cfg"], ctx["job"], ctx["stream"], ctx["emit"]
    # the steps before the settling ones: compiling, then compared
    settle_from = WARM + harness.CHECK_STEPS * len(ctx["rounds"])

    class Rows(paddle.io.IterableDataset):
        """The first round's batches to compile on, every round's to compare
        on, then the stream, row by row, until stopped."""
        stop = False

        def __iter__(self):
            for _, batches in ctx["rounds"][:1] + ctx["rounds"]:
                for x, y in batches:
                    yield from zip(x, y)
            while not self.stop:
                x, y = stream.next()
                yield from zip(x, y)

    class CrossEntropy(paddle.nn.Layer):
        """Softmax cross-entropy on float32 logits, as the model's own
        `labels=` path computes it."""
        def forward(self, logits, labels):
            return paddle.nn.functional.cross_entropy(
                logits.astype("float32"), labels)

    class Phases(paddle.callbacks.Callback):
        """Compile, compare, settle, window, and in a traced run the traced
        steps."""

        def __init__(self):
            super().__init__()
            self.rounds = []         # what each round's compared steps gave
            self.ends, self.losses = [], []
            self.window = None      # (first step, t0) once it has begun
            self.result = None
            self.span = None
            self.traced_until = None
            self.call_s = []         # each compiling step, from the end of the last
            self.mark = clock()
            self.held = None         # before a compared step: a parameter's value, the compile count

        def on_train_batch_begin(self, step, logs=None):
            if WARM <= step < settle_from:
                self.held = (network.parameters()[0]._val, ctx["events"].requests)
            self.span = jax.profiler.TraceAnnotation("bench.step")
            self.span.__enter__()

        def on_train_batch_end(self, step, logs=None):
            self.span.__exit__(None, None, None)
            now = clock()
            self.ends.append(now)
            self.losses.append(float(logs["loss"][0]))
            if step < WARM:
                self.call_s.append(now - self.mark)
                if step == WARM - 1:
                    program.reset(ctx, network, opt, ctx["rounds"][0][0])
                self.mark = clock()
            elif step < settle_from:
                self.compared_step(*divmod(step - WARM, harness.CHECK_STEPS))
                self.ends[-1] = clock()
            elif step == settle_from + SETTLE_STEPS - 1:
                steptimer.get_steptimer().reset()
                self.requests = ctx["events"].requests
                self.window = (step + 1, clock())
                self.ends[-1] = self.window[1]
            elif self.window and self.result is None and \
                    now >= self.window[1] + ctx["seconds"]:
                self.close_window(step, now)
            elif self.result and step == self.traced_until:
                self.result["trace"] = stop_trace(ctx["trace_dir"])
                rows.stop = True

        def compared_step(self, r, i):
            """Step `i` of round `r`: from the seed, through the donating
            program that the window drives."""
            held, requests = self.held
            if i == 0:
                self.rounds.append({"losses": [], "steps_off_the_window_program": 0})
            prog, make_weights = self.rounds[r], ctx["rounds"][r][0]
            prog["losses"].append(self.losses[-1])
            prog["steps_off_the_window_program"] += not program.ran_donating(
                held, ctx["events"].requests - requests)
            if i == 0:
                prog["grad_norms"], prog["grad_vectors"] = \
                    program.first_gradient(ctx, network, opt)
            if i == harness.CHECK_STEPS - 1:
                prog["update_norms"] = program.update_norms(
                    ctx, network, opt, make_weights)
                if r + 1 < len(ctx["rounds"]):
                    program.reset(ctx, network, opt, ctx["rounds"][r + 1][0])

        def close_window(self, step, now):
            first, t0 = self.window
            ends = self.ends[first - 1:]          # t0, then each completion
            timer = steptimer.get_steptimer().breakdown()
            n = step + 1 - first
            phase = timer["phase_ms"]
            call_s = self.call_s
            steady = (self.ends[first - 1]
                      - self.ends[settle_from]) / (SETTLE_STEPS - 1)
            self.result = {
                "program": self.rounds[-1], "rounds": self.rounds,
                "losses": self.losses[first:],
                "compiles_in_window": ctx["events"].requests - self.requests,
                "setup_s": t0 - ctx["t_process"],
                "eager_pass_s": call_s[0],
                "compile_s": call_s[1] + call_s[2] - 2 * steady,
                "window_s": now - t0, "steps": n,
                "step_intervals_s": [b - a for a, b in zip(ends, ends[1:])],
                # Model.train_batch's phases: h2d stages the batch, compute
                # dispatches the step and, every 16th step, waits for it
                "host_dispatch_s": (phase.get("h2d", 0.0)
                                    + phase.get("compute", 0.0)) / 1e3 / n,
                "input_wait_s": phase.get("input_wait", 0.0) / 1e3 / n,
                "collectives": None, "trace": None,
            }
            emit("train", setup_s=self.result["setup_s"], call_seconds=call_s,
                 steady_step_s=steady, steps=n, window_s=now - t0,
                 interval_samples=n, eager_first_loss=self.losses[0],
                 longest_intervals_s=harness.longest_intervals(
                     self.result["step_intervals_s"]),
                 first_loss=self.rounds[-1]["losses"][0],
                 last_loss=self.losses[-1], steptimer=timer)
            if ctx["trace"]:
                start_trace(ctx["trace_dir"])
                self.traced_until = step + job["trace_steps"]
            else:
                rows.stop = True

    rows = Rows()
    phases = Phases()
    model = paddle.Model(network)
    model.prepare(optimizer=opt, loss=CrossEntropy())
    model.fit(rows, batch_size=job["batch"], epochs=1, verbose=0,
              callbacks=[phases])
    if phases.result is None:
        raise RuntimeError("fit ended before the window closed")
    return phases.result
