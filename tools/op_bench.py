#!/usr/bin/env python
"""Per-op / fused-kernel micro-benchmark harness (VERDICT r4 missing #1).

Reference precedent: the config-driven op benchmark tool
operators/benchmark/op_tester.cc:1 + the CPU-vs-GPU timing harness
python/paddle/fluid/tests/unittests/benchmark.py:1, feeding the CI op-level
regression gate tools/check_op_benchmark_result.py:1. This is the TPU-native
equivalent: it times each fused kernel in ops/ against the unfused XLA
composition it replaces, per direction (fwd, fwd+bwd) and per dtype, and
emits a JSON artifact (OPBENCH.json) that `--check-against` compares
round-over-round so kernel-tier regressions are attributable instead of
being inferred from e2e deltas.

Usage:
    python tools/op_bench.py [--out OPBENCH.json] [--filter flash]
        [--dtypes bf16,f32] [--check-against OLD.json] [--tol 0.10]
        [--small]   # CI-sized shapes (CPU-runnable; used by the unit test)

Timing: per case, the `inner` repetitions are folded INSIDE one jitted
`lax.scan` whose carry takes a (numerically ~1) data dependence on each
iteration's outputs — so a single device dispatch times `inner` serialized
executions, and per-call dispatch cost cannot swamp ms-scale kernels. The
carry also rescales the inputs each iteration (one elementwise pass), which
defeats CSE; that overhead is identical for the fused and unfused paths, so
the speedup column is unbiased and the absolute ms carry a small constant
inflation. Reports min ms/iter over `iters` dispatches (min strips
host scheduler noise).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _np_dtype(name):
    import ml_dtypes
    import numpy as np
    return {"bf16": np.dtype(ml_dtypes.bfloat16),
            "f32": np.float32}[name]


def _repeat_fn(fn, inner):
    """One jitted program running `inner` serialized executions of fn: the
    scan carry c (~1.0) rescales the inputs each iteration and absorbs a
    tiny projection of the outputs, forcing iteration-to-iteration data
    dependence so XLA can neither CSE nor reorder the repeats."""
    import jax
    import jax.numpy as jnp

    def rep(*args):
        def body(c, _):
            scaled = [a * c.astype(a.dtype) if hasattr(a, "dtype")
                      and jnp.issubdtype(a.dtype, jnp.inexact) else a
                      for a in args]
            outs = fn(*scaled)
            s = sum(jnp.sum(o.astype(jnp.float32))
                    for o in jax.tree_util.tree_leaves(outs))
            return (1.0 + s * 1e-30).astype(jnp.float32), ()
        c, _ = jax.lax.scan(body, jnp.float32(1.0), None, length=inner)
        return c
    return jax.jit(rep)


def _timed(fn, args, iters, inner):
    """ms per execution of fn.

    On an accelerator: the DIFFERENCE between a 4*inner-iteration scan and
    an inner-iteration scan (one dispatch each) — dispatch latency and the
    result fetch cancel exactly, leaving 3*inner executions of pure device
    time. The scalar result is pulled to the host, which cannot complete
    before the execution has.

    On CPU (CI --small path): a direct timed loop — there is no dispatch
    latency worth cancelling, and differencing two us-scale runs is
    noise-dominated."""
    import jax
    import numpy as np

    def run_sync(rep):
        out = rep(*args)
        return float(np.asarray(jax.device_get(out)))

    if jax.devices()[0].platform == "cpu":
        jitted = jax.jit(fn)
        jax.block_until_ready(jitted(*args))  # compile
        jax.block_until_ready(jitted(*args))
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            for _ in range(inner):
                out = jitted(*args)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / inner)
        return max(best, 1e-9) * 1e3  # same zero floor as below

    # adaptive scan length: for sub-ms kernels the 3*inner executions must
    # dominate the jitter between the two dispatches, so grow
    # inner until the delta is a solid fraction of the total, else the
    # cheap fwd rows are noise (first artifact recorded a floored 0.000 ms
    # flash fwd with a nonsense speedup)
    inner_cur = max(1, inner)
    while True:
        rep_small = _repeat_fn(fn, inner_cur)
        rep_big = _repeat_fn(fn, 4 * inner_cur)
        run_sync(rep_small)  # compile
        run_sync(rep_big)
        best_small = best_big = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            run_sync(rep_small)
            best_small = min(best_small, time.perf_counter() - t0)
            t0 = time.perf_counter()
            run_sync(rep_big)
            best_big = min(best_big, time.perf_counter() - t0)
        delta = best_big - best_small
        if delta >= 0.25 * best_small or inner_cur >= 64 * max(1, inner):
            break
        inner_cur *= 4
    # floor at 1 ns: a noise-dominated delta must not divide speedup by 0
    return max(delta, 1e-9) / (3 * inner_cur) * 1e3  # ms


# ---------------------------------------------------------------- cases ---

def _case_flash_attention(dtype, small):
    """Pallas flash attention vs the XLA fused-softmax attention path —
    the exact pair ops/attention.py auto-selects between."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.attention import _flash_attention_diff, _xla_attention
    from paddle_tpu.ops.pallas.flash_attention import _interpret

    b, s, h, d = (1, 256, 2, 64) if small else (4, 1024, 16, 64)
    scale = 1.0 / d ** 0.5
    rng = np.random.RandomState(0)
    qkv = [jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)
                       .astype(_np_dtype(dtype))) for _ in range(3)]
    interp = _interpret(qkv[0])

    def fused_fwd(q, k, v):
        return _flash_attention_diff(q, k, v, True, scale, interp)

    def unfused_fwd(q, k, v):
        return _xla_attention(q, k, v, None, scale, True, 0.0, None)

    def grad_of(f):
        def loss(q, k, v):
            return jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))

    return {"args": qkv, "shape": f"b{b} s{s} h{h} d{d}",
            "fwd": (fused_fwd, unfused_fwd),
            "fwd_bwd": (grad_of(fused_fwd), grad_of(unfused_fwd))}


def _case_fused_conv_bn(dtype, small):
    """fused_conv_bn's custom-backward memory plan vs plain autodiff
    through the identical forward math (what per-op autodiff would save)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.fused_conv_bn import (_fused_conv_bn_diff,
                                              _fused_fwd_impl)

    n, hw, cin, cout = (4, 16, 8, 8) if small else (64, 56, 56, 64)
    stride, pad, dil = (1, 1), ((1, 1), (1, 1)), (1, 1)
    dn = ("NHWC", "OIHW", "NHWC")
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, hw, hw, cin).astype(np.float32)
                    .astype(_np_dtype(dtype)))
    w = jnp.asarray((rng.randn(cout, cin, 3, 3) * 0.1).astype(np.float32)
                    .astype(_np_dtype(dtype)))
    g = jnp.asarray((rng.rand(cout) + 0.5).astype(np.float32))
    beta = jnp.asarray(rng.randn(cout).astype(np.float32) * 0.1)

    def fused_fwd(xv, wv, gv, bv):
        return _fused_conv_bn_diff(xv, wv, gv, bv, stride, pad, dil, 1, dn,
                                   1e-5, True)[0]

    def unfused_fwd(xv, wv, gv, bv):
        return _fused_fwd_impl(xv, wv, gv, bv, stride, pad, dil, 1, dn,
                               1e-5, True)[0]

    def grad_of(f):
        def loss(xv, wv, gv, bv):
            return jnp.sum(jnp.tanh(f(xv, wv, gv, bv).astype(jnp.float32)))
        return jax.grad(loss, argnums=(0, 1, 2, 3))

    return {"args": [x, w, g, beta], "shape": f"n{n} {hw}x{hw} c{cin}->{cout}",
            "fwd": (fused_fwd, unfused_fwd),
            "fwd_bwd": (grad_of(fused_fwd), grad_of(unfused_fwd))}


def _case_fused_ffn(dtype, small):
    """fused_ffn (backward recomputes the 4h activation) vs the composed
    linear->gelu->linear whose autodiff saves it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.fused_ffn import _fused_ffn_diff

    n, d, dff = (8, 64, 256) if small else (4096, 1024, 4096)
    rng = np.random.RandomState(0)
    cast = lambda a: jnp.asarray(a.astype(np.float32).astype(_np_dtype(dtype)))
    x = cast(rng.randn(n, d))
    w1 = cast(rng.randn(d, dff) * 0.05)
    b1 = cast(rng.randn(dff) * 0.05)
    w2 = cast(rng.randn(dff, d) * 0.05)
    b2 = cast(rng.randn(d) * 0.05)

    def fused_fwd(xv, w1v, b1v, w2v, b2v):
        return _fused_ffn_diff(xv, w1v, b1v, w2v, b2v, "gelu_tanh")

    def unfused_fwd(xv, w1v, b1v, w2v, b2v):
        h = jnp.dot(xv, w1v) + b1v
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(
            xv.dtype)
        return jnp.dot(h, w2v) + b2v

    def grad_of(f):
        def loss(*a):
            return jnp.sum(f(*a).astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))

    return {"args": [x, w1, b1, w2, b2], "shape": f"n{n} d{d} dff{dff}",
            "fwd": (fused_fwd, unfused_fwd),
            "fwd_bwd": (grad_of(fused_fwd), grad_of(unfused_fwd))}


def _case_fused_residual_ln(dtype, small):
    """fused_residual_ln (backward recovers x_hat from the LN output; the
    residual stream z never saved) vs plain autodiff of layer_norm(x+y)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.fused_residual_ln import _fused_residual_ln_diff

    b, s, h = (2, 32, 64) if small else (4, 1024, 1024)
    rng = np.random.RandomState(0)
    cast = lambda a: jnp.asarray(a.astype(np.float32).astype(_np_dtype(dtype)))
    x = cast(rng.randn(b, s, h))
    y = cast(rng.randn(b, s, h))
    w = cast(rng.rand(h) + 0.5)
    bias = cast(rng.randn(h) * 0.1)

    def fused_fwd(xv, yv, wv, bv):
        z, out = _fused_residual_ln_diff(xv, yv, wv, bv, 1e-5, True, None)
        return z, out

    def unfused_fwd(xv, yv, wv, bv):
        z = xv + yv
        zf = z.astype(jnp.float32)
        mean = jnp.mean(zf, axis=-1, keepdims=True)
        var = jnp.var(zf, axis=-1, keepdims=True)
        out = ((zf - mean) * jax.lax.rsqrt(var + 1e-5)
               * wv.astype(jnp.float32)
               + bv.astype(jnp.float32)).astype(z.dtype)
        return z, out

    def grad_of(f):
        def loss(*a):
            z, out = f(*a)
            return (jnp.sum(out.astype(jnp.float32) ** 2)
                    + 0.3 * jnp.sum(z.astype(jnp.float32) ** 2))
        return jax.grad(loss, argnums=(0, 1, 2, 3))

    return {"args": [x, y, w, bias], "shape": f"b{b} s{s} h{h}",
            "fwd": (fused_fwd, unfused_fwd),
            "fwd_bwd": (grad_of(fused_fwd), grad_of(unfused_fwd))}


CASES = {
    "flash_attention": _case_flash_attention,
    "fused_conv_bn": _case_fused_conv_bn,
    "fused_ffn": _case_fused_ffn,
    "fused_residual_ln": _case_fused_residual_ln,
}


def run(filter_=None, dtypes=("bf16", "f32"), small=False, iters=5,
        inner=10):
    import jax
    rows = []
    for name, build in CASES.items():
        if filter_ and filter_ not in name:
            continue
        for dtype in dtypes:
            case = build(dtype, small)
            args = list(case["args"])
            for direction in ("fwd", "fwd_bwd"):
                fused_fn, unfused_fn = case[direction]
                # 1e-6 ms floor survives the 6-decimal artifact rounding: a
                # noise-floored measurement records as the sentinel
                # 0.000001, never 0.0 (which would fake infinite speedups
                # and dodge check_against)
                fused_ms = max(_timed(fused_fn, args, iters, inner), 1e-6)
                unfused_ms = max(_timed(unfused_fn, args, iters, inner),
                                 1e-6)
                speedup = unfused_ms / fused_ms
                rows.append({
                    "op": name, "dtype": dtype, "direction": direction,
                    "shape": case["shape"],
                    "fused_ms": round(fused_ms, 6),
                    "unfused_ms": round(unfused_ms, 6),
                    "speedup": round(speedup, 3),
                })
                print(f"[op_bench] {name:18s} {dtype:4s} {direction:7s} "
                      f"fused {fused_ms:8.3f} ms  unfused {unfused_ms:8.3f} "
                      f"ms  x{speedup:.2f}", file=sys.stderr, flush=True)
    return {"device": jax.devices()[0].device_kind,
            "small": small, "ops": rows}


def check_against(new_doc, old_doc, tol=0.10):
    """Kernel-tier regression check (the micro analog of
    check_bench_regression): fused_ms may not slow by more than tol vs the
    previous artifact on the same (op, dtype, direction, device). Returns a
    list of regression rows."""
    if new_doc.get("device") != old_doc.get("device"):
        return []  # different hardware: timings not comparable
    old = {(r["op"], r["dtype"], r["direction"]): r
           for r in old_doc.get("ops", [])}
    regs = []
    for r in new_doc.get("ops", []):
        o = old.get((r["op"], r["dtype"], r["direction"]))
        if not o or o.get("shape") != r.get("shape"):
            continue
        if o["fused_ms"] <= 2e-6 or r["fused_ms"] <= 2e-6:
            continue  # noise-floored row(s): not a comparable measurement
        if r["fused_ms"] > o["fused_ms"] * (1.0 + tol):
            regs.append({"op": r["op"], "dtype": r["dtype"],
                         "direction": r["direction"],
                         "old_ms": o["fused_ms"], "new_ms": r["fused_ms"],
                         "ratio": round(r["fused_ms"] / o["fused_ms"], 3)})
    return regs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "OPBENCH.json"))
    ap.add_argument("--filter", default=None)
    ap.add_argument("--dtypes", default="bf16,f32")
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="fastest useful run: --small shapes, one iteration "
                         "(the non-slow test-suite / bench.py opbench lane)")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--inner", type=int, default=10)
    ap.add_argument("--check-against", default=None)
    ap.add_argument("--tol", type=float, default=0.10)
    ns = ap.parse_args(argv)
    if ns.smoke:
        ns.small, ns.iters, ns.inner = True, 1, 1
    doc = run(ns.filter, tuple(ns.dtypes.split(",")), ns.small, ns.iters,
              ns.inner)
    doc["smoke"] = ns.smoke
    with open(ns.out, "w") as f:
        json.dump(doc, f, indent=2)
    if ns.check_against and os.path.exists(ns.check_against):
        with open(ns.check_against) as f:
            old = json.load(f)
        regs = check_against(doc, old, ns.tol)
        print(json.dumps({"status": "fail" if regs else "ok",
                          "regressions": regs}))
        return 1 if regs else 0
    print(json.dumps({"status": "ok", "rows": len(doc["ops"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
