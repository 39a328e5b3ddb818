"""What the program's own spans and scopes say of a traced run: device time
per step by program scope (the Paddle op or `optimizer` whose code staged an
instruction), the `to_static.call` / `to_static.launch` spans of the window,
the counters those spans carry, and the longest idle gaps labelled by the
program span the host was in.

The profiler's trace names a device operation by its HLO instruction and not
by the `op_name` that holds the scope (jax's `ProfileData` shows an event's
own stats, `tf_op` is a stat of its metadata, and multi-output fusions have
none at all). But the trace carries each compiled module whole, as an
HloProto in its `/host:metadata` plane, and `programs_of` reads the scopes
from there: the file says everything itself, and nothing of the program has
to be alive when it is read. An instruction takes the scope of its own
`op_name` (XLA gives a fusion its root's); one without that of its fused
computation's root, or of most of that computation's instructions where the
root has none. That scope splits busy time exactly (`scope_ms`). XLA also
fuses across scopes: on the TPU the AdamW update of a weight is the epilogue
of the matmul that makes its gradient, and LayerNorm the prologue or epilogue
of the matmul beside it, so a fusion also *holds* every scope that an
instruction of its fused computation carries (one that computes: a constant
or a broadcast that XLA shares between two fusions keeps the `op_name` of
the first), and `held_ms` is the time of the operations that hold a scope:
carrier time, which does not add up across scopes. The two bracket what a
scope costs; the time inside one fusion cannot be split.

`load` reads the trace into plain lists, `reduce` is arithmetic on them
(tests/benchmark/test_program_trace.py), `of(measured)` does both for the
run's own trace, once, and prints the `program_trace` phase line. A
program without these spans (the parent of the PR that added them) reduces
to None, and every reader built on this returns None for it. A window
whose program the trace does not carry, or carries with no scoped instruction
at all (jax's persistent cache ignores `op_name`, so a cache that another
tree filled hands back that tree's scopes), gives `scope_ms`, `held_ms` and
`unscoped_pct` None, not 100% unscoped: the scope readers then return None.

By hand: python3 benchmarks/program_trace.py <file.xplane.pb>
"""
import collections
import glob
import json
import os
import re
import statistics
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import trace_reduce

PROGRAM_SPANS = ("to_static.", "step/", "metrics.")
CALL, LAUNCH = "to_static.call", "to_static.launch"
UNSCOPED = "(unscoped)"
_TRANSFORM = re.compile(r"^\w+\((.*)\)$")
_STAGED = ("jit(", "pjit(")
# opcodes that compute nothing: their `op_name` does not make a fusion hold a scope
NO_WORK = frozenset(("constant", "parameter", "iota", "broadcast", "bitcast",
                     "tuple", "get-tuple-element"))


# ---------------------------------------------------------------------------
# from an instruction to its program scope

def scope_of(op_name):
    """The outermost program scope in an HLO `op_name`, or None:
    `jit(pure_fn)/transpose(jvp(sdpa))/dot_general` is `sdpa`,
    `jit(pure_fn)/optimizer/jit(clip)/max` is `optimizer`, `jit(pure_fn)/add`
    has none. The last part is the primitive; `jit(...)` parts are functions
    jax staged, not scopes; transforms wrap the scope they differentiated."""
    for part in op_name.split("/")[:-1]:
        while not part.startswith(_STAGED) and _TRANSFORM.match(part):
            part = _TRANSFORM.match(part).group(1)
        if part and not part.startswith(_STAGED):
            return part
    return None


def instruction_scopes(computations):
    """{instruction name: (its scope or None, the other scopes it holds...)}
    for every instruction of a compiled module, given as
    [(computation id, root instruction id, [(name, id, op_name, [ids of the
    computations it calls], opcode)])]."""
    own, calls, members, roots = {}, {}, {}, {}
    for computation, root, instructions in computations:
        members[computation] = [name for name, *_, opcode in instructions
                                if opcode not in NO_WORK]
        for name, ident, op_name, called, _ in instructions:
            own[name] = scope_of(op_name) if op_name else None
            if called:
                calls[name] = called[0]
            if ident == root:
                roots[computation] = name
    scopes = {}
    for name, scope in own.items():
        inside = collections.Counter(
            own[i] for i in members.get(calls.get(name), ()) if own[i] is not None)
        if scope is None and inside:
            scope = own.get(roots.get(calls[name])) or inside.most_common(1)[0][0]
        scopes[name] = (scope, *sorted(set(inside) - {scope}))
    return scopes


# ---------------------------------------------------------------------------
# the compiled programs a trace carries

def varint(buf, at):
    """(the varint that starts at `buf[at]`, the index after it)."""
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf):
    """(field number, value) of each field of a serialized protobuf message:
    a varint as an int, a length-delimited field as a slice of `buf`."""
    at, end = 0, len(buf)
    while at < end:
        key, at = varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = varint(buf, at)
            yield key >> 3, value
        elif wire == 2:
            size, at = varint(buf, at)
            yield key >> 3, buf[at:at + size]
            at += size
        elif wire in (1, 5):
            at += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {at}")


def varints(buf):
    """The varints of a packed repeated field."""
    out, at = [], 0
    while at < len(buf):
        value, at = varint(buf, at)
        out.append(value)
    return out


def field(buf, number):
    """Every value of field `number` of a serialized message."""
    return [value for n, value in fields(buf) if n == number]


def programs_of(xspace):
    """{program name as the "XLA Modules" line has it: instruction_scopes}
    from a serialized XSpace. The profiler puts each compiled module into the
    `/host:metadata` plane as an HloProto, the one place where the `op_name`
    of instructions inside fused computations can be read: jax's ProfileData
    does not reach it, so this walks the wire format, by the field numbers
    of tsl's xplane.proto and xla's hlo.proto."""
    out = {}
    for plane in field(xspace, 1):                                  # XSpace.planes
        if bytes(field(plane, 2)[0]) != b"/host:metadata":          # XPlane.name
            continue
        for entry in field(plane, 4):                               # .event_metadata
            metadata = field(entry, 2)[0]                           # map value
            name = bytes(field(metadata, 2)[0]).decode()            # XEventMetadata.name
            for stat in field(metadata, 5):                         # .stats
                for proto in field(stat, 6):                        # XStat.bytes_value
                    module = field(proto, 1)[0]                     # HloProto.hlo_module
                    out[name] = instruction_scopes(
                        [computation_of(c) for c in field(module, 3)])
    return out


def computation_of(buf):
    """(id, root id, [(name, id, op_name, called computation ids, opcode)])
    of a serialized HloComputationProto."""
    ident = root = None
    instructions = []
    for number, value in fields(buf):
        if number == 5:
            ident = value
        elif number == 6:
            root = value
        elif number == 2:                                           # .instructions
            name = op_name = opcode = i = None
            called = []
            for n, v in fields(value):
                if n == 1:
                    name = bytes(v).decode()
                elif n == 2:
                    opcode = bytes(v).decode()
                elif n == 35:
                    i = v
                elif n == 7:                                        # .metadata.op_name
                    op_name = "".join(bytes(x).decode() for x in field(v, 2))
                elif n == 38:                                       # .called_computation_ids
                    called += [v] if isinstance(v, int) else varints(v)
            instructions.append((name, i, op_name, called, opcode))
    return ident, root, instructions


def instruction_of(event_name):
    """`%fusion.12 = f32[..] fusion(...)` -> `fusion.12`."""
    return event_name.partition(" = ")[0].lstrip("%")


# ---------------------------------------------------------------------------
# the trace as plain lists

def load(source):
    """A trace, from the path of an `.xplane.pb` or from a serialized XSpace:
    trace_reduce.load's dict, with the program's host spans under "spans",
    [(name, start, dur, {attribute: value}, line)], and under "programs" the
    scopes of every compiled program the trace carries (`programs_of`)."""
    from jax.profiler import ProfileData
    if isinstance(source, str):
        with open(source, "rb") as f:
            source = f.read()
    profile = ProfileData.from_serialized_xspace(source)
    loaded = trace_reduce.load(profile)
    spans = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns, dict(e.stats), line.name)
                          for e in line.events if e.name.startswith(PROGRAM_SPANS)]
    loaded["spans"] = spans
    loaded["programs"] = programs_of(source)
    return loaded


# ---------------------------------------------------------------------------
# arithmetic

def exclusive_ns(intervals):
    """For each [start, end), the time during which it is the one that
    started last among those running: nested and overlapping operations
    share no time, and the sum is the measure of their union."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    out = [0] * len(intervals)
    running = []                     # indices, in order of start
    now = None

    def advance(to):
        nonlocal now
        while running and now < to:
            top = running[-1]
            end = intervals[top][1]
            if end <= now:
                running.pop()
                continue
            upto = min(end, to)
            out[top] += upto - now
            now = upto
        now = to

    for i in order:
        start = intervals[i][0]
        if now is None:
            now = start
        advance(start)
        running.append(i)
    if running:
        advance(max(end for _, end in intervals))
    return out


def device_by_scope(ops, modules, programs):
    """One device: the window trace_reduce takes (first to last execution of
    the longest-running program), its steps and busy time, and that busy time
    split by the scopes `programs` has for that program, None for operations
    that carry none; `kernels` counts the window's custom calls (the Pallas
    kernels: an event is named by its instruction's text, `%name = type
    custom-call(operands)`) by scope. `by_scope`, `held`, `unscoped_ops` and
    `kernels` are None where
    `programs` lacks that program or has no scope on any of its instructions:
    what is read then is not what this program's code staged."""
    reduced = trace_reduce.reduce_device(ops, modules)
    if reduced is None:
        return None
    lo, hi = reduced["lo"], reduced["hi"]
    runs = collections.defaultdict(list)
    for name, start, dur in modules:
        runs[name].append((start, dur))
    program = max(runs, key=lambda name: sum(d for _, d in runs[name]), default=None)
    starts = sorted(s for s, _ in runs.get(program, ()))
    out = {"steps": reduced["steps"], "busy_ns": reduced["busy_ns"], "program": program,
           "step_interval_ns": statistics.median(
               b - a for a, b in zip(starts, starts[1:])) if len(starts) > 1 else None,
           "by_scope": None, "held": None, "unscoped_ops": None, "kernels": None,
           "gaps": reduced["gaps"], "idle": 1.0 - reduced["busy_ns"] / (hi - lo)}
    scopes = programs.get(program, {})
    if not any(scope is not None for scope, *_ in scopes.values()):
        return out
    inside = [(n, s, d) for n, s, d in ops if s < hi and s + d > lo]
    shares = exclusive_ns(trace_reduce.clip(inside, lo, hi))
    by_scope, held, unscoped, kernels = (collections.defaultdict(int) for _ in range(4))
    for (name, _, _), ns in zip(inside, shares):
        scope, *others = scopes.get(instruction_of(name), (None,))
        by_scope[scope] += ns
        for holder in (scope, *others):
            held[holder] += ns
        if scope is None:
            unscoped[name] += ns
        elif " custom-call(" in name:
            kernels[scope] += 1
    return dict(out, by_scope=dict(by_scope), held=dict(held), unscoped_ops=dict(unscoped),
                kernels=dict(kernels))


def calls_with_launch(spans):
    """[(call, launch or None)] in order of start: each `to_static.call`
    with the `to_static.launch` on its line that it holds."""
    launches = [s for s in spans if s[0] == LAUNCH]
    out = []
    for call in sorted((s for s in spans if s[0] == CALL), key=lambda s: s[1]):
        held = [l for l in launches if l[4] == call[4] and call[1] <= l[1]
                and l[1] + l[2] <= call[1] + call[2]]
        out.append((call, held[0] if len(held) == 1 else None))
    return out


def label_gap(gap, spans):
    """The innermost program span that covers most of an idle gap."""
    best, key = "no program span", (0, 0)
    for name, start, dur, _, _ in spans:
        cover = min(gap[1], start + dur) - max(gap[0], start)
        if cover > 0 and (cover, -dur) > key:
            best, key = name, (cover, -dur)
    return best


def reduce(trace, expected=None):
    """Per step and in milliseconds: device busy time split by scope
    (`scope_ms`, sums to `busy_ms`), the time of the operations that hold
    each scope (`held_ms`) and the custom calls a step by scope
    (`scope_kernels`), all None where the trace has no scopes for the
    window's program; the launch and the Python round it (medians over
    the window's calls, beside the benchmark's own dispatch span over the
    same calls); counters per call from the spans' attributes; the ten
    longest idle gaps by program span. None where the trace holds no
    `to_static.call` (a program without the spans) or no device plane.
    `expected`, the run's own reduction (trace_reduce.reduce), has to agree
    on steps and window: another run's trace is refused."""
    base = trace_reduce.reduce(trace)
    if base is None:
        return None
    if expected is not None and (base["steps"], base["window_s"]) != (
            expected["steps"], expected["window_s"]):
        raise RuntimeError(
            f"the trace holds {base['steps']} steps in {base['window_s']} s, the "
            f"run's {expected['steps']} in {expected['window_s']} s: not this run's trace")
    calls = calls_with_launch(trace["spans"])
    if not calls:
        return None
    devices = [d for d in (device_by_scope(v["ops"], v["modules"], trace["programs"])
                           for v in trace["devices"].values()) if d]
    n, steps = len(devices), min(d["steps"] for d in devices)
    per_step_ms = 1e-6 / n / max(steps, 1)
    busy_ms = sum(d["busy_ns"] for d in devices) * per_step_ms
    scoped = all(d["by_scope"] is not None for d in devices)
    by_scope, held, unscoped, kernels = (collections.defaultdict(float) for _ in range(4))
    for d in devices if scoped else ():
        for scope, count in d["kernels"].items():
            kernels[scope] += count / n / max(steps, 1)
        for scope, ns in d["by_scope"].items():
            by_scope[UNSCOPED if scope is None else scope] += ns * per_step_ms
        for scope, ns in d["held"].items():
            if scope is not None:
                held[scope] += ns * per_step_ms
        for name, ns in d["unscoped_ops"].items():
            unscoped[trace_reduce.short(name)] += ns * per_step_ms
    worst = max(devices, key=lambda d: d["idle"])
    gaps = sorted(worst["gaps"], key=lambda g: g[0] - g[1])[:10]
    whole = [(c, l) for c, l in calls if l is not None]
    dispatches = [d for n, _, d in trace["host"] if n == "bench.dispatch"]
    first, last = calls[0][0][3], calls[-1][0][3]
    per_call = {k: (float(last[k]) - float(first[k])) / (len(calls) - 1)
                for k in first if k != "fn"} if len(calls) > 1 else {}
    return {
        "steps": steps, "calls": len(calls), "fn": first.get("fn"),
        "busy_ms": busy_ms,
        # between starts of successive executions, on the worst device: the
        # step time while the profiler is on
        "device_step_ms": worst["step_interval_ns"] / 1e6
        if worst["step_interval_ns"] else None,
        # None: the trace has no scopes for the window's program
        "scoped_program": worst["program"] if scoped else None,
        "scope_ms": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])) if scoped else None,
        "held_ms": dict(sorted(held.items(), key=lambda kv: -kv[1])) if scoped else None,
        # custom calls (Pallas kernels) a step by scope: how often the traced
        # program runs a kernel, a rematerialised forward among them
        "scope_kernels": dict(kernels) if scoped else None,
        "unscoped_pct": 100.0 * by_scope.get(UNSCOPED, 0.0) / busy_ms if scoped else None,
        "unscoped_ops": sorted(unscoped.items(), key=lambda kv: -kv[1])[:8],
        "launch_ms": statistics.median(l[2] for _, l in whole) / 1e6 if whole else None,
        "python_ms": statistics.median(c[2] - l[2] for c, l in whole) / 1e6
        if whole else None,
        "bench_dispatch_ms": statistics.median(dispatches) / 1e6 if dispatches else None,
        "per_call": per_call,
        "idle_gaps": [[label_gap(g, trace["spans"]), (g[1] - g[0]) / 1e9] for g in gaps],
    }


def scope_ms(m, names, key="scope_ms"):
    """For a reader: device milliseconds per step of the operations whose
    scope is one of `names` (or, with `key="held_ms"`, that hold one), in the
    traced run behind `m`; None where `of(m)` is, or has no scopes."""
    reduced = of(m)
    if reduced is None or reduced[key] is None:
        return None
    return sum(reduced[key].get(name, 0.0) for name in names)


# ---------------------------------------------------------------------------
# the run's own trace

def newest_trace(m):
    """The trace the run behind `m` wrote: the newest `.xplane.pb` under its
    own `trace_dir` (`.bench_trace/<cell>/`, emptied when the run starts its
    trace), or None."""
    paths = glob.glob(os.path.join(m["trace_dir"], "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def of(m):
    """`reduce` of the traced run behind `m` (what a reader is handed), made
    once and kept in `m`; None for an untraced run, a run without a device
    trace, or a program without the spans."""
    if "program_trace" not in m:
        m["program_trace"] = None
        path = newest_trace(m) if m["run"]["trace"] else None
        if path:
            reduced = reduce(load(path), expected=m["run"]["trace"])
            if reduced:
                print(json.dumps({"phase": "program_trace", **reduced}), flush=True)
            m["program_trace"] = reduced
    return m["program_trace"]


def kernels_a_layer(m, names, layers):
    """For a reader: the custom calls a step under the scopes `names` in the
    traced run behind `m`, a layer of `layers`; None where `of(m)` is, or has
    no scopes, or the program stages no kernel there."""
    reduced = of(m)
    if reduced is None or not reduced.get("scope_kernels") or not layers:
        return None
    calls = sum(reduced["scope_kernels"].get(name, 0.0) for name in names)
    return calls / layers if calls else None


def counter(name):
    """A counter of the program's registry; None where the program has none
    of that name."""
    from paddle_tpu.profiler import metrics
    return metrics.get_registry().snapshot()["counters"].get(name)


if __name__ == "__main__":
    print(json.dumps(reduce(load(sys.argv[1])), indent=1))
