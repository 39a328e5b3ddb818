"""From the profiler's `.xplane.pb` to device busy and idle time, time per
operation, exposed collective time, and the longest idle gaps with what the
host was doing in them.

`load` reads the planes with `jax.profiler.ProfileData` into plain lists;
`reduce` is arithmetic on those lists, so it can be checked on a small
recorded trace (tests/benchmark/test_trace_reduce.py). All times are in
nanoseconds until `reduce` returns seconds.

Run by hand to look at a trace:
    python3 benchmarks/trace_reduce.py <file.xplane.pb>
"""
import collections
import json
import sys

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def load(path_or_profile):
    """{"devices": {plane: {"ops": [(name, start, dur)], "modules": [...]}},
    "host": [(name, start, dur)]}: device operations and whole-program
    executions per TPU plane, and the benchmark's own host spans."""
    from jax.profiler import ProfileData
    profile = path_or_profile
    if isinstance(profile, str):
        profile = ProfileData.from_file(profile)
    devices, host = {}, []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {line.name: line for line in plane.lines}
            devices[plane.name] = {
                key: [(e.name, e.start_ns, e.duration_ns)
                      for e in lines[name].events] if name in lines else []
                for key, name in (("ops", OPS_LINE), ("modules", MODULES_LINE))}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events if e.name.startswith(HOST_SPANS)]
    return {"devices": devices, "host": host}


def union(intervals):
    """Sorted, disjoint [start, end) covering the same points."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def total(merged):
    return sum(end - start for start, end in merged)


def intersect(a, b):
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        start, end = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if start < end:
            out.append([start, end])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip(events, lo, hi):
    return [(max(s, lo), min(s + d, hi)) for _, s, d in events
            if s < hi and s + d > lo]


def short(name):
    """An HLO instruction's name and result type, without its operands."""
    head, _, rest = name.partition(" = ")
    return (head.lstrip("%") + " " + rest.split(") ")[0].split(" fusion(")[0])[:120].strip()


def is_collective(name):
    return name.lstrip("%").startswith(COLLECTIVES)


def reduce_device(ops, modules):
    """One device's window (first to last execution of its longest-running
    program), busy union, per-op sums, exposed collective time and gaps."""
    by_module = collections.defaultdict(list)
    for name, start, dur in modules:
        by_module[name].append((start, start + dur))
    if by_module:
        runs = max(by_module.values(), key=lambda r: sum(e - s for s, e in r))
        lo, hi, steps = min(s for s, _ in runs), max(e for _, e in runs), len(runs)
    elif ops:
        lo = min(s for _, s, _ in ops)
        hi = max(s + d for _, s, d in ops)
        steps = 0
    else:
        return None
    inside = [(n, s, d) for n, s, d in ops if s < hi and s + d > lo]
    busy = union(clip(inside, lo, hi))
    coll = union(clip([e for e in inside if is_collective(e[0])], lo, hi))
    rest = union(clip([e for e in inside if not is_collective(e[0])], lo, hi))
    op_ns = collections.defaultdict(int)
    for name, start, dur in inside:
        op_ns[name] += min(start + dur, hi) - max(start, lo)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return {"lo": lo, "hi": hi, "steps": steps, "busy_ns": total(busy),
            "exposed_collective_ns": total(coll) - total(intersect(coll, rest)),
            "op_ns": dict(op_ns), "gaps": gaps}


def label_gap(gap, host):
    """The benchmark's host span that covers most of an idle gap."""
    best, cover = "no bench span", 0
    for name, start, dur in host:
        c = min(gap[1], start + dur) - max(gap[0], start)
        if c > cover:
            best, cover = name, c
    return best


def reduce(trace):
    """Seconds and shares over the traced window; None where the trace holds
    no device plane (a CPU run)."""
    per_device = {name: reduce_device(d["ops"], d["modules"])
                  for name, d in trace["devices"].items()}
    per_device = {k: v for k, v in per_device.items() if v}
    if not per_device:
        return None
    n = len(per_device)
    worst = max(per_device.values(),
                key=lambda d: 1.0 - d["busy_ns"] / (d["hi"] - d["lo"]))
    op_ns = collections.defaultdict(int)
    for d in per_device.values():
        for name, ns in d["op_ns"].items():
            op_ns[name] += ns
    gaps = sorted(worst["gaps"], key=lambda g: g[0] - g[1])[:10]
    return {
        "devices": n,
        "steps": min(d["steps"] for d in per_device.values()),
        "window_s": sum(d["hi"] - d["lo"] for d in per_device.values()) / n / 1e9,
        "busy_s": sum(d["busy_ns"] for d in per_device.values()) / n / 1e9,
        "idle_pct_worst": 100.0 * (1.0 - worst["busy_ns"]
                                   / (worst["hi"] - worst["lo"])),
        "exposed_collective_s": max(d["exposed_collective_ns"]
                                    for d in per_device.values()) / 1e9,
        "device_ops": [[short(name), ns / n / 1e9] for name, ns in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[label_gap(g, trace["host"]), (g[1] - g[0]) / 1e9]
                      for g in gaps],
    }


if __name__ == "__main__":
    loaded = load(sys.argv[1])
    reduced = reduce(loaded)
    if reduced:
        reduced["device_ops"] = reduced["device_ops"][:25]
    print(json.dumps(reduced, indent=1))
