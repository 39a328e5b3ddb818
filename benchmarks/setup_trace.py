"""What the program's own set-up records say of a run: seconds by phase,
compile requests by phase, the programs a cache miss compiled, the discovery
pass by op, the autotuner's searches, and what of `setup_s` is in none of
them.

The program keeps a timeline of set-up spans (`paddle_tpu.profiler
.setup_timeline()`: `runtime.import`, `to_static.discover`, `.probe`,
`.compile`, `autotune.search`, each with its parent) and counts every compile
request under a `phase` label in its registry (`compile.*`; `eager` is the
package's own code outside any span, `user` the caller's: here the plain
reference where the family module had imported the package before it, the
seeded weights, `program.reset`, the norms of the compared steps). `load`
reads both, `reduce` is arithmetic on them
(tests/benchmark/test_setup_trace.py), `of(measured)` does both once a run,
after the traced steps, prints the `setup_trace` phase line and hands the
nine `layer_metrics` readers their numbers. Read that late, the counts hold
what came after the window too: the traced steps ask for no compile, the one
`lower().compile()` of `entries/to_static_loop.compiled_text` is one request
under `user`.

A program without the timeline (the parent of the PR that added it) reduces
to None, and every reader built on this returns None for it.
"""
import collections
import json
import re
import sys
import time

_SERIES = re.compile(r'^(?P<name>[^{]+)(\{phase="(?P<phase>[^"]*)"\})?$')
_BY_PHASE = {"compile.requests_total": "requests",
             "compile.cache_hits_total": "hits",
             "compile.cache_misses_total": "misses",
             "compile.backend_sec": "backend_s",
             "compile.cache_load_sec": "load_s"}
_AUTOTUNE = ("searches", "disk_hits", "mem_hits", "fallbacks",
             "candidate_failures", "cache_errors")
KEPT = 16   # rows of `missed_programs` and `slowest_discover_ops` on the line


def load():
    """(the program's set-up timeline, its registry's counters), or None
    where the program keeps no timeline."""
    from paddle_tpu import profiler
    timeline = getattr(profiler, "setup_timeline", None)
    if timeline is None:
        return None
    return timeline(), profiler.metrics.get_registry().snapshot()["counters"]


def label(record):
    """`to_static.compile{donating}` for a record with a `program`."""
    program = record["attrs"].get("program")
    return record["name"] + (f"{{{program}}}" if program else "")


def reduce(timeline, counters, setup_s):
    """The `setup_trace` line's fields from a timeline (a list of records as
    `setup_timeline()` gives them), the registry's counters ({series: value})
    and the run's `setup_s`."""
    closed = [r for r in timeline if r["end"] is not None]
    inside = collections.defaultdict(float)     # index: its children's seconds
    for r in closed:
        if r["parent"] is not None:
            inside[r["parent"]] += r["end"] - r["start"]
    phases = {}
    for i, r in enumerate(timeline):
        if r["end"] is None:
            continue
        row = phases.setdefault(r["name"], {"count": 0, "seconds": 0.0, "self_s": 0.0})
        row["count"] += 1
        row["seconds"] += r["end"] - r["start"]
        row["self_s"] += r["end"] - r["start"] - inside[i]

    compiles = collections.defaultdict(lambda: dict.fromkeys(_BY_PHASE.values(), 0.0))
    plain = {}
    for series, value in counters.items():
        match = _SERIES.match(series)
        if match is None:       # labelled otherwise than by phase
            continue
        name, phase = match.group("name", "phase")
        if name in _BY_PHASE and phase is not None:
            compiles[phase][_BY_PHASE[name]] = value
        elif phase is None:
            plain[name] = value

    missed = sorted(([label(r), name, seconds] for r in timeline
                     for name, seconds in r["missed_programs"]),
                    key=lambda row: -row[2])
    ops = collections.defaultdict(lambda: [0, 0.0, 0, 0])
    for r in timeline:
        for name, *row in r.get("slowest_ops", ()):
            ops[name] = [a + b for a, b in zip(ops[name], row)]
    origin = min((r["start"] for r in closed), default=0.0)

    def seconds(name):
        return plain.get(name, 0.0)

    def over_phases(field):
        return sum(row[field] for row in compiles.values())

    out = {
        "import_s": seconds("runtime.import_sec"),
        "discover_s": seconds("to_static.discover_sec"),
        "step_build_s": seconds("to_static.probe_sec") + seconds("to_static.compile_sec"),
        "eager_compile_load_s": compiles["eager"]["backend_s"],
        "setup_compile_requests": over_phases("requests"),
        "setup_cache_misses": over_phases("misses"),
        "step_cache_misses": compiles["compile"]["misses"],
        "autotune_search_s": seconds("autotune.search_sec"),
    }
    out["setup_unattributed_s"] = setup_s - (
        out["import_s"] + out["discover_s"] + out["step_build_s"]
        + out["eager_compile_load_s"])
    out.update({
        "phases_s": phases,
        "compile": {phase: dict(row) for phase, row in sorted(compiles.items())},
        "missed_programs": missed[:KEPT],
        "slowest_discover_ops": sorted(
            ([name, *row] for name, row in ops.items()), key=lambda row: -row[2])[:KEPT],
        "autotune": {name: seconds(f"autotune.{name}_total") for name in _AUTOTUNE},
        # the records no other holds, from the first one's start: what lies
        # between them is the program's Python outside any phase, or the caller's
        "top_level": [[label(r), r["start"] - origin, r["end"] - r["start"]]
                      for r in closed if r["parent"] is None],
        "origin": origin,
        "records": len(timeline),
        "dropped": seconds("runtime.setup_records_dropped_total"),
    })
    return out


def of(m):
    """`reduce` of the run behind `m` (what a reader is handed), made once
    and kept in `m`; None for a program without the timeline."""
    if "setup_trace" not in m:
        start = time.perf_counter()
        loaded = load()
        m["setup_trace"] = None
        if loaded is not None:
            reduced = reduce(*loaded, m["run"]["setup_s"])
            # the first record's start after run.py's first line (the plain
            # reference's seconds are in between where it ran before the import)
            t_process = getattr(sys.modules["__main__"], "T_PROCESS", None)
            origin = reduced.pop("origin")
            reduced["origin_s"] = None if t_process is None else origin - t_process
            reduced["reduce_s"] = time.perf_counter() - start
            print(json.dumps({"phase": "setup_trace", **reduced}), flush=True)
            m["setup_trace"] = reduced
    return m["setup_trace"]


def metric(m, name):
    """For a reader: the field `name` of `of(m)`, or None where that is."""
    reduced = of(m)
    return None if reduced is None else reduced[name]
