"""benchmarks/setup_trace.py and the nine readers built on it, over a
hand-made timeline and registry: no program runs here."""
import json

import pytest

from benchmarks import harness, setup_trace


def record(name, start, end, parent=None, requests=0, misses=0, missed=(), **attrs):
    return {"name": name, "start": start, "end": end, "parent": parent,
            "attrs": attrs, "requests": requests, "misses": misses,
            "missed_programs": [list(m) for m in missed]}


# a run that found everything but the step's two programs in the cache
TIMELINE = [
    record("eager", 9.0, None, requests=40),
    record("user", 9.0, None, requests=6, misses=1, missed=[("jit(block_grad)", 30.0)]),
    record("runtime.import", 10.0, 12.5),
    record("to_static.discover", 20.0, 39.0, fn="train_step", ops=525),
    record("autotune.search", 25.0, 27.0, parent=3, op="flash", requests=4),
    record("to_static.probe", 39.0, 40.0, fn="train_step"),
    record("to_static.probe", 40.0, 40.5, fn="train_step"),
    record("to_static.compile", 40.5, 100.5, fn="train_step", program="plain",
           requests=1, misses=1, missed=[("jit(pure_fn)", 55.0)]),
    record("to_static.compile", 101.0, 171.0, fn="train_step", program="donating",
           requests=1, misses=1, missed=[("jit(pure_fn)", 66.0)]),
    record("to_static.compile", 300.0, None, fn="other", program="plain"),   # still open
]
TIMELINE[3]["slowest_ops"] = [["flash_attention", 4, 2.0, 8, 0], ["grad(kda)", 3, 1.5, 30, 0],
                              ["linear", 40, 0.5, 12, 0]]
COUNTERS = {
    "runtime.import_sec": 2.5,
    "to_static.discover_sec": 19.0,
    "to_static.probe_sec": 1.5,
    "to_static.compile_sec": 130.0,
    "autotune.search_sec": 2.0,
    "autotune.searches_total": 1.0,
    "autotune.disk_hits_total": 11.0,
    "to_static.backend_compile_sec": 121.0,
    'compile.requests_total{phase="eager"}': 40.0,
    'compile.backend_sec{phase="eager"}': 4.0,
    'compile.cache_hits_total{phase="eager"}': 40.0,
    'compile.cache_load_sec{phase="eager"}': 3.0,
    'compile.requests_total{phase="user"}': 6.0,
    'compile.backend_sec{phase="user"}': 31.0,
    'compile.cache_misses_total{phase="user"}': 1.0,
    'compile.requests_total{phase="discover"}': 800.0,
    'compile.backend_sec{phase="discover"}': 9.0,
    'compile.requests_total{phase="autotune"}': 4.0,
    'compile.requests_total{phase="compile"}': 2.0,
    'compile.backend_sec{phase="compile"}': 121.0,
    'compile.cache_misses_total{phase="compile"}': 2.0,
    'steptime.rank_ms{rank="0"}': 7.0,         # another label: not a phase
}
SETUP_S = 177.0
EXPECTED = {
    "import_s": 2.5,
    "discover_s": 19.0,
    "step_build_s": 131.5,
    "eager_compile_load_s": 4.0,
    "setup_compile_requests": 852.0,
    "setup_cache_misses": 3.0,
    "step_cache_misses": 2.0,
    "autotune_search_s": 2.0,
    "setup_unattributed_s": 177.0 - (2.5 + 19.0 + 131.5 + 4.0),
}


@pytest.fixture
def measured(monkeypatch):
    monkeypatch.setattr(setup_trace, "load", lambda: (TIMELINE, COUNTERS))
    return {"run": {"setup_s": SETUP_S}}


def test_the_manifest_names_the_nine_for_every_cell():
    entries = {m["name"]: m for m in harness.manifest()["per_layer"]}
    for name in EXPECTED:
        assert entries[name] == {
            "name": name, "unit": "count" if name.endswith(("requests", "misses")) else "s",
            "better": "lower", "source": "program_counter",
            "layer": "compile path", "moves": "setup_s"}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_over_a_hand_made_run(measured, name):
    assert harness.load_reader("layer_metrics", name)(measured) \
        == pytest.approx(EXPECTED[name], abs=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_none_where_the_program_keeps_no_timeline(monkeypatch, name):
    monkeypatch.setattr(setup_trace, "load", lambda: None)
    m = {"run": {"setup_s": SETUP_S}}
    assert harness.load_reader("layer_metrics", name)(m) is None
    assert m["setup_trace"] is None


def test_load_finds_no_timeline_in_a_program_without_one(monkeypatch):
    from paddle_tpu import profiler
    monkeypatch.delattr(profiler, "setup_timeline")
    assert setup_trace.load() is None


def test_the_four_parts_and_the_rest_add_up_to_setup_s(measured):
    reduced = setup_trace.of(measured)
    parts = ("import_s", "discover_s", "step_build_s", "eager_compile_load_s",
             "setup_unattributed_s")
    assert sum(reduced[p] for p in parts) == pytest.approx(SETUP_S, abs=1e-9)


def test_a_run_without_a_phase_reads_zero_not_none(monkeypatch):
    monkeypatch.setattr(setup_trace, "load", lambda: (TIMELINE[:3], {"runtime.import_sec": 2.5}))
    reduced = setup_trace.of({"run": {"setup_s": 10.0}})
    assert reduced["discover_s"] == reduced["autotune_search_s"] == 0.0
    assert reduced["step_cache_misses"] == reduced["setup_compile_requests"] == 0
    assert reduced["setup_unattributed_s"] == 7.5


def test_phases_have_counts_seconds_and_self_time():
    phases = setup_trace.reduce(TIMELINE, COUNTERS, SETUP_S)["phases_s"]
    assert phases["to_static.discover"] == {"count": 1, "seconds": 19.0, "self_s": 17.0}
    assert phases["autotune.search"] == {"count": 1, "seconds": 2.0, "self_s": 2.0}
    assert phases["to_static.probe"] == {"count": 2, "seconds": 1.5, "self_s": 1.5}
    assert phases["to_static.compile"] == {"count": 2, "seconds": 130.0, "self_s": 130.0}
    assert "eager" not in phases and "user" not in phases     # they never close


def test_compile_requests_by_phase_and_the_missed_programs_longest_first():
    reduced = setup_trace.reduce(TIMELINE, COUNTERS, SETUP_S)
    assert reduced["compile"]["compile"] == {
        "requests": 2.0, "hits": 0.0, "misses": 2.0, "backend_s": 121.0, "load_s": 0.0}
    assert reduced["compile"]["eager"]["load_s"] == 3.0
    assert sorted(reduced["compile"]) == ["autotune", "compile", "discover", "eager", "user"]
    assert reduced["missed_programs"] == [
        ["to_static.compile{donating}", "jit(pure_fn)", 66.0],
        ["to_static.compile{plain}", "jit(pure_fn)", 55.0],
        ["user", "jit(block_grad)", 30.0]]


def test_discovery_ops_the_autotuner_and_the_top_level_records():
    reduced = setup_trace.reduce(TIMELINE, COUNTERS, SETUP_S)
    assert reduced["slowest_discover_ops"][0] == ["flash_attention", 4, 2.0, 8, 0]
    assert [row[0] for row in reduced["slowest_discover_ops"]] \
        == ["flash_attention", "grad(kda)", "linear"]
    assert reduced["autotune"] == {"searches": 1.0, "disk_hits": 11.0, "mem_hits": 0.0,
                                   "fallbacks": 0.0, "candidate_failures": 0.0,
                                   "cache_errors": 0.0}
    assert reduced["top_level"][:2] == [["runtime.import", 0.0, 2.5],
                                        ["to_static.discover", 10.0, 19.0]]
    assert "autotune.search" not in [row[0] for row in reduced["top_level"]]
    assert (reduced["records"], reduced["dropped"]) == (len(TIMELINE), 0.0)


def test_the_line_is_printed_once_a_run(measured, capsys):
    for name in EXPECTED:
        harness.load_reader("layer_metrics", name)(measured)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [line["phase"] for line in lines] == ["setup_trace"]
    assert lines[0]["step_cache_misses"] == 2.0 and lines[0]["reduce_s"] >= 0
    for key in ("phases_s", "compile", "missed_programs", "slowest_discover_ops",
                "autotune", "setup_unattributed_s", "records", "dropped"):
        assert key in lines[0]
