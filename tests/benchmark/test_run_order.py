"""The order of a run (benchmarks/run.py::run_cell): the program first, the
yardstick after. The entry trains; the peak is read, which is the program's
because nothing else has run; the program's state is given back; only then
the plain reference follows the compared steps, and the comparison decides
`correct`. With a stub entry (what an entry returns, and nothing of
paddle_tpu) and with the tiny GPT cell of test_rehearsal.py end to end."""
import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from benchmarks import harness, program, run  # noqa: E402
from test_rehearsal import CELLS, tiny_cell  # noqa: E402

NUMBERS = {"losses": [6.0, 5.5, 5.0], "grad_norms": {"w": 1.0, "b": 0.5},
           "grad_vectors": {}, "update_norms": {"w": 0.1, "b": 0.1}}


def phases(printed):
    return [json.loads(line) for line in printed.splitlines() if line.startswith("{")]


def stub_cell(log, keep=None):
    """The tiny GPT cell with an entry that trains nothing: it returns the
    reference's own numbers as the program's. `keep` is what it leaves alive
    on the device when it returns."""
    cell = tiny_cell(CELLS[0])

    def entry_run(ctx):
        log.append("entry")
        assert "reference_s" not in ctx          # no reference runs before the window
        if keep is not None:
            keep.append(ctx["make_weights"]())
        return {"program": dict(NUMBERS, grad_vectors={"b": np.ones(3, np.float32)}),
                "losses": [4.0] * 40, "compiles_in_window": 0, "trace": None,
                "setup_s": 1.0}

    cell["entry"] = types.SimpleNamespace(run=entry_run)
    return cell


@pytest.fixture
def logged(monkeypatch):
    """run_cell's steps as they happen; the two peak readings are 100 (the
    program's) and 900 (after a reference nine times as large)."""
    log = []
    readings = {"peak_bytes_in_use": iter([100, 900])}

    def memory_reading(devices, key):
        if key != "peak_bytes_in_use":
            return 0
        log.append("peak")
        return next(readings[key])

    def reference_numbers(*args, **kwargs):
        log.append("reference")
        return dict(NUMBERS, grad_vectors={"b": np.ones(3, np.float32)})

    compare = harness.compare
    monkeypatch.setattr(harness, "memory_reading", memory_reading)
    monkeypatch.setattr(harness, "reference_numbers", reference_numbers)
    monkeypatch.setattr(harness, "compare",
                        lambda *a: log.append("compare") or compare(*a))
    release = program.release
    monkeypatch.setattr(program, "release", lambda: log.append("release") or release())
    return log


def test_the_program_first_the_yardstick_after(logged, capsys):
    result = run.run_cell(stub_cell(logged), seed=3, seconds=0.1, trace=0, need_tpu=False)
    assert logged == ["entry", "peak", "release", "reference", "peak", "compare"]
    assert result["correct"], result["checks"]
    # the peak is the reading taken before the reference, whatever it needed
    assert result["device"]["memory_peak_bytes"] == 100
    (memory,) = [p for p in phases(capsys.readouterr().out) if p["phase"] == "memory"]
    assert memory["peak_bytes_in_use"] == 100 and memory["peak_after_reference"] == 900
    assert {"bytes_in_use_after_window", "bytes_in_use_before_reference",
            "live_arrays_before_reference"} <= set(memory)


def test_a_larger_reference_is_no_part_of_correct(logged):
    # the reference's peak is nine times the program's and the run is correct:
    # no row compares the two, and the result comes last with its checks last
    result = run.run_cell(stub_cell(logged), seed=3, seconds=0.1, trace=0, need_tpu=False)
    names = [row["name"] for row in result["checks"]]
    assert "reference_peak_over_peak" not in names
    assert names == ["first_loss_gap", "later_loss_gap", "grad_norm_gap",
                     "grad_vector_error", "update_norm_gap",
                     "steps_off_the_window_program", "steps_not_finite",
                     "loss_last32_over_first", "compiles_in_window"]
    assert list(result)[-1] == "checks" and result["correct"]


def test_a_run_that_still_holds_its_state_stops_with_the_message(logged, monkeypatch, capsys):
    # what the entry leaves alive is over the line: the reference would run
    # beside it, so the run ends there, with no result and no comparison
    import jax.numpy as jnp
    monkeypatch.setattr(run, "HELD_BEFORE_REFERENCE", 4096)
    # what was alive before the run began (another test's, in a shared
    # process) is not the run's to give back
    ballast = jnp.ones((4096,), jnp.float32)
    kept = []
    with pytest.raises(SystemExit) as stop:
        run.run_cell(stub_cell(logged, keep=kept), seed=3, seconds=0.1, trace=0,
                     need_tpu=False)
    assert "still held" in str(stop.value.code) and stop.value.code != 0
    assert logged == ["entry", "peak", "release"]
    assert not [p for p in phases(capsys.readouterr().out)
                if p["phase"] in ("reference", "check", "memory")]
    # let go of, the same run goes through
    kept.clear()
    del logged[:]
    monkeypatch.setattr(harness, "memory_reading", lambda devices, key: 0)
    assert run.run_cell(stub_cell(logged), seed=3, seconds=0.1, trace=0,
                        need_tpu=False)["correct"]
    assert ballast.nbytes > run.HELD_BEFORE_REFERENCE


def test_held_counts_what_is_alive():
    import jax
    import jax.numpy as jnp
    before = harness.held(jax.devices()[:1])
    block = jnp.ones((1024, 1024), jnp.float32)
    with_block = harness.held(jax.devices()[:1])
    assert with_block["live_arrays"] == before["live_arrays"] + 1
    assert with_block["bytes_in_use"] == before["bytes_in_use"] + block.nbytes
    del block
    assert harness.held(jax.devices()[:1]) == before
    assert harness.memory_reading(jax.devices()[:1], "peak_bytes_in_use") == 0   # a CPU


def test_release_gives_the_programs_state_back(capsys):
    # the real entry and the real program: after the window the model, its
    # masters and moments are alive; when the reference starts they are gone
    import jax
    cell = tiny_cell(CELLS[0])
    result = run.run_cell(cell, seed=5, seconds=0.2, trace=0, need_tpu=False)
    assert result["correct"], result["checks"]
    (memory,) = [p for p in phases(capsys.readouterr().out) if p["phase"] == "memory"]
    shapes = cell["family"].reference.param_shapes(cell["cfg"])
    parameters = sum(int(np.prod(s)) for s, _ in shapes.values())
    # bf16 weights, float32 masters and two moments: 14 bytes a parameter
    freed = memory["bytes_in_use_after_window"] - memory["bytes_in_use_before_reference"]
    assert freed >= 14 * parameters
    assert memory["live_arrays_before_reference"] <= len(jax.live_arrays()) + 1
    from paddle_tpu.jit import ast_transform
    assert not ast_transform._CACHE


def test_the_result_prints_each_number_beside_its_limit_on_standard_error(monkeypatch, capsys):
    cell = stub_cell([])
    monkeypatch.setattr(run, "fix_caches", lambda workload: None)
    monkeypatch.setattr(harness, "load_cell", lambda workload: cell)
    monkeypatch.setattr(run, "find_device", lambda chips, need_tpu=True: (
        {"platform": "cpu", "kind": "cpu", "count": 1}, None))
    monkeypatch.setattr(harness, "reference_numbers", lambda *a, **k: dict(
        NUMBERS, grad_vectors={"b": np.ones(3, np.float32)}))
    run.main(["--workload", CELLS[0], "--seed", "3", "--seconds", "0.1"])
    out, err = capsys.readouterr()
    result = json.loads(out.splitlines()[-1])
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    lines = err.strip().splitlines()[-len(result["checks"]):]
    assert [l.split()[0] for l in lines] == [row["name"] for row in result["checks"]]
    assert all(" limit " in l for l in lines)
