"""Each entry end to end on the CPU at a tiny size, the comparison that
decides `correct`, and the two ways it has to fail.

The run goes through benchmarks/run.py::run_cell with the look for a chip
skipped: it prints and returns counts and comparisons only, and no metric
(no peaks are known for a CPU, so no reader is called).
"""
import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import control, harness, run  # noqa: E402

TINY = dict(hidden_size=64, num_heads=2, intermediate_size=128, vocab_size=600,
            max_position_embeddings=32, num_layers=2)
# at this size and seed, read on the CPU: the bf16 program's gradient error
# over the biases and LayerNorm gains is 0.011 (gpt) and 0.0016 (bert), the
# float8 control's 0.031 and 0.0067; the first loss differs by 1e-4 at most
SEED = 5
TINY_LIMITS = {"first_loss_gap": 3e-4, "later_loss_gap": 0.5, "grad_norm_gap": 0.03,
               "update_norm_gap": 0.75, "loss_last32_over_first": 1.0}
VECTOR_LIMIT = {"gpt": 0.02, "bert": 0.004}
CELLS = ["gpt3-1p3b.pretrain-1chip-b2-s1024", "bert-base.finetune-fit-b64-s128"]
# no cell yet: the gpt configuration under the dp2 x mp2 job, on 4 virtual devices
HYBRID = "rehearsal.pretrain-dp2mp2-s1024"


def tiny_cell(name):
    cell = harness.load_cell(CELLS[0] if name == HYBRID else name)
    if name == HYBRID:
        cell["job"] = harness.read_json("jobs", "pretrain-dp2mp2-s1024.json")
        cell["entry"] = importlib.import_module("benchmarks.entries.fleet_hybrid")
        cell["cell"] = dict(cell["cell"], name=name, chips=cell["job"]["chips"])
    cell["cfg"].update(TINY)
    cell["job"].update(batch=4, seq=32 if cell["cfg"]["family"] == "gpt" else 16,
                       reference_rows_per_block=2, trace_steps=4)
    cell["limits"] = dict(TINY_LIMITS,
                          grad_vector_error=VECTOR_LIMIT[cell["cfg"]["family"]])
    return cell


def by_name(rows):
    return {r["name"]: r for r in rows}


@pytest.mark.parametrize("name", CELLS + [HYBRID])
def test_entry_runs_and_agrees_with_the_reference(name, capsys, monkeypatch):
    import jax
    cell = tiny_cell(name)
    devices = jax.devices()[:cell["cell"]["chips"]]   # fleet meshes all it finds
    monkeypatch.setattr(jax, "devices", lambda *a: devices)
    result = run.run_cell(cell, seed=SEED, seconds=0.5, trace=1, need_tpu=False)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 8
    assert result["metrics"] == {}            # counts only on a CPU
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == cell["cell"]["chips"]
    printed = capsys.readouterr().out
    for row in result["checks"]:
        assert f'"name": "{row["name"]}"' in printed   # each number beside its limit
    assert by_name(result["checks"])["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("name", CELLS[:2])      # one per family
def test_lower_precision_control_is_not_correct(name):
    rows = by_name(control.control_checks(tiny_cell(name), seed=SEED))
    assert not rows["grad_vector_error"]["ok"], rows


def test_step_that_leaves_its_state_unchanged_is_not_correct(monkeypatch):
    import paddle_tpu as paddle
    monkeypatch.setattr(paddle.optimizer.AdamW, "step", lambda self: None)
    result = run.run_cell(tiny_cell(CELLS[0]), seed=3, seconds=0.3, trace=0,
                          need_tpu=False)
    assert not result["correct"]
    assert not by_name(result["checks"])["update_norm_gap"]["ok"]


def test_compared_step_through_another_program_is_not_correct(monkeypatch):
    # the plain program in the donating one's place: the same numbers from
    # a program that the window does not drive
    to_static = importlib.import_module("paddle_tpu.jit.to_static")
    monkeypatch.setattr(to_static, "_donation_paused", [True])
    result = run.run_cell(tiny_cell(CELLS[0]), seed=6, seconds=0.3, trace=0,
                          need_tpu=False)
    assert not result["correct"]
    rows = by_name(result["checks"])
    assert rows["steps_off_the_window_program"]["value"] == harness.CHECK_STEPS
    assert rows["grad_vector_error"]["ok"] and rows["update_norm_gap"]["ok"]


@pytest.mark.parametrize("name", CELLS)
def test_program_read_over_several_seeds_in_one_process(name):
    # what benchmarks/control.py --program-seeds reads a limit from: one
    # round of compared steps per seed, each from its own seeded state, and
    # each giving what a run of that seed alone gives
    seeds = [8, SEED]
    first, last = control.program_checks(tiny_cell(name), seeds, need_tpu=False)
    alone = run.run_cell(tiny_cell(name), seed=SEED, seconds=0.3, trace=0,
                         need_tpu=False)
    assert all(r["ok"] for r in last), last
    for row in last:
        assert row["value"] == by_name(alone["checks"])[row["name"]]["value"]
    assert by_name(first)["grad_vector_error"]["value"] != \
        by_name(last)["grad_vector_error"]["value"]


def test_part_of_the_batch_left_out_is_not_correct(monkeypatch):
    cell = tiny_cell(CELLS[0])
    whole = cell["family"].loss_of
    monkeypatch.setattr(cell["family"], "loss_of",
                        lambda model, x, y: whole(model, x[:2], y[:2]))
    result = run.run_cell(cell, seed=4, seconds=0.3, trace=0, need_tpu=False)
    assert not result["correct"]
    assert not by_name(result["checks"])["first_loss_gap"]["ok"]


def test_seeds_beyond_32_bits():
    big = 2 ** 31 + 2 ** 20 + 11
    cell = tiny_cell(CELLS[0])
    x, y = cell["family"].Stream(cell["cfg"], cell["job"], big).next()
    assert x.shape == (4, 32) and (x[:, 1:] == y[:, :-1]).all()
    a = harness.init_params({"w": ((3, 5), 0.02)}, big, "float32")["w"]
    b = harness.init_params({"w": ((3, 5), 0.02)}, big - 2 ** 31, "float32")["w"]
    assert not (a == b).all()


def test_no_chip_means_no_run(capsys):
    with pytest.raises(SystemExit) as stop:
        run.find_device(chips=1)            # jax is held to the CPU here
    assert stop.value.code not in (0, None)
    assert capsys.readouterr().out == ""
