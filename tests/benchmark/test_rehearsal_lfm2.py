"""The LFM2 cell end to end on the CPU at a tiny size: the entry, the
comparison that decides `correct`, and the lower-precision control, as
tests/benchmark/test_rehearsal.py does for the other cells. Widths are cut
here and nowhere else; the routing keeps 16 experts of which 4 are held."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import control, harness, run  # noqa: E402

CELL = "lfm2-24b-a2b.pretrain-1chip-b2-s4096"
SEED = 5
# read on the CPU at this size and seed: the bf16 program's gradient error
# over the RMS gains is 0.0149 and the float8 control's 0.0693; the worst
# leaf's gradient norm gap 0.0104 and 0.0619; the first loss differs by 1.5e-7
TINY_LIMITS = {"first_loss_gap": 3e-4, "later_loss_gap": 0.5, "grad_norm_gap": 0.03,
               "grad_vector_error": 0.03, "update_norm_gap": 0.75,
               "loss_last32_over_first": 1.0}


def tiny_cell(recompute=True):
    cell = harness.load_cell(CELL)
    cell["cfg"].update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                       intermediate_size=96, moe_intermediate_size=32,
                       vocab_size=600, num_experts=4, held_experts=[0, 1, 2, 3],
                       recompute=recompute)
    cell["cfg"]["published"] = dict(cell["cfg"]["published"], num_experts=16)
    cell["job"].update(batch=2, seq=128, reference_rows_per_block=1, trace_steps=4)
    cell["limits"] = dict(TINY_LIMITS)
    return cell


def by_name(rows):
    return {r["name"]: r for r in rows}


@pytest.mark.parametrize("recompute", [True, False], ids=["remat", "plain"])
def test_entry_runs_and_agrees_with_the_reference(recompute, capsys):
    result = run.run_cell(tiny_cell(recompute), seed=SEED, seconds=0.5, trace=1,
                          need_tpu=False)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert result["metrics"] == {}            # counts only on a CPU
    rows = by_name(result["checks"])
    assert rows["compiles_in_window"]["value"] == 0
    assert rows["steps_off_the_window_program"]["value"] == 0
    printed = capsys.readouterr().out
    assert '"name": "grad_vector_error"' in printed


def test_lower_precision_control_is_not_correct():
    rows = by_name(control.control_checks(tiny_cell(), seed=SEED))
    assert not rows["grad_vector_error"]["ok"] and not rows["grad_norm_gap"]["ok"], rows


def test_served_precision_witness_lands_on_the_programs_side():
    # the reference with bf16-served weights and bf16 operands, the second
    # witness of the cell's loss limits: it is compared as the program is,
    # passes as the program does, and its loss after two updates lies nearer
    # the program's than the float32 reference's does
    from benchmarks import served_precision
    witness = by_name(served_precision.checks(tiny_cell(), SEED))
    assert all(row["ok"] for row in witness.values()), witness
    program = by_name(run.run_cell(tiny_cell(), seed=SEED, seconds=0.2, trace=0,
                                   need_tpu=False)["checks"])
    reference = witness["first_loss_gap"]["reference"]
    assert reference == program["first_loss_gap"]["reference"]
    last = program["first_loss_gap"]["program"][-1]
    assert abs(witness["first_loss_gap"]["program"][-1] - last) < abs(reference[-1] - last)


def test_the_cell_as_the_manifest_has_it():
    cell = harness.load_cell(CELL)
    cfg, family = cell["cfg"], cell["family"]
    kinds = family.layer_kinds(cfg)
    assert kinds == [("conv", "dense"), ("full_attention", "experts"),
                     ("conv", "experts"), ("conv", "experts"), ("conv", "experts")]
    shapes = family.reference.param_shapes(cfg)
    assert set(shapes) == set(family.program_names(cfg))
    n = sum(int(__import__("numpy").prod(s)) for s, _ in shapes.values())
    assert n == 469285248                      # 469.3M: PERF.md section 4
    assert cell["job"]["batch"] * cell["job"]["seq"] == 8192


def test_routing_counters_count_the_reference_rows():
    # the program's device counters against the reference's own routing,
    # at the seeded weights, in float32
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.profiler import metrics
    cell = tiny_cell(recompute=False)
    cfg, family = dict(cell["cfg"], weights_dtype="float32"), cell["family"]
    p = harness.init_params(family.reference.param_shapes(cfg), SEED, "float32")
    p = {k: 8 * v if v.ndim >= 2 else v for k, v in p.items()}
    x, _ = family.Stream(cfg, cell["job"], SEED).next()
    model = family.build_model(cfg)
    names = family.program_names(cfg)
    model.set_state_dict({names[k]: paddle.Tensor(v) for k, v in p.items()})
    before = metrics.get_registry().snapshot()["counters"]["moe.rows_here_total"]
    model(paddle.to_tensor(x))
    after = metrics.get_registry().snapshot()["counters"]["moe.rows_here_total"]
    want = [int(c) for c in family.reference.rows_routed_here(p, jnp.asarray(x), cfg)]
    assert after - before == sum(want) and len(want) == 4
    layers = [b.feed_forward for b in model.model.layers if not b.is_dense]
    assert [int(l.rows_total._val) for l in layers] == want


def test_route_check_counts_rows_and_flips():
    # bf16 against float32 at a tiny size: the counters agree with the
    # reference up to the few tokens whose pick flips, and each held
    # expert's gradient norm is read
    from benchmarks import route_check
    cell = tiny_cell()
    out = route_check.check(cell, SEED)
    assert out["tokens"] == 256 and len(out["layers"]) == 4
    for layer in out["layers"]:
        assert abs(layer["program_rows_here"] - layer["reference_rows_here"]) <= 12
        assert 0.0 <= layer["tokens_with_another_pick"] < 0.1
        assert len(layer["expert_grad_norm_gap"]) == 4
