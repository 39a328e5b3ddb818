"""The DeepSeek-V2-Lite cell end to end on the CPU at a tiny size: the entry,
the comparison that decides `correct`, the lower-precision control and the
two faulty programs the limits have to catch, as
tests/benchmark/test_rehearsal_keye.py does for the Keye cell; the manifest's
entries and the configuration's cut; the reference's leaf names against the
program's `state_dict`; the family's early exit on a tree without the model.
Widths are cut here and nowhere else; the routing keeps 16 experts of which 4
are held and 3 picked, the rows 128 tokens, three of the five layers (the
dense one and two expert layers)."""
import importlib
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import control, harness, run  # noqa: E402

CELL = "deepseek-v2-lite.pretrain-1chip-b1-s8192"
SEED = 5
# read on the CPU at this size over seeds 5 and 6 (the bf16 program / the
# float8 control): grad_vector_error 0.0125, 0.0102 / 0.055, 0.053;
# grad_norm_gap 0.0044, 0.0021 / 0.031, 0.018 (3.35, 3.33 with renormalised
# weights); first_loss_gap 1.4e-5, 3e-6 / 3.3e-5, 1.5e-4 (3.2e-4 without the
# balance terms: two expert layers' 0.001 of a loss of ln 600)
# what the dense-layer rule left (PERF.md section 4): published layers 0-4
DEPTH = (5, 0, ["dense"] + ["experts"] * 4)     # num_layers, first_layer, kinds
PARAMETERS, PARAMETERS_TEXT = 535061248, "535.1M"
TINY_LIMITS = {"first_loss_gap": 1e-4, "later_loss_gap": 0.5, "grad_norm_gap": 0.012,
               "grad_vector_error": 0.025, "update_norm_gap": 0.75,
               "loss_last32_over_first": 1.0}


def tiny_cell():
    cell = harness.load_cell(CELL)
    cell["cfg"].update(
        hidden_size=64, num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=16, intermediate_size=96,
        moe_intermediate_size=32, n_routed_experts_published=16, n_routed_experts=4,
        held_experts=[0, 1, 2, 3], num_experts_per_tok=3, vocab_size=600, num_layers=3)
    cell["job"].update(batch=2, seq=128, reference_rows_per_block=1, trace_steps=4)
    cell["limits"] = dict(TINY_LIMITS)
    return cell


def by_name(rows):
    return {r["name"]: r for r in rows}


def test_entry_runs_and_agrees_with_the_reference(capsys, monkeypatch):
    from benchmarks import program
    from paddle_tpu.profiler import metrics
    before = metrics.get_registry().snapshot()["counters"]
    # the registry as the readers find it: the device counters live in the
    # layers, which are gone once run_cell has released the program
    seen, release = [], program.release
    monkeypatch.setattr(program, "release",
                        lambda: seen.append(program.registry()) or release())
    result = run.run_cell(tiny_cell(), seed=SEED, seconds=0.5, trace=1,
                          need_tpu=False)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert result["metrics"] == {}            # counts only on a CPU
    rows = by_name(result["checks"])
    assert rows["compiles_in_window"]["value"] == 0
    assert rows["steps_off_the_window_program"]["value"] == 0
    assert '"name": "grad_vector_error"' in capsys.readouterr().out
    (snap,) = seen
    after = snap["counters"]
    assert metrics.get_registry().snapshot()["gauges"]["moe.live_layers_count"] == 0
    calls = after["moe.layer_calls_total"] - before.get("moe.layer_calls_total", 0.0)
    # held experts stand in for the absent ones: every pick is a row here
    assert after["moe.rows_here_total"] - before.get("moe.rows_here_total", 0.0) \
        == calls * 2 * 128 * 3
    # about alpha a layer a call, and the router's gauge between balance and collapse
    mean = (after["moe.balance_loss_total"]
            - before.get("moe.balance_loss_total", 0.0)) / calls
    assert 0.001 <= mean < 0.004
    assert 1.0 <= snap["gauges"]["moe.router_max_over_mean_ratio"] <= 16 / 3


def test_lower_precision_control_is_not_correct():
    rows = by_name(control.control_checks(tiny_cell(), seed=SEED))
    assert not rows["grad_vector_error"]["ok"] and not rows["grad_norm_gap"]["ok"], rows


class Faulty:
    """The reference with a fault put in, in the program's place."""

    def __init__(self, reference, **fault):
        self.reference, self.fault = reference, fault

    def loss_fn(self, p, x, y, cfg, **kwargs):
        return self.reference.loss_fn(p, x, y, cfg, **self.fault, **kwargs)


@pytest.mark.parametrize("fault, caught_by", [
    ("renormalised", "grad_norm_gap"), ("no_balance_loss", "first_loss_gap")])
def test_a_faulty_program_is_not_correct(fault, caught_by):
    import jax
    cell = tiny_cell()
    cfg, job = cell["cfg"], cell["job"]
    bad = harness.reference_numbers(
        Faulty(cell["family"].reference, **{fault: True}), cfg,
        *control.seeded(cell, SEED), job["reference_rows_per_block"],
        jax.local_devices()[:1])
    rows = by_name(harness.compare(bad, control.reference_numbers(cell, SEED),
                                   cell["limits"]))
    assert not rows[caught_by]["ok"], rows


def test_the_cell_as_the_manifest_has_it():
    bench = harness.manifest()
    cell = harness.load_cell(CELL)
    cfg, family = cell["cfg"], cell["family"]
    # found by name: a later PR appends after these, so no position is pinned
    entry = next(c for c in bench["configs"] if c["name"] == "deepseek-v2-lite")
    assert cell["cell"] in bench["workloads"] and cell["cell"]["chips"] == 1
    assert cell["cell"]["traffic"] == "pretrain-1chip-b1-s8192"
    assert entry["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json"
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "mla_rope_ms.train", "mla_rope_flash_roofline_pct",
        "moe_balance_loss_ms.train", "moe_router_max_over_mean"]
    assert all(m["moves"] == "tokens_per_s_per_chip" and m["layer"] == "kernels"
               for m in mine)
    # the published keys, and the three that differ beside their published values
    assert (cfg["num_hidden_layers"], cfg["num_layers"], cfg["first_layer"],
            cfg["first_k_dense_replace"], cfg["n_routed_experts"],
            cfg["n_routed_experts_published"], cfg["vocab_size"],
            cfg["vocab_size_published"]) == (27, DEPTH[0], DEPTH[1], 1, 8, 64, 12800, 102400)
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["norm_topk_prob"], cfg["q_lora_rank"],
            cfg["routed_scaling_factor"], cfg["seq_aux"]) == (
        2048, 16, 512, 128, 64, 128, 10944, 1408, 6, 2, 10000, 1e-06, False, None, 1, True)
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096, "type": "yarn"}
    assert cfg["held_experts"] == list(range(8)) and cfg["recompute"] is True
    assert cfg["absent_experts"] == "stand_in" and cfg["aux_loss_alpha"] == 0.001
    assert cfg["optimizer"]["learning_rate"] == 1e-4
    assert {std for k, (_, std) in family.reference.param_shapes(cfg).items()
            if k.endswith("_w") or k == "wte"} == {0.02}
    assert {"aux_loss_alpha", "balance_loss_value", "rotary_pairing", "absent_experts",
            "initialisation", "optimizer", "router_precision", "recompute"} \
        <= set(cfg["assumed"])
    assert "8 chips" in cfg["deployment"] and PARAMETERS_TEXT in cfg["deployment"]
    # the catalog's row, every number under its key but the two reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V2-Lite")
        assert row["source_url"] == entry["source"]
        assert {k for k, v in row["config"].items() if cfg[k] != v} == \
            {"n_routed_experts", "vocab_size"}
    # the reference's leaves are the program's state, name for name
    shapes = family.reference.param_shapes(cfg)               # shapes only, no arrays
    assert set(shapes) == set(family.program_names(cfg))
    count = {k: int(np.prod(s)) for k, (s, _) in shapes.items()}
    # the mixer 13,763,072 with its latent norm; an expert layer outside its
    # routed experts: two norms, the router and its zero bias, the shared
    # SwiGLU of 2816; 8 experts of 8,650,752; the dense layer's SwiGLU of 10944
    mixer = 2048 * 16 * 192 + 2048 * 576 + 512 + 512 * 16 * 256 + 2048 * 2048
    assert mixer == 13763072
    expert_layer = mixer + 4096 + 2048 * 64 + 64 + 3 * 2048 * 2816 + 8 * 3 * 2048 * 1408
    dense_layer = mixer + 4096 + 3 * 2048 * 10944
    assert (expert_layer, dense_layer) == (100405824, 81007104)
    kinds = family.layer_kinds(cfg)
    assert kinds == DEPTH[2]
    for i, kind in enumerate(kinds):
        assert sum(n for k, n in count.items() if k.startswith(f"l{i}.")) == \
            (dense_layer if kind == "dense" else expert_layer)
    assert count["wte"] == count["head_w"] == 12800 * 2048
    assert sum(count.values()) == PARAMETERS
    assert cell["job"]["batch"] * cell["job"]["seq"] == 8192
    assert set(cell["limits"]["read_from"]) >= {
        "runs", "first_loss_gap", "later_loss_gap", "grad_norm_gap",
        "grad_vector_error", "update_norm_gap", "control"}


def test_the_programs_state_is_the_references_leaves():
    """At the tiny size, with arrays: every key of the program's `state_dict`
    is named by a reference leaf of the same shape, and none is left over."""
    cell = tiny_cell()
    cfg, family = cell["cfg"], cell["family"]
    state = family.build_model(cfg).state_dict()
    names = family.program_names(cfg)
    assert set(names.values()) == set(state)
    for leaf, (shape, _) in family.reference.param_shapes(cfg).items():
        assert list(state[names[leaf]].shape) == list(shape), leaf


def test_a_tree_without_the_model_stops_at_once(monkeypatch):
    from benchmarks.families import deepseek_v2
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "paddle_tpu.text.models.deepseek_v2"
        else real(name, *a))
    with pytest.raises(SystemExit, match="nothing was run"):
        importlib.reload(deepseek_v2)
    monkeypatch.undo()
    importlib.reload(deepseek_v2)
