"""The Kimi Linear cell end to end on the CPU at a tiny size: the entry, the
comparison that decides `correct`, and the lower-precision control, as
tests/benchmark/test_rehearsal_lfm2.py does for the LFM2 cell; the manifest's
entries and the configuration's cut; the family's early exit on a tree
without the model. Widths are cut here and nowhere else; the routing keeps 16
experts of which 4 are held, and two of the four layers (published 7 and 8:
one of each mixer) keep the compile short."""
import importlib
import importlib.util
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import control, harness, run  # noqa: E402

CELL = "kimi-linear-48b-a3b.pretrain-1chip-b2-s4096"
SEED = 5
# read on the CPU at this size and seed: the bf16 program's gradient error
# over the one-dimensional leaves is 0.0147 and the float8 control's 0.0634;
# the worst leaf's gradient norm gap 0.0033 and 0.0118; the first loss
# differs by 7e-6
TINY_LIMITS = {"first_loss_gap": 3e-4, "later_loss_gap": 0.5, "grad_norm_gap": 0.03,
               "grad_vector_error": 0.03, "update_norm_gap": 0.75,
               "loss_last32_over_first": 1.0}


def tiny_cell():
    cell = harness.load_cell(CELL)
    cfg = cell["cfg"]
    cfg.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               intermediate_size=96, moe_intermediate_size=32, vocab_size=600,
               num_experts=4, held_experts=[0, 1, 2, 3], gate_rank=8,
               num_layers=2, first_layer=6)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"], num_heads=4,
                                     head_dim=16)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cell["job"].update(batch=2, seq=128, reference_rows_per_block=1, trace_steps=4)
    cell["limits"] = dict(TINY_LIMITS)
    return cell


def by_name(rows):
    return {r["name"]: r for r in rows}


def test_entry_runs_and_agrees_with_the_reference(capsys):
    from paddle_tpu.profiler import metrics
    before = metrics.get_registry().snapshot()["counters"].get("kda.calls_total", 0.0)
    result = run.run_cell(tiny_cell(), seed=SEED, seconds=0.5, trace=1,
                          need_tpu=False)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert result["metrics"] == {}            # counts only on a CPU
    rows = by_name(result["checks"])
    assert rows["compiles_in_window"]["value"] == 0
    assert rows["steps_off_the_window_program"]["value"] == 0
    assert '"name": "grad_vector_error"' in capsys.readouterr().out
    after = metrics.get_registry().snapshot()["counters"]
    assert after["kda.calls_total"] > before and after["kda.tokens_total"] > 0


def test_lower_precision_control_is_not_correct():
    rows = by_name(control.control_checks(tiny_cell(), seed=SEED))
    assert not rows["grad_vector_error"]["ok"], rows


def test_the_cell_as_the_manifest_has_it():
    bench = harness.manifest()
    cell = harness.load_cell(CELL)
    cfg, family = cell["cfg"], cell["family"]
    # found by name: a later PR appends after these, so no position is pinned
    entry = next(c for c in bench["configs"] if c["name"] == "kimi-linear-48b-a3b")
    assert cell["cell"] in bench["workloads"] and cell["cell"]["chips"] == 1
    assert cell["cell"]["traffic"] == "pretrain-1chip-b2-s4096"
    assert entry["reduced"] == ["num_layers", "first_k_dense_replace",
                                "num_experts", "vocab_size"]
    assert entry["source"].endswith("Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    mine = [m for m in bench["per_layer"]
            if m["name"] in ("kda_ms.train", "kda_roofline_pct", "mla_flash_roofline_pct")]
    assert [m["name"] for m in mine] == [
        "kda_ms.train", "kda_roofline_pct", "mla_flash_roofline_pct"]
    assert all(m["workloads"] == [CELL] for m in mine)
    # the published keys, and the four that differ beside their published values
    assert cfg["published"] == {"num_hidden_layers": 27, "first_k_dense_replace": 1,
                                "num_experts": 256, "vocab_size": 163840}
    assert (cfg["num_hidden_layers"], cfg["num_layers"], cfg["first_layer"],
            cfg["first_k_dense_replace"], cfg["num_experts"],
            cfg["vocab_size"]) == (27, 4, 4, 0, 8, 20480)
    assert (cfg["hidden_size"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_token"], cfg["routed_scaling_factor"]) == (
        2304, 512, 128, 64, 128, 1024, 8, 2.446)
    assert cfg["linear_attn_config"]["kda_layers"][:6] == [1, 2, 3, 5, 6, 7]
    assert cfg["held_experts"] == list(range(8)) and cfg["recompute"] is True
    assert {"gate_rank", "kda_leaves", "initialisation", "expert_bias", "context"} \
        <= set(cfg["assumed"])
    assert "499,214,560" in cfg["deployment"] and "32 chips" in cfg["deployment"]
    # published layers 5-8: one whole period
    assert family.layer_kinds(cfg) == [("kda", "experts")] * 3 + [("full_attention", "experts")]
    shapes = family.reference.param_shapes(cfg)               # shapes only, no arrays
    assert set(shapes) == set(family.program_names(cfg))
    count = {k: int(np.prod(s)) for k, (s, _) in shapes.items()}
    assert sum(count.values()) == 499214560
    layer = lambda i: sum(n for k, n in count.items() if k.startswith(f"l{i}."))  # noqa: E731
    assert layer(0) == 103809952 and layer(3) == 93410560
    assert count["wte"] == count["head_w"] == 47185920
    assert cell["job"]["batch"] * cell["job"]["seq"] == 8192


def test_a_tree_without_the_model_stops_at_once(monkeypatch):
    from benchmarks.families import kimi_linear
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "paddle_tpu.text.models.kimi_linear"
        else real(name, *a))
    with pytest.raises(SystemExit, match="nothing was run"):
        importlib.reload(kimi_linear)
    monkeypatch.undo()
    importlib.reload(kimi_linear)
