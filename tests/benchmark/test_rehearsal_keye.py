"""The Keye-VL-2.0 cell end to end on the CPU at a tiny size: the entry, the
comparison that decides `correct`, the lower-precision control and the two
faulty programs the limits have to catch, as
tests/benchmark/test_rehearsal_kimi_linear.py does for the Kimi Linear cell;
the manifest's entries and the configuration's cut; the family's early exit
on a tree without the model. Widths are cut here and nowhere else; the
routing keeps 16 experts of which 4 are held, the sets 32 keys of rows of
128, two of the four layers."""
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

CELL = "keye-vl2-30b-a3b.pretrain-1chip-b1-s8192"
SEED = 5
# read on the CPU at this size over seeds 5 and 6 (the bf16 program / the
# float8 control): grad_vector_error 0.065, 0.067 / 0.227, 0.201;
# grad_norm_gap 0.013, 0.036 / 0.089, 0.043; first_loss_gap 6e-5, 4e-5 /
# 1.1e-4, 4.7e-4
TINY_LIMITS = {"first_loss_gap": 3e-4, "later_loss_gap": 0.5, "grad_norm_gap": 0.05,
               "grad_vector_error": 0.12, "update_norm_gap": 0.75,
               "loss_last32_over_first": 1.0}


def tiny_cell():
    cell = harness.load_cell(CELL)
    cfg = cell["cfg"]
    cfg.update(hidden_size=64, head_dim=16, num_attention_heads=4,
               num_key_value_heads=2, moe_intermediate_size=32,
               num_experts_published=16, num_experts=4, held_experts=[0, 1, 2, 3],
               num_experts_per_tok=2, vocab_size=600, num_layers=2)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], mrope_section=[2, 3, 3])
    cfg["sa_config"] = dict(cfg["sa_config"], indexer_head_dim=8,
                            indexer_num_heads=2, topk=32)
    cell["job"].update(batch=2, seq=128, reference_rows_per_block=1, trace_steps=4)
    cell["limits"] = dict(TINY_LIMITS)
    return cell


def by_name(rows):
    return {r["name"]: r for r in rows}


def test_entry_runs_and_agrees_with_the_reference(capsys, monkeypatch):
    from benchmarks import program
    from paddle_tpu.profiler import metrics
    before = metrics.get_registry().snapshot()["counters"].get("dsa.calls_total", 0.0)
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
    assert after["dsa.calls_total"] > before and after["dsa.selected_pairs_total"] > 0


def test_lower_precision_control_is_not_correct():
    rows = by_name(control.control_checks(tiny_cell(), seed=SEED))
    assert not rows["grad_vector_error"]["ok"], rows


class Faulty:
    """The reference with a fault put in, in the program's place."""

    def __init__(self, reference, **fault):
        self.reference, self.fault = reference, fault

    def loss_fn(self, p, x, y, cfg, **kwargs):
        if self.fault.get("no_index_loss"):
            return self.reference.loss_parts(p, x, y, cfg, **kwargs)[0]
        return self.reference.loss_fn(p, x, y, cfg, **self.fault, **kwargs)


@pytest.mark.parametrize("fault, caught_by", [
    ("all_causal_keys", "grad_norm_gap"), ("no_index_loss", "first_loss_gap")])
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
    if fault == "no_index_loss":                  # the indexer's leaves stand still
        assert rows["grad_norm_gap"]["value"] > 0.9 and ".index_" in rows["grad_norm_gap"]["leaf"]


def test_the_picks_and_both_losses_beside_the_reference():
    """benchmarks/dsa_check.py at the tiny size: the two losses apart, and
    per layer the (query, key) picks and the (token, expert) picks that the
    bf16 program and the float32 reference have in common."""
    from benchmarks import dsa_check
    out = dsa_check.check(tiny_cell(), SEED)
    assert out["lm_loss"]["gap"] < 3e-4 and out["index_loss"]["gap"] < 5e-3
    assert out["index_loss"]["reference"] > 0.01
    assert len(out["layers"]) == 2
    for layer in out["layers"]:
        # flips at the edge of a set and of a pick, no set of another size
        assert layer["pairs_in_common_share"] > 0.97
        assert abs(layer["program_pairs"] - layer["reference_pairs"]) < 0.01 * layer["reference_pairs"]
        assert layer["tokens_with_another_pick"] < 0.1
        # held experts stand in for the absent ones: every pick is a row here
        assert layer["rows_here"] == 2 * 128 * 2
        assert layer["rows_here"] / 4 <= layer["rows_busiest_slot"] <= layer["rows_here"]


def test_the_cell_as_the_manifest_has_it():
    bench = harness.manifest()
    cell = harness.load_cell(CELL)
    cfg, family = cell["cfg"], cell["family"]
    # found by name: a later PR appends after these, so no position is pinned
    entry = next(c for c in bench["configs"] if c["name"] == "keye-vl2-30b-a3b")
    assert cell["cell"] in bench["workloads"] and cell["cell"]["chips"] == 1
    assert cell["cell"]["traffic"] == "pretrain-1chip-b1-s8192"
    assert entry["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json"
    mine = [m for m in bench["per_layer"] if m["name"].startswith("dsa_")]
    assert [m["name"] for m in mine] == [
        "dsa_index_ms.train", "dsa_index_roofline_pct", "dsa_flash_roofline_pct",
        "dsa_selected_pairs_per_step"]
    assert all(CELL in m["workloads"] and m["moves"] == "tokens_per_s_per_chip"
               for m in mine)
    # the published keys, and the three that differ beside their published values
    assert (cfg["num_hidden_layers"], cfg["num_layers"], cfg["first_layer"],
            cfg["num_experts"], cfg["num_experts_published"], cfg["vocab_size"],
            cfg["vocab_size_published"]) == (48, 4, 0, 16, 128, 18992, 151936)
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["rope_theta"], cfg["rms_norm_eps"],
            cfg["norm_topk_prob"]) == (2048, 128, 32, 4, 768, 8, 10000000, 1e-06, True)
    assert cfg["sa_config"] == {"indexer_head_dim": 64, "indexer_num_heads": 16,
                                "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                                "q_chunk_size": 512, "topk": 2048}
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert cfg["held_experts"] == list(range(16)) and cfg["recompute"] is True
    # the held experts stand in for the absent ones; the issue's init (in the
    # reference: every matrix and the embedding at one std) and the cells' rate
    assert cfg["absent_experts"] == "stand_in"
    assert cfg["optimizer"]["learning_rate"] == 1e-4
    assert {std for k, (_, std) in family.reference.param_shapes(cfg).items()
            if k.endswith("_w") or k == "wte"} == {0.02}
    assert {"indexer_input", "indexer_rope", "indexer_k_norm", "indexer_weights_scale",
            "qk_norm", "index_loss", "ties", "initialisation", "absent_experts"} \
        <= set(cfg["assumed"])
    assert "465.4M" in cfg["deployment"] and "8 chips" in cfg["deployment"]
    # the catalog's row, every number under its key but the three reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert row["source_url"] == entry["source"]
        assert {k for k, v in row["config"].items() if cfg[k] != v} == \
            {"num_experts", "vocab_size"}
    shapes = family.reference.param_shapes(cfg)               # shapes only, no arrays
    assert set(shapes) == set(family.program_names(cfg))
    count = {k: int(np.prod(s)) for k, (s, _) in shapes.items()}
    assert sum(count.values()) == 465391616
    layer = sum(n for k, n in count.items() if k.startswith("l0."))
    # attention 18,874,368 + two head norms; indexer 2,260,992 + LayerNorm;
    # router 262,144 + the zero bias; 16 experts of 4,718,592; two norms
    assert layer == 18874368 + 256 + 2260992 + 128 + 262144 + 128 + 75497472 + 4096
    assert count["wte"] == count["head_w"] == 18992 * 2048
    assert cell["job"]["batch"] * cell["job"]["seq"] == 8192
    assert set(cell["limits"]["read_from"]) >= {
        "runs", "first_loss_gap", "later_loss_gap", "grad_norm_gap",
        "grad_vector_error", "update_norm_gap", "control"}


def test_a_tree_without_the_model_stops_at_once(monkeypatch):
    from benchmarks.families import keye_vl2
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "paddle_tpu.text.models.keye_vl2"
        else real(name, *a))
    with pytest.raises(SystemExit, match="nothing was run"):
        importlib.reload(keye_vl2)
    monkeypatch.undo()
    importlib.reload(keye_vl2)
