"""The Laguna family's required FLOPs and what its readers share
(benchmarks/kernel_costs_laguna.py): the band's pairs against a brute-force
count at small sizes, the cell's arithmetic by hand at its own size, the four
readers on a recorded `measured` and where there is nothing to read, the cell
as the manifest has it, and the family's early exit on a tree without the
model."""
import importlib
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import flops, harness, kernel_costs, kernel_costs_laguna as costs  # noqa: E402

NAME = "laguna-xs2.pretrain-1chip-b1-s8192"
CELL = harness.load_cell(NAME)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ("swa_window_ms.train", "swa_window_roofline_pct", "swa_flash_roofline_pct",
           "attn_gate_ms.train")
PARAMETERS, PARAMETERS_TEXT = 691624960, "691.6M"


@pytest.mark.parametrize("seq, window", [(1, 1), (7, 3), (64, 16), (64, 64), (64, 100),
                                         (130, 1), (96, 95)])
def test_the_bands_pairs_by_brute_force(seq, window):
    t = np.arange(seq)
    band = (t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - window)
    assert costs.band_pairs(seq, window) == band.sum()
    assert costs.causal_pairs(seq) == (t[None, :] <= t[:, None]).sum()
    assert costs.band_pairs(seq, window) == sum(min(q + 1, window) for q in range(seq))


def test_matmul_weights_and_flops_per_token():
    family, cfg, job = CELL["family"], CELL["cfg"], CELL["job"]
    assert costs.layers(cfg) == [("full_attention", 48)] + [("sliding_attention", 64)] * 3 \
        + [("full_attention", 48)]
    per_token, per_sequence = family.matmul_shapes(cfg)
    assert per_sequence == []

    def mixer(heads):       # q and o, k and v, the heads' gate
        return 2 * 2048 * heads * 128 + 2 * 2048 * 1024 + 2048 * heads
    # router; the shared expert; all 8 picks of a token are computed here
    # (the held experts stand in for the absent ones)
    assert cfg["absent_experts"] == "stand_in"
    experts = 2048 * 256 + 3 * 2048 * 512 + 8 * 3 * 2048 * 512
    dense, head = 3 * 2048 * 8192, 2048 * 12544             # untied, once
    assert (mixer(48), mixer(64), experts, dense, head) == (
        29458432, 37879808, 28835840, 50331648, 25690112)
    weights = 2 * mixer(48) + 3 * mixer(64) + 4 * experts + dense + head
    assert flops.matmul_weights(per_token) == weights == 363921408
    # attention: scores and values, 2 x 128 a pair a head each, forward and
    # twice that backward; a window layer at the band's pairs, a full layer at
    # the triangle's
    band, triangle = 512 * 513 / 2 + 7680 * 512, 8192 * 8193 / 2
    assert (costs.band_pairs(8192, 512), costs.causal_pairs(8192)) == (band, triangle)
    window_layer, full_layer = 12 * 64 * 128 * band, 12 * 48 * 128 * triangle
    assert window_layer == pytest.approx(0.3995e12, rel=1e-3)
    assert full_layer == pytest.approx(2.474e12, rel=1e-3)
    assert 12 * 64 * 128 * triangle == pytest.approx(3.299e12, rel=1e-3)   # never counted
    assert costs.attention_train_flops(cfg, 8192) == pytest.approx(
        3 * window_layer + 2 * full_layer)
    assert family.flops_per_token(cfg, job) == pytest.approx(
        6 * weights + (3 * window_layer + 2 * full_layer) / 8192)
    # a step: 17.9 TFLOP in the matrix products, 6.1 in attention
    assert 8192 * 6 * weights == pytest.approx(17.9e12, rel=2e-3)
    assert 8192 * family.flops_per_token(cfg, job) == pytest.approx(24.03e12, rel=2e-3)
    # with the absent experts' terms dropped: 32 held of 256 at 8 a token
    # weigh one expert a token a layer
    dropped = dict(cfg, absent_experts="drop")
    assert flops.matmul_weights(family.matmul_shapes(dropped)[0]) == \
        weights - 4 * 7 * 3 * 2048 * 512
    assert family.tokens_per_step(job) == 8192


def test_the_rooflines_count_the_band_and_the_passes():
    cfg, job = CELL["cfg"], CELL["job"]
    band, triangle = costs.band_pairs(8192, 512), costs.causal_pairs(8192)

    def pair(heads, pairs, passes):
        product = 2 * heads * 128 * pairs / 197e12
        return passes * 2 * product + 5 * product           # compute-bound both
    for passes in (1, 2):
        window = 3 * pair(64, band, passes)
        assert costs.flash_seconds(cfg, job, passes, PEAK, kinds=("sliding_attention",)) \
            == pytest.approx(window)
        assert costs.flash_seconds(cfg, job, passes, PEAK) == pytest.approx(
            window + 2 * pair(48, triangle, passes))
    # a window layer's pair 2.4 ms at one forward pass, a full layer's 14.7
    assert pair(64, band, 1) == pytest.approx(2.37e-3, rel=5e-3)
    assert pair(48, triangle, 1) == pytest.approx(14.65e-3, rel=5e-3)
    # a short row is memory-bound: the operands cross HBM once a pass
    short = dict(cfg, num_layers=1, first_layer=1, num_key_value_heads=64)
    q = 256 * 64 * 128 * 2
    assert costs.flash_seconds(short, {"batch": 1, "seq": 256}, 1, PEAK) == \
        pytest.approx((4 * q + 8 * q) / 819e9)


def recorded(ops=(), scope_ms=None, kernels=None, steps=20):
    """A `measured` as run.py hands it to a reader, of a traced run whose
    window held `steps` steps: `ops` the device operations (name as
    trace_reduce.short leaves it, seconds over the window)."""
    return {"run": {"trace": {"steps": steps, "device_ops": [list(op) for op in ops]}},
            "peak": PEAK, "cell": CELL, "program_trace": {
                "scope_ms": scope_ms or {}, "held_ms": {}, "scope_kernels": kernels or {}}}


def read_all(m):
    return {name: harness.load_reader("layer_metrics", name)(dict(m)) for name in READERS}


def test_the_readers_with_nothing_to_read():
    untraced = {"run": {"trace": None}, "peak": PEAK, "cell": CELL}
    assert set(read_all(untraced).values()) == {None}
    # a traced run whose program has neither the kernels nor the scopes
    other = recorded([("fusion.3 f32[8]", 0.5), ("flash_set_fwd.2 (bf16[8]", 0.25)],
                     {"linear": 60.0})
    assert set(read_all(other).values()) == {None}


@pytest.mark.parametrize("forwards, passes", [(3, 1), (6, 2)])
def test_the_readers_on_a_recorded_run(forwards, passes):
    """Three window layers, one backward kernel each and one forward kernel a
    pass: 2.0 ms a forward, 7.5 a backward, 20 steps in the window."""
    ops = [(f"flash_window_fwd.{i} (bf16[64,16,128,512]", 20 * 2.0e-3) for i in range(forwards)]
    ops += [(f"flash_window_bwd.{i} (bf16[64,16,128,512]", 20 * 7.5e-3) for i in range(3)]
    ops += [("fusion.9 bf16[8192,2048]", 1.0), ("custom-call.4 (bf16[48,8,128,1024]", 0.4)]
    spent = forwards * 2.0 + 3 * 7.5
    read = read_all(recorded(
        ops, {"flash_attention": 80.0, "attn_gate": 1.5, "linear": 100.0},
        {"flash_attention": forwards + 3 + 2 * (passes + 1)}))
    assert read["swa_window_ms.train"] == pytest.approx(spent)
    assert read["attn_gate_ms.train"] == 1.5
    cfg, job = CELL["cfg"], CELL["job"]
    assert read["swa_window_roofline_pct"] == pytest.approx(100 * costs.flash_seconds(
        cfg, job, passes, PEAK, kinds=("sliding_attention",)) * 1e3 / spent)
    assert read["swa_flash_roofline_pct"] == pytest.approx(
        100 * costs.flash_seconds(cfg, job, passes, PEAK) * 1e3 / 80.0)
    assert 0 < read["swa_window_roofline_pct"] < 100
    assert 0 < read["swa_flash_roofline_pct"] < 100
    assert kernel_costs.forward_passes((forwards + 3) / 3, backward_kernels=1) == passes


def test_the_cell_as_the_manifest_has_it():
    bench = harness.manifest()
    cfg, family = CELL["cfg"], CELL["family"]
    entry = next(c for c in bench["configs"] if c["name"] == "laguna-xs2")
    assert CELL["cell"] in bench["workloads"] and CELL["cell"]["chips"] == 1
    assert CELL["cell"]["traffic"] == "pretrain-1chip-b1-s8192"
    assert entry["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [NAME]]
    assert [m["name"] for m in mine] == list(READERS)
    assert all(m["moves"] == "tokens_per_s_per_chip" and m["layer"] == "kernels"
               for m in mine)
    # no accepted metric's list gained the cell: the expert layer's readers
    # keep theirs until a benchmark PR lists it
    assert sum(NAME in m.get("workloads", ()) for m in bench["per_layer"]) == len(READERS)
    assert (cfg["num_hidden_layers"], cfg["num_layers"], cfg["first_layer"],
            cfg["num_experts"], cfg["num_experts_published"], cfg["vocab_size"],
            cfg["vocab_size_published"]) == (40, 5, 0, 32, 256, 12544, 100352)
    assert cfg["held_experts"] == list(range(32)) and cfg["recompute"] is True
    assert cfg["absent_experts"] == "stand_in"
    assert cfg["optimizer"]["learning_rate"] == 1e-4
    assert {"attention_gate", "router", "qk_norm", "rotary_pairing", "window", "positions",
            "initialisation", "optimizer", "absent_experts"} <= set(cfg["assumed"])
    assert "8 chips" in cfg["deployment"] and PARAMETERS_TEXT in cfg["deployment"]
    # the catalog's row: every key as published but the two reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
        assert row["source_url"] == entry["source"]
        assert {k for k, v in row["config"].items() if cfg[k] != v} == \
            {"num_experts", "vocab_size"}
    shapes = family.reference.param_shapes(cfg)               # shapes only, no arrays
    assert set(shapes) == set(family.program_names(cfg))
    count = {k: int(np.prod(s)) for k, (s, _) in shapes.items()}
    by_layer = [sum(n for k, n in count.items() if k.startswith(f"l{i}.")) for i in range(5)]
    assert by_layer == [79794176, 142217472, 142217472, 142217472, 133796096]
    assert count["wte"] == count["head_w"] == 12544 * 2048
    assert sum(count.values()) == PARAMETERS
    assert {std for k, (_, std) in shapes.items() if k.endswith("_w") or k == "wte"} == {0.02}
    assert CELL["job"]["batch"] * CELL["job"]["seq"] == 8192
    assert set(CELL["limits"]["read_from"]) >= {
        "runs", "first_loss_gap", "later_loss_gap", "grad_norm_gap",
        "grad_vector_error", "update_norm_gap", "control"}


def test_a_tree_without_the_model_stops_at_once(monkeypatch):
    from benchmarks.families import laguna
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "paddle_tpu.text.models.laguna"
        else real(name, *a))
    with pytest.raises(SystemExit, match="nothing was run"):
        importlib.reload(laguna)
    monkeypatch.undo()
    importlib.reload(laguna)
