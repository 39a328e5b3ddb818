"""The DeepSeek-V2 family's required FLOPs and what its readers share
(benchmarks/kernel_costs_dsv2.py), against values worked by hand from the
shapes at the cell's size, and what the four readers give where there is
nothing to read."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import flops, harness, kernel_costs_dsv2 as costs  # noqa: E402

CELL = harness.load_cell("deepseek-v2-lite.pretrain-1chip-b1-s8192")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ("mla_rope_ms.train", "mla_rope_flash_roofline_pct",
           "moe_balance_loss_ms.train", "moe_router_max_over_mean")


def test_matmul_weights_and_flops_per_token():
    family, job = CELL["family"], CELL["job"]
    # the cell's own depth is the rule's to decide: the arithmetic is held at
    # the published layers 0-4
    cfg = dict(CELL["cfg"], first_layer=0, num_layers=5)
    per_token, per_sequence = family.matmul_shapes(cfg)
    assert per_sequence == []
    mixer = 2048 * 16 * 192 + 2048 * (512 + 64) + 512 * 16 * 256 + 2048 * 2048
    # router; the two shared experts as one SwiGLU of 2816; all 6 picks of a
    # token are computed here (the held experts stand in for the absent ones)
    assert cfg["absent_experts"] == "stand_in"
    experts = 2048 * 64 + 3 * 2048 * 2816 + 6 * 3 * 2048 * 1408
    dense = 3 * 2048 * 10944
    head = 2048 * 12800                                     # untied, once
    assert (mixer, experts, dense, head) == (13762560, 69337088, 67239936, 26214400)
    assert flops.matmul_weights(per_token) == 5 * mixer + 4 * experts + dense + head
    assert flops.matmul_weights(per_token) == 439615488
    # a layer's attention: scores at 192 and values at 128 over 16 heads,
    # 4 x s x 16 x 160 forward a token, half of it causal, three times in training
    attention = 12 * 8192 * 16 * 160 / 2
    assert family.flops_per_token(cfg, job) == pytest.approx(6 * 439615488 + 5 * attention)
    assert family.flops_per_token(cfg, job) == pytest.approx(3266838528)
    # forward, a token: the mixer's projections 27.5 MF, the core 41.9, the
    # shared experts 34.6, six routed experts 103.8
    assert 2 * mixer == pytest.approx(27.5e6, rel=2e-3)
    assert attention / 3 == pytest.approx(41.9e6, rel=2e-3)
    assert 2 * 3 * 2048 * 2816 == pytest.approx(34.6e6, rel=2e-3)
    assert 2 * 6 * 3 * 2048 * 1408 == pytest.approx(103.8e6, rel=2e-3)
    # with the absent experts' terms dropped: 8 held of 64 at 6 a token weigh
    # three quarters of an expert a token a layer
    dropped = dict(cfg, absent_experts="drop")
    assert flops.matmul_weights(family.matmul_shapes(dropped)[0]) == 257949696
    assert family.flops_per_token(dropped, job) == pytest.approx(2176843776)
    assert family.tokens_per_step(job) == 8192
    # without the leading dense layer: published layers 1-4
    assert family.flops_per_token(dict(cfg, first_layer=1, num_layers=4), job) == \
        pytest.approx(6 * (4 * (mixer + experts) + head) + 4 * attention)


def test_the_flash_pairs_roofline_counts_the_passes_the_program_runs():
    cfg, job = dict(CELL["cfg"], first_layer=0, num_layers=5), CELL["job"]
    unit = 2 * 16 * 8192 * 8192 / 2                       # a product of width 1, causal
    fwd, bwd = unit * (192 + 128) / 197e12, unit * (3 * 192 + 2 * 128) / 197e12
    assert costs.flash_seconds(cfg, job, 1, PEAK) == pytest.approx(5 * (fwd + bwd))
    assert costs.flash_seconds(cfg, job, 2, PEAK) == pytest.approx(5 * (2 * fwd + bwd))
    assert 5 * (2 * fwd + bwd) == pytest.approx(40.1e-3, rel=2e-3)      # compute-bound
    # what the trace says: a layer's kernels less its one backward kernel
    # (kernel_costs.forward_passes; tests/benchmark/test_lfm2_costs.py)


def test_the_readers_with_nothing_to_read(monkeypatch):
    from benchmarks import program
    # an untraced run, and a program without the gauge (a parent of this PR)
    untraced = {"run": {"trace": None}, "peak": PEAK}
    monkeypatch.setattr(program, "registry", lambda: {"counters": {}, "gauges": {}})
    for name in READERS:
        assert harness.load_reader("layer_metrics", name)(dict(untraced)) is None
    monkeypatch.setattr(program, "registry", lambda: None)
    assert harness.load_reader("layer_metrics", "moe_router_max_over_mean")({}) is None
    # a traced run whose program stages neither scope
    traced = {"run": {"trace": {"steps": 1}}, "peak": PEAK,
              "program_trace": {"scope_ms": {"linear": 60.0}, "held_ms": {}}}
    for name in READERS[:3]:
        assert harness.load_reader("layer_metrics", name)(dict(traced)) is None


@pytest.mark.parametrize("kernels, passes", [
    ({"flash_attention": 15.0}, 2),           # forward, rerun forward, backward: five layers
    ({"flash_attention": 10.0}, 1),           # a tree that keeps the pair's operands
    ({}, 2),                                  # a trace that does not say: `recompute`
])
def test_the_readers_with_something_to_read(kernels, passes, monkeypatch):
    from benchmarks import program
    cell = dict(CELL, cfg=dict(CELL["cfg"], first_layer=0, num_layers=5))
    monkeypatch.setattr(program, "registry", lambda: {
        "counters": {}, "gauges": {"moe.router_max_over_mean_ratio": 1.75}})
    traced = {"run": {"trace": {"steps": 1}}, "peak": PEAK, "cell": cell, "program_trace": {
        "scope_ms": {"flash_attention": 87.0, "mla_rope": 6.5, "moe_balance_loss": 1.25},
        "held_ms": {}, "scope_kernels": kernels}}
    read = {name: harness.load_reader("layer_metrics", name)(dict(traced))
            for name in READERS}
    assert read["mla_rope_ms.train"] == 6.5 and read["moe_balance_loss_ms.train"] == 1.25
    assert read["moe_router_max_over_mean"] == 1.75
    assert read["mla_rope_flash_roofline_pct"] == pytest.approx(
        100 * costs.flash_seconds(cell["cfg"], cell["job"], passes, PEAK) * 1e3 / 87.0)
    assert 0 < read["mla_rope_flash_roofline_pct"] < 100
