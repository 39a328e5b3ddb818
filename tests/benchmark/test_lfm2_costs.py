"""The LFM2 family's required FLOPs and the new kernels' operations and
bytes, against values worked by hand from the shapes."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import flops, harness, kernel_costs  # noqa: E402

CELL = harness.load_cell("lfm2-24b-a2b.pretrain-1chip-b2-s4096")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_matmul_weights_and_flops_per_token():
    family, cfg, job = CELL["family"], CELL["cfg"], CELL["job"]
    per_token, per_sequence = family.matmul_shapes(cfg)
    assert per_sequence == []
    conv = 2048 * 6144 + 2048 * 2048                    # in and out projections
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512        # q, o; k, v of 8 heads
    dense = 3 * 2048 * 11776
    # 8 held of 64 experts at 4 a token: half an expert a token a layer
    experts = 2048 * 64 + 0.5 * 3 * 2048 * 1536
    head = 2048 * 8192                                  # tied, once
    assert flops.matmul_weights(per_token) == pytest.approx(
        (conv + dense) + (attention + experts) + 3 * (conv + experts) + head)
    assert flops.matmul_weights(per_token) == pytest.approx(186122240)
    # one attention layer of five: 12 x 1 x 4096 x 2048 / 2 = 50,331,648
    assert family.flops_per_token(cfg, job) == pytest.approx(
        6 * 186122240 + 50331648)
    assert family.tokens_per_step(job) == 8192


def test_grouped_product_costs():
    # 4096 rows of 2048 against 8 matrices of (2048, 1536), bf16
    ops, nbytes = kernel_costs.grouped_product(4096, 2048, 1536, 8)
    assert ops == 2 * 4096 * 2048 * 1536 == 25769803776
    assert nbytes == 2 * (4096 * 2048 + 8 * 2048 * 1536 + 4096 * 1536) == 79691776
    seconds, bound = kernel_costs.roofline_seconds(ops, nbytes, PEAK)
    assert bound == "compute" and seconds == pytest.approx(ops / 197e12)
    # few rows: the weights' bytes bound it
    assert kernel_costs.roofline_seconds(
        *kernel_costs.grouped_product(256, 2048, 1536, 8), PEAK)[1] == "memory"
    # the transposed product moves and multiplies the same
    assert kernel_costs.grouped_product(4096, 1536, 2048, 8) == (ops, nbytes)


def test_expert_layer_seconds_count_every_pass():
    one = kernel_costs.roofline_seconds(
        *kernel_costs.grouped_product(4096, 2048, 1536, 8), PEAK)[0]
    # three projections x (forward + transposed product + weight gradient),
    # all compute-bound and of one size at 4096 rows
    assert kernel_costs.expert_layer_seconds(4096, 2048, 1536, 8, 1, PEAK) == \
        pytest.approx(9 * one)
    assert kernel_costs.expert_layer_seconds(4096, 2048, 1536, 8, 2, PEAK) == \
        pytest.approx(12 * one)


def test_causal_attention_seconds():
    # 2 x 32 heads x 4096^2 x 64, halved by the mask, 2 FLOPs a multiply-add
    product = 2 * 2 * 32 * 4096 * 4096 * 64 / 2
    got = kernel_costs.causal_attention_seconds(2, 32, 8, 4096, 64, 1, PEAK)
    assert got == pytest.approx(7 * product / 197e12)
    again = kernel_costs.causal_attention_seconds(2, 32, 8, 4096, 64, 2, PEAK)
    assert again == pytest.approx(9 * product / 197e12)


def test_readers_return_none_where_there_is_nothing_to_read():
    # an untraced run, or a program without the counters: no value, no raise
    m = {"run": {"trace": None}, "peak": PEAK}
    for name in ("moe_experts_ms.train", "moe_route_ms.train", "short_conv_ms.train",
                 "attention_flash_pct", "moe_gmm_roofline_pct",
                 "flash_attention_roofline_pct"):
        assert harness.load_reader("layer_metrics", name)(dict(m)) is None, name
    from benchmarks import program
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(program, "registry", lambda: {"counters": {}, "gauges": {}})
        for name in ("moe_rows_here_per_step", "moe_load_max_over_mean"):
            assert harness.load_reader("layer_metrics", name)(dict(m)) is None, name
        mp.setattr(program, "registry", lambda: None)
        assert harness.load_reader("layer_metrics", "moe_rows_here_per_step")(dict(m)) is None


def test_routing_readers_read_the_registry():
    from benchmarks import program
    snap = {"counters": {"moe.rows_here_total": 4 * 10 * 4000.0,
                         "moe.layer_calls_total": 4 * 10.0},
            "gauges": {"moe.live_layers_count": 4, "moe.load_max_over_mean_ratio": 1.25}}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(program, "registry", lambda: snap)
        m = {"run": {"trace": None}, "peak": PEAK}
        assert harness.load_reader("layer_metrics", "moe_rows_here_per_step")(m) == 16000
        assert harness.load_reader("layer_metrics", "moe_load_max_over_mean")(m) == 1.25
        assert m["moe_routing"]["rows_per_layer_step"] == 4000
        assert m["moe_routing"]["layers"] == 4


@pytest.mark.parametrize("batch", [2, 8], ids=["the-cell", "a-later-cell-of-the-family"])
def test_roofline_readers_score_the_cell_that_ran(batch):
    # the readers name no cell: they take the widths, batch and sequence of
    # the cell run.py hands them, and the passes the run's trace holds
    from benchmarks import lfm2_readings
    cell = dict(CELL, job=dict(CELL["job"], batch=batch))
    # my chip run, PR 27, seed 27001: rows, scope times and both readings; a
    # layer's 12 grouped products and the attention layer's 3 flash kernels
    m = {"run": {"trace": {"steps": 20}}, "peak": PEAK, "cell": cell,
         "program_trace": {"scope_ms": {"moe_experts": 17.8947154,
                                        "flash_attention": 15.2378491}, "held_ms": {},
                           "scope_kernels": {"moe_experts": 48.0, "flash_attention": 3.0}},
         "moe_routing": {"rows_per_step": 26270.846666666668,
                         "rows_per_layer_step": 26270.846666666668 / 4, "layers": 4,
                         "load_max_over_mean": 1.4310544840494792}}
    assert lfm2_readings.gmm_roofline_pct(m) == pytest.approx(56.262037707823154)
    assert lfm2_readings.flash_roofline_pct(m) == pytest.approx(
        20.60309470005759 * batch / 2)
    # a trace that does not say (a cache another tree filled: no kernel under
    # the scopes) falls back on what `recompute` means: the same two passes
    silent = dict(m, program_trace=dict(m["program_trace"], scope_kernels={}))
    assert lfm2_readings.gmm_roofline_pct(silent) == pytest.approx(56.262037707823154)
    # a program that runs each forward once: 9 products and 2 flash kernels a layer
    once = dict(m, program_trace=dict(m["program_trace"], scope_kernels={
        "moe_experts": 36.0, "flash_attention": 2.0}))
    assert lfm2_readings.gmm_roofline_pct(once) == pytest.approx(56.262037707823154 * 9 / 12)
    assert lfm2_readings.flash_roofline_pct(once) == pytest.approx(
        20.60309470005759 * batch / 2 * 7 / 9)


@pytest.mark.parametrize("passes, kernels, backward, a_pass", [
    (2, 12.0, 6, 3), (1, 9.0, 6, 3),          # a layer's grouped products
    (2, 3.0, 1, 1), (1, 2.0, 1, 1),           # its flash pair
    (2, 4.0, 0, 2), (1, 2.0, 0, 2),           # the sparse-attention index's sets and loss
])
def test_forward_passes_are_the_traces(passes, kernels, backward, a_pass):
    assert kernel_costs.forward_passes(kernels, backward, a_pass, otherwise=7) == passes
    # neither one pass nor two, or no trace to ask: what the caller says
    assert kernel_costs.forward_passes(kernels + 0.4, backward, a_pass, otherwise=7) == 7
    assert kernel_costs.forward_passes(None, backward, a_pass, otherwise=7) == 7


# PERF.md section 5's hand readings of each expert cell (my chip runs, PR 45):
# `moe_experts` ms a step, pairs a layer a step from the counters, and by hand
# the share: 4 layers x 12 products x 2 x rows x hidden x width / 197 TFLOP/s
@pytest.mark.parametrize("workload, spent, rows, by_hand", [
    ("deepseek-v2-lite.pretrain-1chip-b1-s8192", 78.65, 49152.0,
     100 * 48 * 2 * 49152 * 2048 * 1408 / 197e12 / 78.65e-3),       # 87.8
    ("keye-vl2-30b-a3b.pretrain-1chip-b1-s8192", 62.73, 65536.0,
     100 * 48 * 2 * 65536 * 2048 * 768 / 197e12 / 62.73e-3),        # 80.0
    ("kimi-linear-48b-a3b.pretrain-1chip-b2-s4096", 11.80, 2048.0, None),
    ("lfm2-24b-a2b.pretrain-1chip-b2-s4096", 17.43, 27983.0 / 4, None),
])
def test_the_expert_readers_in_every_cell_that_runs_the_layer(workload, spent, rows, by_hand):
    from benchmarks import lfm2_readings
    cell = harness.load_cell(workload)
    m = {"run": {"trace": {"steps": 20}}, "peak": PEAK, "cell": cell,
         "program_trace": {"scope_ms": {"moe_experts": spent, "moe_route": 20.02,
                                        "moe_combine": 21.89, "flash_attention": 70.49},
                           "held_ms": {}, "scope_kernels": {"moe_experts": 48.0}},
         "moe_routing": {"rows_per_step": 4 * rows, "rows_per_layer_step": rows,
                         "layers": 4, "load_max_over_mean": 1.28}}
    read = {name: harness.load_reader("layer_metrics", name)(m) for name in (
        "moe_experts_ms.train", "moe_route_ms.train", "moe_rows_here_per_step",
        "moe_load_max_over_mean", "moe_gmm_roofline_pct", "attention_flash_pct")}
    assert read["moe_experts_ms.train"] == spent
    assert read["moe_route_ms.train"] == pytest.approx(41.91)
    assert read["moe_rows_here_per_step"] == 4 * rows
    assert read["moe_load_max_over_mean"] == 1.28
    assert read["attention_flash_pct"] == 100.0
    # pairs computed, never buffer rows: no share passes 100
    assert 0 < read["moe_gmm_roofline_pct"] < 100
    if by_hand is not None:      # compute-bound at a rank's rows
        assert read["moe_gmm_roofline_pct"] == pytest.approx(by_hand)
        assert 80 <= by_hand <= 88
    # the manifest lists the cell for each of them
    listed = {m_["name"]: m_.get("workloads", ()) for m_ in cell["bench"]["per_layer"]}
    assert all(workload in listed[name] for name in read)
