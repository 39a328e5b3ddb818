"""The Keye-VL-2.0 family's required FLOPs and the operations and bytes of
what it adds (benchmarks/kernel_costs_keye.py), against values worked by hand
from the shapes at the cell's size and at a size small enough to count on
paper, and what the four readers give where there is nothing to read."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import flops, harness, kernel_costs_keye as costs  # noqa: E402

CELL = harness.load_cell("keye-vl2-30b-a3b.pretrain-1chip-b1-s8192")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("seq, topk, in_sets, causal", [
    (4, 2, 1 + 2 + 2 + 2, 10),                 # rows hold 1, 2, 2, 2 keys
    (3, 8, 6, 6),                              # topk above the row length: every causal key
    (8192, 2048, 14681088, 33558528),          # the cell: 2048 x 2049 / 2 + 6144 x 2048
])
def test_pairs_by_hand(seq, topk, in_sets, causal):
    assert costs.set_pairs(seq, topk) == in_sets
    assert costs.causal_pairs(seq) == causal
    assert costs.set_pairs(seq, topk) <= costs.causal_pairs(seq)


@pytest.mark.parametrize("seq, topk", [(4, 2), (8192, 2048)])
def test_a_layers_required_flops(seq, topk):
    in_sets, causal = costs.set_pairs(seq, topk), costs.causal_pairs(seq)
    # forward: scores and values over the sets, 32 heads of 128; the index's
    # one product at every causal pair, 16 heads of 64; backward twice that;
    # the loss's second pass over the main scores once
    main, index = 2 * 2 * 32 * 128 * in_sets, 2 * 16 * 64 * causal
    assert costs.main_forward_flops(seq, 32, 128, topk) == main
    assert costs.index_forward_flops(seq, 16, 64) == index
    assert costs.layer_train_flops(seq, 32, 128, 16, 64, topk) == pytest.approx(
        3 * (main + index) + 2 * 32 * 128 * in_sets)
    # never the dense triangle: less than what a causal pair would count
    if seq > topk:
        assert costs.main_forward_flops(seq, 32, 128, topk) < 2 * 2 * 32 * 128 * causal


def test_matmul_weights_and_flops_per_token():
    family, cfg, job = CELL["family"], CELL["cfg"], CELL["job"]
    per_token, per_sequence = family.matmul_shapes(cfg)
    assert per_sequence == []
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512            # q, o; k, v
    indexer = 2048 * 1024 + 2048 * 64 + 2048 * 16
    # router; all 8 picks of a token are computed here (the held experts
    # stand in for the absent ones): eight experts a token a layer
    assert cfg["absent_experts"] == "stand_in"
    experts = 2048 * 128 + 8 * 3 * 2048 * 768
    head = 2048 * 18992                                     # untied, once
    assert (attention, indexer, experts) == (18874368, 2260992, 38010880)
    assert flops.matmul_weights(per_token) == 4 * (attention + indexer + experts) + head
    assert flops.matmul_weights(per_token) == 275480576
    layer = costs.layer_train_flops(8192, 32, 128, 16, 64, 2048)
    assert layer == pytest.approx(1048055906304)
    assert family.flops_per_token(cfg, job) == pytest.approx(
        6 * 275480576 + 4 * layer / 8192)
    assert family.flops_per_token(cfg, job) == pytest.approx(2164629504)
    # with the absent experts' terms dropped: 16 held of 128 at 8 a token
    # weigh one expert a token a layer
    dropped = dict(cfg, absent_experts="drop")
    assert flops.matmul_weights(family.matmul_shapes(dropped)[0]) == 143360000
    assert family.flops_per_token(dropped, job) == pytest.approx(1371906048)
    assert family.tokens_per_step(job) == 8192
    # one layer, by hand
    assert family.flops_per_token(dict(cfg, num_layers=1), job) == pytest.approx(
        6 * (attention + indexer + experts + head) + layer / 8192)


def test_the_rooflines_count_every_pass():
    in_sets, causal = 14681088, 33558528
    product = 2 * 32 * 128 * in_sets
    fwd, bwd = 2 * product / 197e12, 5 * product / 197e12   # compute-bound
    assert costs.set_attention_seconds(1, 8192, 32, 4, 128, 2048, 1, PEAK) == \
        pytest.approx(fwd + bwd)
    assert costs.set_attention_seconds(1, 8192, 32, 4, 128, 2048, 2, PEAK) == \
        pytest.approx(2 * fwd + bwd)
    # under the dense causal pair's count by the pairs' ratio
    from benchmarks import kernel_costs
    dense = kernel_costs.causal_attention_seconds(1, 32, 4, 8192, 128, 2, PEAK)
    assert costs.set_attention_seconds(1, 8192, 32, 4, 128, 2048, 2, PEAK) == \
        pytest.approx(dense * in_sets / (8192 * 8192 / 2))
    proj = 2 * 8192 * 2048 * (1024 + 64 + 16)
    scores, second = 2 * 16 * 64 * causal, 2 * 32 * 128 * in_sets
    fwd, bwd = (proj + scores + second) / 197e12, 2 * (proj + scores) / 197e12
    assert costs.index_seconds(1, 8192, 2048, 32, 128, 16, 64, 2048, 2, PEAK) == \
        pytest.approx(2 * fwd + bwd)
    assert costs.index_seconds(2, 8192, 2048, 32, 128, 16, 64, 2048, 1, PEAK) == \
        pytest.approx(2 * (fwd + bwd))


@pytest.mark.parametrize("kernels, flash_passes, index_passes", [
    # since PR 43 the core stands outside the rematerialised regions: a layer
    # runs one forward and one backward flash kernel, one sets and one loss kernel
    ({"flash_attention": 8.0, "dsa_index": 4.0, "dsa_index_loss": 4.0}, 1, 1),
    # before it, the whole block was rerun: two forwards, the sets twice, the
    # loss with its gradient and alone
    ({"flash_attention": 12.0, "dsa_index": 8.0, "dsa_index_loss": 8.0}, 2, 2),
    # a trace that does not say: one pass, whatever `recompute` is
    ({}, 1, 1), ({"flash_attention": 9.0, "dsa_index": 5.0}, 1, 1),
])
def test_the_shares_count_the_passes_the_trace_holds(kernels, flash_passes, index_passes):
    assert CELL["cfg"]["recompute"]
    m = {"run": {"trace": {"steps": 20}}, "peak": PEAK, "cell": CELL, "program_trace": {
        "scope_ms": {"flash_attention": 100.0, "dsa_index": 60.0, "dsa_index_loss": 40.0},
        "held_ms": {}, "scope_kernels": kernels}}
    shares = {name: costs.read_share(m, name)
              for name in ("dsa_flash_roofline_pct", "dsa_index_roofline_pct")}
    assert shares == costs.cell_shares(CELL, m["program_trace"]["scope_ms"], PEAK,
                                       flash_passes, index_passes)
    assert shares["dsa_flash_roofline_pct"] == pytest.approx(
        100 * 4 * costs.set_attention_seconds(1, 8192, 32, 4, 128, 2048, flash_passes, PEAK)
        * 1e3 / 100.0)
    assert shares["dsa_index_roofline_pct"] == pytest.approx(
        100 * 4 * costs.index_seconds(1, 8192, 2048, 32, 128, 16, 64, 2048, index_passes, PEAK)
        * 1e3 / 100.0)
    assert all(0 < v < 100 for v in shares.values())


def test_the_shares_at_the_cells_own_readings():
    # my chip run, PR 45 (ledger): 82.55 and 50.99 ms a step, where two
    # forward passes read 26.6 and 26.4; the trace holds one
    scope_ms = {"flash_attention": 82.54526795, "dsa_index": 13.5742894,
                "dsa_index_loss": 37.4204689}
    assert costs.cell_shares(CELL, scope_ms, PEAK, 2, 2)["dsa_flash_roofline_pct"] == \
        pytest.approx(26.62516320036734)
    shares = costs.cell_shares(CELL, scope_ms, PEAK)
    assert shares["dsa_flash_roofline_pct"] == pytest.approx(26.62516320036734 * 7 / 9)
    assert shares["dsa_flash_roofline_pct"] == pytest.approx(20.7, abs=0.05)
    assert shares["dsa_index_roofline_pct"] == pytest.approx(17.42, abs=0.01)


def test_cell_shares_and_readers_with_nothing_to_read():
    # a trace without the scopes (a parent of the PR that added them): no entry
    assert costs.cell_shares(CELL, {"linear": 60.0}, PEAK) == {}
    untraced = {"run": {"trace": None}, "peak": PEAK}
    for name in ("dsa_index_ms.train", "dsa_index_roofline_pct", "dsa_flash_roofline_pct"):
        assert harness.load_reader("layer_metrics", name)(dict(untraced)) is None


def test_the_counter_reader(monkeypatch):
    from benchmarks import program
    read = harness.load_reader("layer_metrics", "dsa_selected_pairs_per_step")
    monkeypatch.setattr(program, "registry", lambda: {"counters": {
        "dsa.selected_pairs_total": 10 * 4 * 14681088.0, "dsa.calls_total": 40.0}})
    assert read({"cell": CELL}) == pytest.approx(4 * 14681088.0)   # ten steps of four layers
    monkeypatch.setattr(program, "registry", lambda: {"counters": {}})
    assert read({"cell": CELL}) is None                     # a program without the counter
    monkeypatch.setattr(program, "registry", lambda: None)
    assert read({"cell": CELL}) is None
