"""The Kimi Linear family's required FLOPs and its kernels' operations and
bytes (benchmarks/kernel_costs_kimi.py), against values worked by hand from
the shapes, and what the three readers give where there is nothing to read."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import flops, harness, kernel_costs_kimi as costs  # noqa: E402

CELL = harness.load_cell("kimi-linear-48b-a3b.pretrain-1chip-b2-s4096")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_matmul_weights_and_flops_per_token():
    family, cfg, job = CELL["family"], CELL["cfg"], CELL["job"]
    per_token, per_sequence = family.matmul_shapes(cfg)
    assert per_sequence == []
    # a KDA layer's mixer: q, k, v, o; two low-rank gates; a beta a head
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    # the latent layer's: q of 32 x 192, the latent of 512 + 64, its
    # expansion to 32 x (128 + 128), o
    mla = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    # router, the shared expert once, 8 held of 256 at 8 a token: a quarter
    # of an expert a token a layer
    experts = 2304 * 256 + 3 * 2304 * 1024 + 0.25 * 3 * 2304 * 1024
    head = 2304 * 20480                                  # untied, once
    assert kda == 39460864 and mla == 29114368 and experts == 9437184
    assert flops.matmul_weights(per_token) == pytest.approx(
        3 * kda + mla + 4 * experts + head)
    # latent attention's causal products: 12 x 4096 x 32 x (192 + 128) / 2 / 2
    attention = 12 * 4096 * 32 * 160 / 2
    # the KDA state, a layer: 3 products of 2 x 128 x 128 a head, 32 heads,
    # forward; three times that in training
    state = 3 * 3 * 2 * 128 * 128 * 32
    assert attention == 125829120 and state == 9437184
    assert family.flops_per_token(cfg, job) == pytest.approx(
        6 * 232431616 + attention + 3 * state)
    assert family.flops_per_token(cfg, job) == pytest.approx(1548730368)
    assert family.tokens_per_step(job) == 8192


def test_one_layer_of_each_kind_by_hand():
    family, job = CELL["family"], CELL["job"]
    head = 6 * 2304 * 20480
    experts = 6 * 9437184
    only_kda = dict(CELL["cfg"], num_layers=1, first_layer=4)       # published layer 5
    only_mla = dict(CELL["cfg"], num_layers=1, first_layer=7)       # published layer 8
    assert family.layer_kinds(only_kda) == [("kda", "experts")]
    assert family.layer_kinds(only_mla) == [("full_attention", "experts")]
    assert family.flops_per_token(only_kda, job) == pytest.approx(
        head + experts + 6 * 39460864 + 9437184)
    assert family.flops_per_token(only_mla, job) == pytest.approx(
        head + experts + 6 * 29114368 + 125829120)
    dense = dict(only_kda, first_k_dense_replace=1)
    assert family.flops_per_token(dense, job) == pytest.approx(
        head + 6 * 3 * 2304 * 9216 + 6 * 39460864 + 9437184)


def test_kda_pass_flops():
    # one chunk of one head, 128 x 128: key-key and query-key products of 64
    # rows against 40 keys on average; the solve; U and W on the triangle;
    # three products against the state; the triangle times Delta
    chunk = (2 * 2 * 64 * 40 * 128 + 2 * 64 ** 3 / 3 + 2 * 2080 * 256
             + 3 * 2 * 64 * 128 * 128 + 2 * 2080 * 128)
    assert costs.kda_pass_flops(64, 128, 128) == pytest.approx(chunk)
    assert costs.kda_pass_flops(64, 128, 128) == pytest.approx(9374378.67, rel=1e-6)
    assert costs.kda_intra_flops(64, 128, 128) == pytest.approx(
        chunk - 3 * 2 * 64 * 128 * 128 - 2 * 2080 * 128)
    # linear in the tokens: a layer's pass at 2 x 4096 x 32
    assert costs.kda_pass_flops(2 * 4096 * 32, 128, 128) == pytest.approx(4096 * chunk)


def test_kda_layer_seconds_count_every_pass():
    pairs = 2 * 4096 * 32
    forward = costs.kda_pass_flops(pairs, 128, 128)
    operands = pairs * (3 * 128 * 2 + 4 * 128 + 2)
    states = pairs / 64 * 128 * 128 * 4
    fwd = max(forward / 197e12, (operands + pairs * 128 * 2 + states) / 819e9)
    bwd = max((costs.kda_intra_flops(pairs, 128, 128) + 2 * forward) / 197e12,
              (2 * operands + pairs * 128 * 2 + states) / 819e9)
    assert costs.kda_layer_seconds(2, 4096, 32, 128, 128, 1, PEAK) == \
        pytest.approx(fwd + bwd)
    assert costs.kda_layer_seconds(2, 4096, 32, 128, 128, 2, PEAK) == \
        pytest.approx(2 * fwd + bwd)
    # the operands, not the products, bound a forward pass: 0.8 ms against 0.2
    assert fwd == pytest.approx((operands + pairs * 128 * 2 + states) / 819e9)


def test_latent_attention_seconds():
    unit = 2 * 2 * 32 * 4096 * 4096 / 2                    # a product a width
    forward = unit * (192 + 128) / 197e12
    backward = unit * (3 * 192 + 2 * 128) / 197e12
    assert costs.latent_attention_seconds(2, 32, 4096, 192, 128, 1, PEAK) == \
        pytest.approx(forward + backward)
    assert costs.latent_attention_seconds(2, 32, 4096, 192, 128, 2, PEAK) == \
        pytest.approx(2 * forward + backward)
    # equal sizes: kernel_costs.causal_attention_seconds' seven products
    from benchmarks import kernel_costs
    assert costs.latent_attention_seconds(2, 32, 4096, 64, 64, 2, PEAK) == \
        pytest.approx(kernel_costs.causal_attention_seconds(2, 32, 32, 4096, 64, 2, PEAK))


def test_cell_shares_and_readers_with_nothing_to_read():
    shares = costs.cell_shares(CELL, {"kda": 30.0, "flash_attention": 20.0}, PEAK)
    assert shares["kda_roofline_pct"] == pytest.approx(
        100 * 3 * costs.kda_layer_seconds(2, 4096, 32, 128, 128, 2, PEAK) * 1e3 / 30.0)
    assert shares["mla_flash_roofline_pct"] == pytest.approx(
        100 * costs.latent_attention_seconds(2, 32, 4096, 192, 128, 2, PEAK) * 1e3 / 20.0)
    assert all(0 < v < 100 for v in shares.values())
    # a trace without the scope (a parent of the PR that added it): no entry
    assert costs.cell_shares(CELL, {"linear": 60.0}, PEAK) == {}
    untraced = {"run": {"trace": None}, "peak": PEAK}
    for name in ("kda_ms.train", "kda_roofline_pct", "mla_flash_roofline_pct"):
        assert harness.load_reader("layer_metrics", name)(dict(untraced)) is None


def test_the_readers_score_the_cell_the_run_hands_them():
    # a traced run's `measured` as run.py makes it: the cell that ran is in it
    # (PR 46), so the shares take its sizes and not a guess from a directory
    # (my chip run, PR 45: the scopes' ms a step)
    traced = {"run": {"trace": {"steps": 20}}, "peak": PEAK, "cell": CELL,
              "program_trace": {"scope_ms": {"kda": 100.6, "flash_attention": 17.34},
                                "held_ms": {}, "scope_kernels": {"flash_attention": 3.0}}}
    read = {name: harness.load_reader("layer_metrics", name)(dict(traced))
            for name in ("kda_ms.train", "kda_roofline_pct", "mla_flash_roofline_pct")}
    assert read["kda_ms.train"] == 100.6
    assert read == dict(costs.cell_shares(CELL, traced["program_trace"]["scope_ms"], PEAK),
                        **{"kda_ms.train": 100.6})
    assert read["kda_roofline_pct"] == pytest.approx(8.56, abs=0.05)
    assert read["mla_flash_roofline_pct"] == pytest.approx(46.3, abs=0.3)
