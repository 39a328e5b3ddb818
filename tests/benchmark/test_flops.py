"""FLOP and byte counts against values worked by hand from the shapes."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import flops, harness  # noqa: E402
from benchmarks.families import bert, gpt  # noqa: E402

GPT = harness.load_cell("gpt3-1p3b.pretrain-1chip-b2-s1024")
BERT = harness.load_cell("bert-base.finetune-fit-b64-s128")


def test_gpt_matmul_weights_and_flops_per_token():
    # per layer 4 x 2048^2 + 2 x 2048 x 8192 = 50,331,648; six layers;
    # the tied head 2048 x 50304 = 103,022,592 once
    per_token, per_sequence = gpt.matmul_shapes(GPT["cfg"])
    assert per_sequence == []
    assert flops.matmul_weights(per_token) == 6 * 50331648 + 103022592 == 405012480
    # causal attention: 12 x 6 x 1024 x 2048 / 2 = 75,497,472
    assert gpt.flops_per_token(GPT["cfg"], GPT["job"]) == pytest.approx(
        6 * 405012480 + 75497472)


def test_bert_matmul_weights_and_flops_per_token():
    # per layer 4 x 768^2 + 2 x 768 x 3072 = 7,077,888; twelve layers
    per_token, per_sequence = bert.matmul_shapes(BERT["cfg"])
    assert flops.matmul_weights(per_token) == 12 * 7077888 == 84934656
    # pooler 768^2 and classifier 768 x 2 see one position in 128
    assert flops.matmul_weights(per_sequence) == 589824 + 1536
    assert bert.flops_per_token(BERT["cfg"], BERT["job"]) == pytest.approx(
        6 * (84934656 + 591360 / 128) + 12 * 12 * 128 * 768)


def test_embedding_gathers_and_position_tables_are_not_matmuls():
    shapes = gpt.reference.param_shapes(GPT["cfg"])
    n_params = sum(int(__import__("numpy").prod(s)) for s, _ in shapes.values())
    assert n_params == 407273472                      # chip_smoke.py's count, PR 23
    per_token, _ = gpt.matmul_shapes(GPT["cfg"])
    assert 6 * flops.matmul_weights(per_token) < 6 * n_params   # bench.py's 6P


def test_optimizer_bytes():
    # bf16 weight and gradient, float32 master and two moments: 28 B a parameter
    assert flops.optimizer_bytes_per_step(1000, 2, True) == 28000
    # float32 without masters: read w, g, m, v; write w, m, v
    assert flops.optimizer_bytes_per_step(1000, 4, False) == 28000
