"""DroplessMoELayer against the plain reference's expert layer
(benchmarks/reference/lfm2_moe.py): the whole layer, the shares of an
expert-parallel deployment adding up to it, no pair dropped under any
imbalance, and what the expert bias may and may not change."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import paddle_tpu as paddle  # noqa: E402
from benchmarks.reference import lfm2_moe as ref  # noqa: E402
from paddle_tpu.incubate.moe import DroplessMoELayer, _route_plan  # noqa: E402

H, F, E, K = 32, 16, 16, 4
RNG = np.random.default_rng(23)


def weights(held, bias=None):
    """Reference leaves of one expert layer that holds `held` of E experts,
    cut from one set of all E experts' weights."""
    rng = np.random.default_rng(5)
    full = {"gate_w": rng.standard_normal((H, E)), "e_w1": rng.standard_normal((E, H, F)),
            "e_w3": rng.standard_normal((E, H, F)), "e_w2": rng.standard_normal((E, F, H))}
    p = {"l0.gate_w": 0.5 * full["gate_w"],
         "l0.expert_bias": np.zeros(E) if bias is None else bias}
    for k in ("e_w1", "e_w3", "e_w2"):
        p["l0." + k] = 0.3 * full[k][list(held)]
    return {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}


def cfg_of(held):
    return {"held_experts": list(held), "num_experts_per_tok": K,
            "routed_scaling_factor": 1.0}


def layer_of(p, held):
    layer = DroplessMoELayer(H, F, E, K, held_experts=held)
    for name, leaf in (("gate.weight", "gate_w"), ("expert_bias", "expert_bias"),
                       ("w1", "e_w1"), ("w3", "e_w3"), ("w2", "e_w2")):
        layer.state_dict()[name].set_value(paddle.Tensor(p["l0." + leaf]))
    return layer


def call(layer, x):
    """The layer's result, its load added to its counters as a model does."""
    out, load = layer(x)
    layer.record_load(load)
    return out


def reference_out(p, x, held):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.expert_ff(p, "l0.", jnp.asarray(x), cfg_of(held), jnp.matmul))


@pytest.mark.parametrize("held", [range(E), [0, 1, 2, 3], [5, 9, 2]],
                         ids=["all", "first-four", "scattered"])
def test_layer_and_its_gradients_match_the_reference(held):
    held = list(held)
    p, x = weights(held), RNG.standard_normal((2, 40, H)).astype(np.float32)
    layer = layer_of(p, held)
    xt = paddle.to_tensor(x, stop_gradient=False)
    out = call(layer, xt)
    np.testing.assert_allclose(np.asarray(out._val), reference_out(p, x, held),
                               rtol=2e-5, atol=2e-5)
    paddle.sum(paddle.sin(out)).backward()

    def total(p, x):
        return jnp.sum(jnp.sin(ref.expert_ff(p, "l0.", x, cfg_of(held), jnp.matmul)))
    with jax.default_matmul_precision("highest"):
        gp, gx = jax.grad(total, argnums=(0, 1))(p, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(xt.grad._val), np.asarray(gx), rtol=1e-4, atol=1e-4)
    for name, leaf in (("gate.weight", "gate_w"), ("w1", "e_w1"), ("w3", "e_w3"),
                       ("w2", "e_w2")):
        got = layer.state_dict()[name].grad
        np.testing.assert_allclose(np.asarray(got._val), np.asarray(gp["l0." + leaf]),
                                   rtol=1e-4, atol=1e-4, err_msg=name)
    assert layer.expert_bias.grad is None and layer.expert_bias.stop_gradient


def test_the_eight_shares_add_up_to_the_uncut_layer():
    # model-configs guide, section 4: expert-parallel 8 with contiguous
    # placement; nothing here is computed alike on every chip, so nothing is
    # counted once
    x = RNG.standard_normal((3, 24, H)).astype(np.float32)
    whole = reference_out(weights(range(E)), x, range(E))
    shares = np.zeros_like(whole)
    rows = 0
    for rank in range(8):
        held = list(range(rank * E // 8, (rank + 1) * E // 8))
        layer = layer_of(weights(held), held)
        shares += np.asarray(call(layer, paddle.to_tensor(x))._val)
        rows += float(layer.rows_total._val)
    np.testing.assert_allclose(shares, whole, rtol=5e-5, atol=5e-5)
    assert rows == 3 * 24 * K            # every pair computed on exactly one rank


def test_no_row_is_dropped_when_every_token_picks_the_same_four_experts():
    held = [0, 1, 2, 3]
    bias = np.zeros(E)
    bias[held] = 10.0                    # the bias decides the pick for every token
    p, x = weights(held, bias), RNG.standard_normal((2, 300, H)).astype(np.float32)
    layer = layer_of(p, held)
    out = call(layer, paddle.to_tensor(x))
    assert float(layer.rows_total._val) == 2 * 300 * K     # the worst case, whole
    np.testing.assert_allclose(np.asarray(out._val), reference_out(p, x, held),
                               rtol=2e-5, atol=2e-5)
    # the fixed-capacity layer beside it would keep ceil(k N / E x 1.25) rows
    # an expert and drop the rest; here each expert took all 600
    assert float(layer.imbalance_total._val) == pytest.approx(1.0)


def test_no_token_picks_a_held_expert():
    held = [12, 13]
    bias = np.zeros(E)
    bias[:4] = 10.0
    p, x = weights(held, bias), RNG.standard_normal((1, 50, H)).astype(np.float32)
    layer = layer_of(p, held)
    xt = paddle.to_tensor(x, stop_gradient=False)
    out = call(layer, xt)
    assert not np.asarray(out._val).any() and float(layer.rows_total._val) == 0
    paddle.sum(out).backward()
    assert not np.asarray(layer.w1.grad._val).any()
    assert np.isfinite(np.asarray(xt.grad._val)).all()


def test_expert_bias_changes_the_pick_and_never_the_weights():
    held = list(range(E))
    x = RNG.standard_normal((1, 64, H)).astype(np.float32)
    p = weights(held)
    bias = np.zeros(E)
    bias[7] = 10.0                       # expert 7 now wins a place in every token
    pb = weights(held, bias)
    idx0, w0 = ref.route(p, "l0.", jnp.asarray(x), cfg_of(held), jnp.matmul)
    idx1, w1 = ref.route(pb, "l0.", jnp.asarray(x), cfg_of(held), jnp.matmul)
    assert (np.asarray(idx1) == 7).any(axis=-1).all()
    assert not (np.asarray(idx0) == 7).any(axis=-1).all()
    # the weights are the un-biased scores of whatever was picked, over their sum
    s = jax.nn.sigmoid(jnp.asarray(x) @ p["l0.gate_w"])
    picked = jnp.take_along_axis(s, idx1, axis=-1)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(
        picked / (picked.sum(-1, keepdims=True) + 1e-6)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(layer_of(pb, held)(paddle.to_tensor(x))[0]._val),
                               reference_out(pb, x, held), rtol=2e-5, atol=2e-5)


def test_plan_lays_each_experts_rows_in_its_own_tiles():
    tm, n_held, rows = 8, 3, 8 * 12
    scores = jnp.asarray(RNG.random((20, 6)), jnp.float32)
    lookup = np.array([-1, 0, -1, 1, 2, -1], np.int32)
    idx, pair_row, row_pair, row_valid, tile_group, num_tiles, counts = _route_plan(
        scores, jnp.zeros(6), top_k=2, lookup=lookup, n_held=n_held, tm=tm, rows=rows)
    idx, pair_row, row_pair, row_valid = map(np.asarray, (idx, pair_row, row_pair, row_valid))
    local = lookup[idx]
    assert int(np.asarray(counts).sum()) == (local >= 0).sum() == row_valid.sum()
    # a held pair's row holds that pair; a pair not held points past the buffer
    for t in range(20):
        for j in range(2):
            r = pair_row[t, j]
            if local[t, j] < 0:
                assert r == rows
            else:
                assert row_valid[r] and row_pair[r] == 2 * t + j
                assert np.asarray(tile_group)[r // tm] == local[t, j]
    assert int(num_tiles) == sum(max(1, -(-int(c) // tm)) for c in np.asarray(counts))


def test_held_experts_are_checked():
    with pytest.raises(Exception, match="held_experts"):
        DroplessMoELayer(H, F, E, K, held_experts=[1, 1])
    with pytest.raises(Exception, match="held_experts"):
        DroplessMoELayer(H, F, E, K, held_experts=[E])
