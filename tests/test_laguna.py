"""The Laguna model (paddle_tpu/text/models/laguna.py) against the plain
reference (benchmarks/reference/laguna.py) on seeded weights, at a small size
on the CPU: the rotary rules by layer type against the reference's tables, the
band of the flash pair against a masked square in both paths and both passes,
the mixer with its gate, the expert layer and its shares, the whole model's
loss and gradients leaf by leaf and three AdamW steps, the faults the
comparison has to catch, what a rematerialised step stages and counts, and
the plain pair's staged program at the four flash cells' shapes as it was.

Tolerances. In float32 the program does the reference's arithmetic in another
order (a sorted buffer against a dense sum, one softmax against blocks of
rows): 1e-5 of a leaf's norm holds the loss and the layers, 1e-4 every leaf's
gradient through five blocks (the projections drawn at N(0, 0.5) make the
softmax sharp, and a sharp softmax carries rounding further). Every fault
below moves a number by at least 30 times the tolerance it is held to."""
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402
import rematerialised_step  # noqa: E402
from benchmarks import harness  # noqa: E402
from benchmarks.reference import adamw  # noqa: E402
from benchmarks.reference import laguna as ref  # noqa: E402
from paddle_tpu.ops import attention  # noqa: E402
from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402

CELL = "laguna-xs2.pretrain-1chip-b1-s8192"
SEED = 7
TOL, GRAD_TOL = 1e-5, 1e-4
YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
        "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5}


def tiny(**over):
    """The cell's configuration with its widths cut, here and nowhere else:
    the five layers (full and dense, three window layers, full), 6 and 8
    query heads over 2 key heads of 16, a window of 16 in rows of 64, 16
    published experts of which 4 are held, 3 picked a token."""
    cell = harness.load_cell(CELL)
    cfg = cell["cfg"]
    cfg.update(hidden_size=64, head_dim=16, num_key_value_heads=2,
               num_attention_heads_per_layer=[
                   6 if kind == "full_attention" else 8 for kind in cfg["layer_types"]],
               sliding_window=16, intermediate_size=96, moe_intermediate_size=32,
               shared_expert_intermediate_size=32, vocab_size=600,
               num_experts_published=16, num_experts=4, held_experts=[0, 1, 2, 3],
               num_experts_per_tok=3, weights_dtype="float32", recompute=False)
    # YaRN's ramp inside the 4 pairs a head of 16 turns under factor 0.5
    cfg["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=32, beta_fast=4)
    cfg.update(over)
    cell["job"].update(batch=2, seq=64)
    return cell


def seeded(cell, dtype="float32", sharp=True, scale=8):
    """Seeded leaves: with `sharp` the query and key projections at
    N(0, 0.5), so that attention is far from uniform and a fault in a
    rotation or in the band shows; the other matrices `scale` times the
    benchmark's 0.02 (8: unit-size products at hidden 64); the expert bias
    off zero."""
    shapes = {}
    for k, (shape, init) in cell["family"].reference.param_shapes(cell["cfg"]).items():
        if sharp and k.endswith(("q_w", "k_w")):
            init = 0.5
        elif not isinstance(init, str):
            init = scale * init
        shapes[k] = (shape, init)
    p = harness.init_params(shapes, SEED, dtype)
    rng = np.random.default_rng(SEED)
    return {k: jnp.asarray(rng.normal(0, 0.002, v.shape), v.dtype)
            if k.endswith("expert_bias") else v for k, v in p.items()}


def build(cell, p):
    family, cfg = cell["family"], cell["cfg"]
    model = family.build_model(cfg)
    if cfg["weights_dtype"] == "bfloat16":
        model.bfloat16()
    names = family.program_names(cfg)
    missing, unexpected = model.set_state_dict(
        {names[k]: paddle.Tensor(v) for k, v in p.items()})
    assert not missing and not unexpected
    return model, names


def norm_gap(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(jnp.ravel(a - b))
                 / max(float(jnp.linalg.norm(jnp.ravel(b))), 1e-12))


def batch(cell, n=1):
    stream = cell["family"].Stream(cell["cfg"], cell["job"], SEED)
    return [stream.next() for _ in range(n)]


@pytest.fixture(scope="module")
def cell():
    return tiny()


@pytest.fixture(scope="module")
def leaves(cell):
    return seeded(cell)


@pytest.fixture(scope="module")
def model(cell, leaves):
    return build(cell, leaves)


# ---------------------------------------------------------------------------
# positions

def test_partial_rotary_under_yarn_against_the_references_tables():
    """Laguna-XS.2's full layers: entries 0-63 of 128 turn, entry i with
    entry i + 32, under YaRN's blend over those 64 with cos and sin times the
    given attention_factor; entries 64-127 pass."""
    cfg = {"head_dim": 128, "rope_parameters": {"full_attention": YARN}}
    r, freqs, carried = ref.rotary_rule(cfg, "full_attention")
    assert (r, carried) == (64, 1.4158883083359672)
    f = F.yarn_frequencies(64, 500000, YARN)
    np.testing.assert_array_equal(f, freqs)
    # pair n turns beta times over 4096 positions at n = 64 ln(4096 / (2 pi
    # beta)) / (2 ln 5e5): 5.66 at beta_fast 64, 15.80 at beta_slow 1. Pairs
    # 0-5 keep their frequency, 16-31 are divided by 64, a ramp between
    theta = lambda n: 500000.0 ** (-2 * n / 64)          # noqa: E731
    by_hand = {0: 1.0, 5: theta(5), 10: theta(10) * (6 / 11 + 5 / 11 / 64),
               16: theta(16) / 64, 31: theta(31) / 64}
    for n, want in by_hand.items():
        assert f[n] == pytest.approx(want, rel=1e-6), n
    rng = np.random.default_rng(0)
    q = rng.normal(size=(2, 24, 6, 128)).astype(np.float32)
    k = rng.normal(size=(2, 24, 2, 128)).astype(np.float32)
    got_q, got_k = F.rotary_position_embedding(
        paddle.to_tensor(q), paddle.to_tensor(k), theta=500000.0, rope_scaling=YARN,
        rotary_dim=64)
    rule = (r, freqs, carried)
    np.testing.assert_allclose(np.asarray(got_q._val), ref.rotate(jnp.asarray(q), rule),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_k._val), ref.rotate(jnp.asarray(k), rule),
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got_q._val)[..., 64:], q[..., 64:])
    # position 0 is turned by nothing and carries the factor alone
    np.testing.assert_allclose(np.asarray(got_q._val)[:, 0, :, :64],
                               q[:, 0, :, :64] * carried, rtol=1e-6)
    # the window layers' rule: plain frequencies over the whole head, which is
    # the call as it was before this model
    r, freqs, carried = ref.rotary_rule(
        {"head_dim": 128, "rope_parameters": {"sliding_attention": {
            "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}}},
        "sliding_attention")
    assert (r, carried) == (128, 1.0)
    plain_q, _ = F.rotary_position_embedding(paddle.to_tensor(q), paddle.to_tensor(k),
                                             theta=10000.0, rotary_dim=128)
    np.testing.assert_allclose(np.asarray(plain_q._val),
                               ref.rotate(jnp.asarray(q), (r, freqs, carried)), atol=2e-5)
    whole_q, _ = F.rotary_position_embedding(paddle.to_tensor(q), paddle.to_tensor(k),
                                             theta=10000.0)
    np.testing.assert_array_equal(np.asarray(plain_q._val), np.asarray(whole_q._val))


@pytest.mark.parametrize("given", [1.25, None])
def test_a_given_attention_factor_takes_the_derived_factors_place(given):
    """cos and sin carry the `attention_factor` the dict gives, whatever
    0.1 ln(factor) + 1 comes to; a dict without one carries that (the DeepSeek
    cell's rule), which at factor 64 is the number Laguna-XS.2 publishes."""
    rope = {k: v for k, v in YARN.items() if k != "attention_factor"}
    derived = F.yarn_scales(rope)[0]
    assert derived == pytest.approx(0.1 * np.log(64) + 1) == pytest.approx(
        YARN["attention_factor"], rel=1e-12)
    if given is not None:
        rope["attention_factor"] = given
    carried = derived if given is None else given
    rng = np.random.default_rng(1)
    q = rng.normal(size=(1, 12, 6, 128)).astype(np.float32)
    k = rng.normal(size=(1, 12, 2, 128)).astype(np.float32)
    got_q, got_k = F.rotary_position_embedding(
        paddle.to_tensor(q), paddle.to_tensor(k), theta=500000.0, rope_scaling=rope,
        rotary_dim=64)
    np.testing.assert_allclose(np.asarray(got_k._val)[:, 0, :, :64],
                               k[:, 0, :, :64] * carried, rtol=1e-6)
    cfg = {"head_dim": 128, "rope_parameters": {
        "full_attention": dict(rope, attention_factor=carried)}}
    rule = ref.rotary_rule(cfg, "full_attention")
    assert rule[2] == carried
    np.testing.assert_allclose(np.asarray(got_q._val), ref.rotate(jnp.asarray(q), rule),
                               atol=2e-5)
    # a turned pair's length is the factor times what it was
    np.testing.assert_allclose(
        np.hypot(np.asarray(got_q._val)[..., :32], np.asarray(got_q._val)[..., 32:64]),
        carried * np.hypot(q[..., :32], q[..., 32:64]), rtol=2e-5)


# ---------------------------------------------------------------------------
# the band

def masked_square(q, k, v, window, scale):
    """XLA's attention with the band as its mask; `window` None is causal."""
    return attention._xla_attention(q, k, v, None, scale, True, 0.0, None,
                                    window=window)


def by_hand(q, k, v, window, scale):
    """The band written out: query t reads keys j with t - window < j <= t."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    t = jnp.arange(q.shape[1])
    keep = (t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - window)
    return jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1), v)


def operands(s, group, d=32, kv_heads=2, seed=0):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (1, s, kv_heads * group, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, s, kv_heads, d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, s, kv_heads, d), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 3), q.shape, jnp.float32)
    return q, k, v, w


@pytest.mark.parametrize("window", [1, 16, 64, 100])
def test_xlas_band_is_the_band_by_hand(window):
    q, k, v, _ = operands(64, 3)
    np.testing.assert_allclose(masked_square(q, k, v, window, 32 ** -0.5),
                               by_hand(q, k, v, window, 32 ** -0.5), atol=2e-6)


# rows of 256 with the kernels' tiles cut to 64 (128-query blocks from 256
# positions on, as 1024 from 8192): a window shorter than a tile, one that is
# no multiple of a tile and crosses several, one equal to the row and one
# longer, which are plain causal attention under the plain rule's tiles
@pytest.mark.parametrize("group", [6, 8])
@pytest.mark.parametrize("window", [24, 150, 256, 400])
def test_the_banded_pair_against_the_masked_square_both_passes(monkeypatch, group, window):
    monkeypatch.setattr(fa, "BLOCK", 64)
    monkeypatch.setattr(fa, "LONG_SEQ_Q", 256)
    s, scale = 256, 32 ** -0.5
    q, k, v, w = operands(s, group, seed=window)
    banded = window < s
    assert fa.tiles(s, s, window) == ((64, 64) if banded else (128, 64))
    assert fa.tiles(s, s) == (128, 64)

    def flash(q, k, v):
        return attention._flash_attention_diff(q, k, v, True, scale, True, window)
    out, pull = jax.vjp(flash, q, k, v)
    want, pull_want = jax.vjp(lambda *a: masked_square(*a, window, scale), q, k, v)
    assert norm_gap(out, want) < TOL
    for got, expected in zip(pull(w), pull_want(w)):
        assert norm_gap(got, expected) < TOL
    text = str(jax.make_jaxpr(flash)(q, k, v))
    assert ("flash_window_fwd" in text) == banded
    if not banded:
        # at least as long as the keys: exactly the program no window stages
        plain = str(jax.make_jaxpr(lambda q, k, v: attention._flash_attention_diff(
            q, k, v, True, scale, True))(q, k, v))
        assert text == plain


@pytest.mark.parametrize("block_q, block_k, span", [(64, 64, None), (128, 64, None),
                                                    (64, 128, 128), (32, 32, 64)])
def test_the_banded_kernels_at_pinned_tiles_and_spans(block_q, block_k, span):
    """Tiles of both shapes (a query block wider than a key tile, as the plain
    rule has it at long rows, and narrower), and the backward's query range
    cut into spans: the lower edge and the diagonal cross the same tile where
    the window is shorter than it."""
    s, window, scale = 256, 40, 32 ** -0.5
    q, k, v, w = operands(s, 4, seed=block_q + block_k)
    out, lse = fa.flash_attention_fwd(q, k, v, True, scale, block_q, block_k, True,
                                      window=window)
    want, pull = jax.vjp(lambda *a: by_hand(*a, window, scale), q, k, v)
    assert norm_gap(out, want) < TOL
    dq, dk, dv = fa._flash_bwd_bh(
        fa._to_bh(q), fa._to_bh(k), fa._to_bh(v), fa._to_bh(out), lse.reshape(8, s),
        fa._to_bh(w), True, scale, block_q, block_k, True, q_span=span, window=window)
    got = fa._from_bh(dq, 1, 8), fa._from_bh(dk, 1, 2), fa._from_bh(dv, 1, 2)
    for g, expected in zip(got, pull(w)):
        assert norm_gap(g, expected) < TOL


def test_the_banded_pair_mapped_over_a_mesh(monkeypatch):
    """Operands spread over a 2 x 2 mesh, the batch over 'data' and the heads
    (6 query heads a key head) over 'model': `_flash_prim` maps the banded
    kernels over it, each device its own batch rows and heads with the band
    whole, and the result and the three gradients are the single device's."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    rematerialised_step.flash_on_a_cpu(monkeypatch)
    monkeypatch.setattr(fa, "BLOCK", 64)
    window, scale = 40, 64 ** -0.5
    key = jax.random.PRNGKey(5)
    shapes = [(2, 256, 12, 64), (2, 256, 2, 64), (2, 256, 2, 64)]
    vals = [jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            for i, shape in enumerate(shapes)]
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    sharding = NamedSharding(mesh, P("data", None, "model", None))

    def run(vals):
        q, k, v = (paddle.to_tensor(a) for a in vals)
        for t in (q, k, v):
            t.stop_gradient = False
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True, window=window)
        (out * out).sum().backward()
        return out, [out._val, q.grad._val, k.grad._val, v.grad._val]
    before = counter("attention.window_total")
    _, single = run(vals)
    out, spread = run([jax.device_put(a, sharding) for a in vals])
    assert counter("attention.window_total") - before == 2
    assert len(out._val.sharding.device_set) == 4
    assert norm_gap(single[0], by_hand(*vals, window, scale)) < TOL
    for got, want in zip(spread, single):
        assert norm_gap(got, want) < TOL


def counter(name):
    from paddle_tpu.profiler import metrics
    return metrics.get_registry().snapshot()["counters"].get(name, 0.0)


def pairs_the_kernels_visit(s, window, block_q, block_k):
    """(forward, backward): (query blocks, key tiles) bool, the tile pairs the
    interpreted kernels multiply, read off their results and not off their
    loop bounds. Forward: head t holds NaN in value tile t, and a query block
    comes out NaN where its loop reached that tile, mask or no mask (0 x NaN).
    Backward: head i holds NaN in the cotangent of query block i, and dv's
    key tile comes out NaN where its loop reached that block."""
    n_q, n_k, d, scale = s // block_q, s // block_k, 16, 0.25
    rng = np.random.default_rng(s + block_q)

    def draw(heads):
        return [jnp.asarray(rng.normal(size=(heads, s, d)), jnp.float32) for _ in range(4)]
    q, k, v, _ = draw(n_k)
    poison = jnp.arange(s)[None, :, None] // block_k == jnp.arange(n_k)[:, None, None]
    out, _ = fa._flash_fwd_bh(q, k, jnp.where(poison, jnp.nan, v), True, scale,
                              block_q, block_k, True, window=window)
    rows = np.isnan(np.asarray(out)).any(axis=2).reshape(n_k, n_q, block_q)
    assert (rows.all(axis=2) == rows.any(axis=2)).all()
    q, k, v, do = draw(n_q)
    out, lse = fa._flash_fwd_bh(q, k, v, True, scale, block_q, block_k, True, window=window)
    poison = jnp.arange(s)[None, :, None] // block_q == jnp.arange(n_q)[:, None, None]
    _, _, dv = fa._flash_bwd_bh(q, k, v, out, lse, jnp.where(poison, jnp.nan, do), True,
                                scale, block_q, block_k, True, window=window)
    tiles = np.isnan(np.asarray(dv)).any(axis=2).reshape(n_q, n_k, block_k)
    assert (tiles.all(axis=2) == tiles.any(axis=2)).all()
    return rows.any(axis=2).T, tiles.any(axis=2)


# the cell's grid at a sixteenth of its size (16 tiles a row, a window of one
# tile), the 1024-query block the rule refuses there, a window shorter than a
# tile and one that is no multiple of one at uneven tiles, a window of 1, and
# the causal grid as it stands
@pytest.mark.parametrize("s, window, block_q, block_k, visited", [
    (512, 32, 32, 32, 31), (512, 32, 64, 32, 2 + 7 * 3), (256, 40, 64, 32, None),
    (256, 100, 32, 64, None), (128, 1, 32, 32, 4), (512, None, 32, 32, 136),
    (512, None, 64, 32, 72)])
def test_the_kernels_visit_the_bands_tile_pairs_and_no_other(s, window, block_q, block_k,
                                                             visited):
    """Both passes multiply exactly the tile pairs that hold a pair of the
    band: at a window of one tile and 16 tiles a row 2 key tiles a query
    block (1 for the first), 31 of the causal grid's 136; a query block of
    two tiles would visit 3 key tiles for a band that fills one, 23 of 72. A
    kernel whose loops fell back to the causal grid would read 136 here."""
    t = np.arange(s)
    keep = t[None, :] <= t[:, None]
    if window is not None:
        keep &= t[None, :] > t[:, None] - window
    want = keep.reshape(s // block_q, block_q, s // block_k, block_k).any(axis=(1, 3))
    forward, backward = pairs_the_kernels_visit(s, window, block_q, block_k)
    np.testing.assert_array_equal(forward, want)
    np.testing.assert_array_equal(backward, want)
    if visited is not None:
        assert forward.sum() == visited


def test_the_tile_rule_under_a_window():
    """A band shorter than the keys keeps the query block at one tile however
    long the row; with no band, or one at least as long as the keys, the plain
    rule's 1024-query block from 8192 positions on."""
    assert fa.tiles(8192, 8192, 512) == (512, 512)
    assert fa.tiles(8192, 8192) == fa.tiles(8192, 8192, 8192) == (1024, 512)
    assert fa.tiles(4096, 4096, 512) == fa.tiles(4096, 4096) == (512, 512)


def test_a_window_through_the_public_call(monkeypatch):
    """`F.scaled_dot_product_attention(..., is_causal=True, window=)`: XLA's
    masked square off the TPU; where the platform rule says TPU the banded
    pair, counted; what a window may not stand beside raises."""
    from paddle_tpu.profiler import metrics
    q, k, v, _ = (paddle.Tensor(a) for a in operands(256, 4, d=64))
    want = by_hand(q._val, k._val, v._val, 40, 64 ** -0.5)
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True, window=40)
    assert norm_gap(out._val, want) < TOL

    def counters():
        c = metrics.get_registry().snapshot()["counters"]
        return [c.get("attention." + n, 0.0) for n in ("flash_total", "window_total")]
    rematerialised_step.flash_on_a_cpu(monkeypatch)
    monkeypatch.setattr(fa, "BLOCK", 64)
    before = counters()
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True, window=40)
    assert norm_gap(out._val, want) < TOL
    assert [b - a for a, b in zip(before, counters())] == [1, 1]
    # a window at least as long as the keys is plain causal attention
    before = counters()
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True, window=256)
    assert norm_gap(out._val, by_hand(q._val, k._val, v._val, 256, 64 ** -0.5)) < TOL
    assert [b - a for a, b in zip(before, counters())] == [1, 0]
    mask = paddle.Tensor(jnp.ones((256, 256), bool))
    with pytest.raises(ValueError, match="window"):
        F.scaled_dot_product_attention(q, k, v, window=40)
    with pytest.raises(ValueError, match="window"):
        F.scaled_dot_product_attention(q, k, v, is_causal=True, window=40, attn_mask=mask)
    with pytest.raises(ValueError, match="window"):
        F.scaled_dot_product_attention(
            q, k, v, is_causal=True, window=40,
            key_set=paddle.Tensor(jnp.ones((1, 256, 256), jnp.int8)))
    with pytest.raises(ValueError, match="holds no key"):
        F.scaled_dot_product_attention(q, k, v, is_causal=True, window=0)


# the plain pair's staged program, forward and backward kernels and the XLA
# round them, at the four flash cells' shapes (batch, positions, query heads,
# key heads, query/key size, value size): SHA-256 of the jaxprs' text as the
# parent of the PR that added the band printed it, source locations masked
PLAIN_PAIR = {
    "lfm2-24b-a2b": ((2, 4096, 32, 8, 64, 64), "40924f48a6c6e76f"),
    "kimi-linear-48b-a3b": ((2, 4096, 32, 32, 192, 128), "3a013f084e73bdcf"),
    "keye-vl2-30b-a3b": ((1, 8192, 32, 4, 128, 128), "28961d224057af9e"),
    "deepseek-v2-lite": ((1, 8192, 16, 16, 192, 128), "7803c8346fad3912"),
}


@pytest.mark.parametrize("config", sorted(PLAIN_PAIR))
def test_the_plain_pairs_staged_program_is_as_it_was(config):
    (b, s, h, hk, d, dv), digest = PLAIN_PAIR[config]

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)
    q, k, v, o = sds(b, s, h, d), sds(b, s, hk, d), sds(b, s, hk, dv), sds(b, s, h, dv)
    lse = sds(b, h, s, dtype=jnp.float32)
    scale = d ** -0.5
    text = str(jax.make_jaxpr(lambda q, k, v: fa.flash_attention_fwd(
        q, k, v, True, scale, interpret=False))(q, k, v))
    text += str(jax.make_jaxpr(lambda q, k, v, o, l, do: fa.flash_attention_bwd(
        q, k, v, o, l, do, True, scale, interpret=False))(q, k, v, o, lse, o))
    text = re.sub(r"0x[0-9a-f]+", "<addr>", re.sub(r" at [^\s\]]+:\d+", " at <src>", text))
    assert "name=None" in text and "flash_window" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# ---------------------------------------------------------------------------
# the layers

@pytest.mark.parametrize("layer", [0, 1], ids=["full-6-a-key-head", "window-8-a-key-head"])
def test_the_mixer_against_the_reference(cell, leaves, model, layer):
    cfg = cell["cfg"]
    kind, heads, _ = ref.layer_kinds(cfg)[layer]
    assert (kind, heads) == (("full_attention", 6), ("sliding_attention", 8))[layer]
    pre = f"l{layer}."
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64))
    want = ref.attention(leaves, pre, kind, heads, x, cfg, jnp.matmul)
    want_dx = jax.grad(lambda v: jnp.sum(
        ref.attention(leaves, pre, kind, heads, v, cfg, jnp.matmul) * w))(x)
    t = paddle.to_tensor(np.asarray(x), stop_gradient=False)
    mixer = model[0].model.layers[layer].self_attn
    assert mixer.window == (None, 16)[layer] and mixer.rotary_dim == (8, 16)[layer]
    out = mixer(t)
    assert norm_gap(out._val, want) < TOL
    (out * paddle.to_tensor(np.asarray(w))).sum().backward()
    assert norm_gap(t.grad._val, want_dx) < GRAD_TOL
    # the gate is there: without it the reference is elsewhere
    assert norm_gap(out._val, ref.attention(leaves, pre, kind, heads, x, cfg, jnp.matmul,
                                            no_gate=True)) > 0.1


@pytest.mark.parametrize("absent", ["drop", "stand_in"])
def test_the_expert_layer_against_the_reference(absent):
    cell = tiny(absent_experts=absent)
    cfg, p = cell["cfg"], seeded(cell)
    layer = build(cell, p)[0].model.layers[1].mlp
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64))
    out, load = layer(paddle.to_tensor(np.asarray(x)))
    assert norm_gap(out._val, ref.expert_ff(p, "l1.", x, cfg, jnp.matmul)) < TOL
    idx, weights = ref.route(p, "l1.", x, cfg, jnp.matmul)
    # renormalised over the picks, times 2.5
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 2.5, rtol=1e-5)
    rows = 2 * 64 * 3 if absent == "stand_in" else int(np.isin(np.asarray(idx), [0, 1, 2, 3]).sum())
    assert float(jnp.sum(load._val)) == rows


def expert_layer(cfg, p, held, absent="drop", stand_ins=None):
    """A DroplessMoELayer as the model builds it, of the tiny widths, holding
    `held` of the published experts of layer 1's leaves `p` (whose e_w* hold
    all of them)."""
    from paddle_tpu.incubate.moe import DroplessMoELayer
    layer = DroplessMoELayer(
        64, 32, cfg["num_experts_published"], cfg["num_experts_per_tok"],
        held_experts=held, routed_scaling_factor=cfg["moe_routed_scaling_factor"],
        shared_width=32, absent=absent)
    rows = jnp.asarray(held if stand_ins is None else stand_ins)
    layer.set_state_dict({
        "gate.weight": paddle.Tensor(p["l1.gate_w"]),
        "expert_bias": paddle.Tensor(p["l1.expert_bias"]),
        **{f"w{n}": paddle.Tensor(p[f"l1.e_w{n}"][rows]) for n in (1, 2, 3)},
        **{f"shared.w{n}.weight": paddle.Tensor(p[f"l1.s_w{n}"]) for n in (1, 2, 3)}})
    return layer


def test_the_shares_tie_to_the_uncut_layer():
    """256 experts in 8 shares of 32, 8 picked a token, as the deployment has
    them. Under "drop" the routed parts that the 8 shares give, the shared
    expert (whole on every share) counted once, add up to the uncut reference
    layer. Under "stand_in" a share equals the uncut layer built with the
    stand-ins' weights: expert e computed with the leaves of held slot
    e mod 32."""
    whole = tiny(num_experts_published=256, num_experts=256,
                 held_experts=list(range(256)), num_experts_per_tok=8)
    cfg, p = whole["cfg"], seeded(whole)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 64))
    want = ref.expert_ff(p, "l1.", x, cfg, jnp.matmul)
    tx = paddle.to_tensor(np.asarray(x))
    total = 0.0
    for share in range(8):
        layer = expert_layer(cfg, p, list(range(32 * share, 32 * share + 32)))
        out, _ = layer(tx)
        shared = layer.shared(tx)._val
        total = total + out._val - shared
    assert norm_gap(total + shared, want) < TOL
    assert norm_gap(total, ref.routed_part(p, "l1.", x, cfg, jnp.matmul)) < TOL
    stood = dict(p, **{f"l1.e_w{n}": p[f"l1.e_w{n}"][jnp.arange(256) % 32] for n in (1, 2, 3)})
    want = ref.expert_ff(stood, "l1.", x, cfg, jnp.matmul)
    out, load = expert_layer(cfg, p, list(range(32)), absent="stand_in")(tx)
    assert norm_gap(out._val, want) < TOL
    assert float(jnp.sum(load._val)) == 2 * 64 * 8
    # and the reference given the same share says the same
    cut = dict(cfg, held_experts=list(range(32)), absent_experts="stand_in")
    held = dict(p, **{f"l1.e_w{n}": p[f"l1.e_w{n}"][:32] for n in (1, 2, 3)})
    assert norm_gap(ref.expert_ff(held, "l1.", x, cut, jnp.matmul), want) < TOL


# ---------------------------------------------------------------------------
# the whole model

def three_steps(cell, p, check):
    """Three AdamW steps of the program beside the reference's; `check(step,
    loss, want, grads, names, tensors)` after each backward."""
    family, cfg = cell["family"], cell["cfg"]
    model, names = build(cell, p)
    model.train()
    o = cfg["optimizer"]
    opt = paddle.optimizer.AdamW(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        multi_precision=cfg["weights_dtype"] != "float32",
        parameters=model.parameters())
    q = {k: v.astype(jnp.float32) for k, v in p.items()}
    start, state = dict(q), adamw.init(q)
    tensors = model.state_dict()
    for step, (x, y) in enumerate(batch(cell, 3)):
        want, grads = jax.value_and_grad(
            lambda r: ref.loss_fn(r, jnp.asarray(x), jnp.asarray(y), cfg))(q)
        loss = family.loss_of(model, paddle.to_tensor(x), paddle.to_tensor(y))
        loss.backward()
        check(step, float(loss.item()), float(want), grads, names, tensors)
        opt.step()
        opt.clear_grad()
        q, state = adamw.update(
            q, grads, state, lr=o["learning_rate"], beta1=o["beta1"],
            beta2=o["beta2"], eps=o["epsilon"], weight_decay=o["weight_decay"])
    return start, q, names, tensors


@pytest.mark.parametrize("absent, remat", [("drop", False), ("stand_in", True)])
def test_loss_gradients_and_three_adamw_steps_in_float32(absent, remat):
    cell = tiny(absent_experts=absent, recompute=remat)

    def check(step, loss, want, grads, names, tensors):
        assert abs(loss - want) < TOL * want
        if step:
            return
        for leaf, key in names.items():
            if leaf.endswith("expert_bias"):
                assert tensors[key].grad is None       # no gradient, by design
            else:
                assert norm_gap(tensors[key].grad._val, grads[leaf]) < GRAD_TOL, leaf

    start, end, names, tensors = three_steps(cell, seeded(cell), check)
    for leaf, key in names.items():
        if leaf.endswith("expert_bias"):
            assert norm_gap(tensors[key]._val, start[leaf]) == 0.0
            continue
        # the change of each leaf over three steps, against the reference's
        # (Adam's first steps move an entry by the learning rate whatever its
        # gradient's size, so an entry whose gradient is rounding moves by
        # its sign)
        moved = float(jnp.linalg.norm(jnp.ravel(end[leaf] - start[leaf])))
        assert float(jnp.linalg.norm(jnp.ravel(tensors[key]._val - end[leaf]))) \
            < 5e-2 * moved, leaf


def test_loss_and_gradients_in_bfloat16():
    """bf16 weights and activations against the float32 reference from the
    same (bf16-rounded) weights, every matrix at the benchmark's own std of
    0.02: the loss within 2e-4 of the reference's (4e-6 to 3e-5 read over
    seeds 7, 8 and 9), a leaf's first gradient within 0.3 of its norm (0.09
    to 0.13 read, the widest a router's matrix: near-ties pick another expert
    in bf16, and a pick weighs 2.5 times its share here. At 8 times that std
    the same flips, through four expert layers, move a router's gradient by
    0.5 to 0.8 of its norm and the loss by 3.5e-3: a band that would hold
    nothing)."""
    cell = tiny(absent_experts="stand_in", weights_dtype="bfloat16")
    p = seeded(cell, "bfloat16", sharp=False, scale=1)
    model, names = build(cell, p)
    (x, y), = batch(cell)
    want, grads = jax.value_and_grad(lambda r: ref.loss_fn(
        r, jnp.asarray(x), jnp.asarray(y), cell["cfg"]))(
        {k: v.astype(jnp.float32) for k, v in p.items()})
    loss = model(paddle.to_tensor(x), labels=paddle.to_tensor(y))
    got = rematerialised_step.grads_by_leaf(model, names, loss)
    assert abs(float(loss.item()) - float(want)) < 2e-4 * float(want)
    worst = max(norm_gap(g._val, grads[leaf]) for leaf, g in got.items()
                if not leaf.endswith("expert_bias"))
    assert worst < 0.3, worst


@pytest.mark.parametrize("fault", ["causal_window_layers", "no_gate", "plain_frequencies"])
def test_a_fault_is_not_within_the_tolerances(cell, leaves, model, fault):
    """The reference with one fault put in, against the program: the loss or
    a leaf's gradient is out by at least 30 times what the sound reference is
    held to."""
    cfg = cell["cfg"]
    (x, y), = batch(cell)
    net, names = model
    net.clear_gradients()
    loss = cell["family"].loss_of(net, paddle.to_tensor(x), paddle.to_tensor(y))
    loss.backward()
    tensors = net.state_dict()

    def gaps(**kw):
        want, grads = jax.value_and_grad(
            lambda r: ref.loss_fn(r, jnp.asarray(x), jnp.asarray(y), cfg, **kw))(leaves)
        return (abs(float(loss.item()) - float(want)) / float(want),
                max(norm_gap(tensors[key].grad._val, grads[leaf])
                    for leaf, key in names.items() if not leaf.endswith("expert_bias")))
    sound = gaps()
    assert sound[0] < TOL and sound[1] < GRAD_TOL
    faulty = gaps(**{fault: True})
    assert faulty[0] > 30 * TOL or faulty[1] > 30 * GRAD_TOL, (fault, faulty)
    net.clear_gradients()


def test_the_model_reads_the_published_lists_from_its_first_layer():
    """Layer i held is published layer `first_layer` + i: kind, query heads,
    rotary rule and feed-forward from the four lists' own entries."""
    from paddle_tpu.text.models import LagunaConfig, LagunaForCausalLM
    cfg = tiny()["cfg"]
    kw = dict(vocab_size=50, hidden_size=64, head_dim=16, num_key_value_heads=2,
              layer_types=cfg["layer_types"], mlp_layer_types=cfg["mlp_layer_types"],
              num_attention_heads_per_layer=cfg["num_attention_heads_per_layer"],
              rope_parameters=cfg["rope_parameters"], sliding_window=16,
              intermediate_size=32, moe_intermediate_size=16,
              shared_expert_intermediate_size=16, num_experts=8, num_experts_per_tok=2)
    net = LagunaForCausalLM(LagunaConfig(num_layers=3, first_layer=3, **kw))
    got = [(b.self_attn.window, b.self_attn.num_heads, b.self_attn.rotary_dim, b.is_dense)
           for b in net.model.layers]
    assert got == [(16, 8, 16, False), (None, 6, 8, False), (16, 8, 16, False)]
    assert net.model.layers[1].self_attn.rope_scaling["rope_type"] == "yarn"
    assert net.model.layers[0].self_attn.rope_scaling is None
    assert LagunaForCausalLM(LagunaConfig(num_layers=1, **kw)).model.layers[0].is_dense
    with pytest.raises(ValueError, match="layer_types"):
        LagunaConfig(num_layers=3, first_layer=38, **kw)
    ungated = LagunaForCausalLM(LagunaConfig(num_layers=2, gating=False, **kw))
    assert ungated.model.layers[1].self_attn.g_proj is None
    ids = paddle.to_tensor(np.random.default_rng(5).integers(0, 50, (2, 32)))
    assert ungated(ids).shape == [2, 32, 50]


# ---------------------------------------------------------------------------
# what a step stages

@pytest.fixture(scope="module")
def traced_step():
    """One training step over the five rematerialised blocks, on a platform
    rule that says TPU so that attention takes the flash pair, the window
    layers its banded grid."""
    with pytest.MonkeyPatch.context() as patch:
        rematerialised_step.flash_on_a_cpu(patch)
        patch.setattr(fa, "BLOCK", 64)
        cell = tiny(recompute=True, absent_experts="stand_in", head_dim=64,
                    sliding_window=32)
        cell["job"].update(batch=1, seq=128)
        model, _ = build(cell, seeded(cell))
        (x, y), = batch(cell)
        got = rematerialised_step.traced_step(
            model, cell["family"].loss_of, paddle.to_tensor(x), paddle.to_tensor(y))
        return dict(got, model=model)


def test_a_rematerialised_step_stages_the_scopes(traced_step):
    """Both kinds of attention under `flash_attention`, on forward and
    backward instructions and on none of a rerun (the core is outside the
    blocks' regions); `attn_gate` on the projection and sigmoid (first
    region) and the multiply (second), forward, rerun and backward. (The
    banded kernels' own names are in the jaxpr, above, and in the program
    compiled for a described chip: tests/test_tpu_aot_compile.py.)"""
    names, text = traced_step["names"], traced_step["text"]
    assert rematerialised_step.passes_of(names, "flash_attention") == {"forward", "backward"}
    assert "transpose(jvp(jvp(flash_attention)))" not in text
    assert {"forward", "rerun", "backward"} <= rematerialised_step.passes_of(names, "attn_gate")
    for scope in ("rope", "linear", "rms_norm", "moe_route", "moe_experts"):
        assert {"forward", "rerun"} <= rematerialised_step.passes_of(names, scope), scope


@pytest.mark.parametrize("which", ["eager", "traced"])
def test_a_rematerialised_step_counts_its_window_layers_once_a_pass(traced_step, which):
    """A layer a pass of the step's body one flash forward, and one call over
    the banded grid for each of the three window layers: none in a region's
    discovery, first run or rerun."""
    passes, moved = traced_step["passes"][which], traced_step["moved"][which]
    assert passes > 0
    assert moved["attention.flash_total"] == 5 * passes
    assert moved["attention.window_total"] == 3 * passes
    assert moved.get("attention.xla_total", 0) == 0


def test_a_rematerialised_model_is_the_plain_model():
    """Two regions round the attention core, the gates leaving the first with
    q, k and v, are the plain block's arithmetic: the loss and every leaf's
    gradient."""
    cell = tiny(absent_experts="stand_in")
    leaves = seeded(cell)
    (x, y), = batch(cell)
    x, y = paddle.to_tensor(x), paddle.to_tensor(y)
    got = {}
    for recompute in (False, True):
        model, names = build(dict(cell, cfg=dict(cell["cfg"], recompute=recompute)),
                             leaves)
        loss = model(x, labels=y)
        got[recompute] = (float(loss.item()),
                          rematerialised_step.grads_by_leaf(model, names, loss))
    (loss, grads), (loss_r, grads_r) = got[False], got[True]
    assert abs(loss_r - loss) <= 1e-6 * loss
    rematerialised_step.assert_the_same_gradients(grads, grads_r, tol=4e-6)
