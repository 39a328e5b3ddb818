"""The mixers whose `forward` is three parts, `operands`, `core` and `project`
(`nn.KimiDeltaAttention`, `nn.MultiHeadLatentAttention` with and without the
rotation, the LFM2 model's attention): `forward` is their composition, and
its result on seeded weights is, bit for bit, what the one-piece `forward`
gave before a rematerialised block ran the parts itself (PR 49). The
one-piece forwards below are those lines as they stood."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402
from paddle_tpu.core.dispatch import apply  # noqa: E402
from paddle_tpu.tensor import manipulation as M  # noqa: E402
from paddle_tpu.text.models.lfm2 import LFM2Attention, LFM2Config  # noqa: E402

BATCH, SEQ, HIDDEN = 2, 48, 64
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}


def one_piece_kda(self, x):
    b, s, _ = x.shape
    heads = [b, s, self.num_heads, self.head_dim]
    q = M.reshape(F.short_conv_silu(
        self.q_proj(x), self.q_conv1d, norm_head_dim=self.head_dim), heads)
    k = M.reshape(F.short_conv_silu(
        self.k_proj(x), self.k_conv1d, norm_head_dim=self.head_dim), heads)
    v = M.reshape(F.short_conv_silu(self.v_proj(x), self.v_conv1d), heads)

    def decay(f, a_log, dt_bias):
        f32 = jnp.float32
        g = jax.nn.softplus(f.astype(f32) + dt_bias.astype(f32)).reshape(heads)
        return -jnp.exp(a_log.astype(f32))[:, None] * g
    g = apply(decay, self.f_b_proj(self.f_a_proj(x)), self.A_log,
              self.dt_bias, name="kda_gate")
    beta = F.sigmoid(self.b_proj(x))
    o = self.o_norm(F.kimi_delta_attention(q, k, v, g, beta))
    gate = M.reshape(F.sigmoid(self.g_b_proj(self.g_a_proj(x))), heads)
    return self.o_proj(M.reshape(o * gate, [b, s, heads[2] * heads[3]]))


def one_piece_mla(self, x):
    b, s, _ = x.shape
    heads, nope, dv = self.num_heads, self.qk_nope_head_dim, self.v_head_dim
    q = M.reshape(self.q_proj(x), [b, s, heads, nope + self.qk_rope_head_dim])
    rank = self.kv_lora_rank
    latent, shared = apply(lambda c: (c[..., :rank], c[..., rank:]),
                           self.kv_a_proj(x), name="mla_kv")
    kv = M.reshape(self.kv_b_proj(self.kv_a_norm(latent)), [b, s, heads, nope + dv])
    if self.rope is not None:
        q_nope, q_pe = apply(lambda v: (v[..., :nope], v[..., nope:]), q,
                             name="mla_rope")
        q_pe, shared = F.rotary_position_embedding(
            q_pe, M.reshape(shared, [b, s, 1, self.qk_rope_head_dim]),
            theta=self.rope["theta"], rope_scaling=self.rope.get("rope_scaling"),
            interleaved=True, name="mla_rope")
        q = apply(lambda a, c: jnp.concatenate([a, c], axis=-1), q_nope, q_pe,
                  name="mla_rope")
        shared = M.reshape(shared, [b, s, self.qk_rope_head_dim])

    def keys_values(kv_, shared_):
        pe = jnp.broadcast_to(shared_[:, :, None, :],
                              kv_.shape[:3] + shared_.shape[-1:])
        return (jnp.concatenate([kv_[..., :nope], pe], axis=-1), kv_[..., nope:])
    k, v = apply(keys_values, kv, shared, name="mla_kv")
    out = F.scaled_dot_product_attention(
        q, k, v, is_causal=True, training=self.training, scale=self.scale)
    return self.o_proj(M.reshape(out, [b, s, heads * dv]))


def one_piece_lfm2(self, x):
    b, s, h = x.shape
    q = M.reshape(self.q_proj(x), [b, s, self.num_heads, self.head_dim])
    k = M.reshape(self.k_proj(x), [b, s, self.num_kv_heads, self.head_dim])
    v = M.reshape(self.v_proj(x), [b, s, self.num_kv_heads, self.head_dim])
    q, k = F.rotary_position_embedding(self.q_norm(q), self.k_norm(k),
                                       theta=self.rope_theta)
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                         training=self.training)
    return self.out_proj(M.reshape(out, [b, s, h]))


def kda():
    layer = paddle.nn.KimiDeltaAttention(HIDDEN, 4, 16, 4, 8, 1e-5)
    # the decay's leaves off their zeros
    rng = np.random.default_rng(5)
    layer.A_log._value = jnp.asarray(rng.normal(0, 0.5, layer.A_log.shape), jnp.float32)
    layer.dt_bias._value = jnp.asarray(rng.normal(0, 0.5, layer.dt_bias.shape), jnp.float32)
    return layer, one_piece_kda, 1


def mla():
    return paddle.nn.MultiHeadLatentAttention(HIDDEN, 4, 32, 16, 8, 16, 1e-5), one_piece_mla, 0


def mla_rope():
    return paddle.nn.MultiHeadLatentAttention(
        HIDDEN, 4, 32, 16, 8, 16, 1e-5,
        rope={"theta": 10000.0, "rope_scaling": YARN}), one_piece_mla, 0


def lfm2():
    return LFM2Attention(LFM2Config(
        hidden_size=HIDDEN, num_attention_heads=4, num_key_value_heads=2)), one_piece_lfm2, 0


MIXERS = {"kda": kda, "mla": mla, "mla_rope": mla_rope, "lfm2": lfm2}


@pytest.fixture(params=list(MIXERS))
def mixer(request):
    """(the layer on seeded weights, its one-piece forward, how many of
    `operands`' results `project` reads beside the core's, an input)."""
    paddle.seed(3)
    layer, one_piece, carried = MIXERS[request.param]()
    x = np.random.default_rng(2).normal(size=(BATCH, SEQ, HIDDEN)).astype(np.float32)
    return layer, one_piece, carried, x


def same(a, b):
    np.testing.assert_array_equal(np.asarray(a._val), np.asarray(b._val))


def test_forward_is_the_three_parts_composed(mixer):
    layer, _, carried, x = mixer
    x = paddle.to_tensor(x)
    operands = layer.operands(x)
    split = len(operands) - carried
    out = layer.project(layer.core(*operands[:split]), *operands[split:])
    assert tuple(out.shape) == (BATCH, SEQ, HIDDEN)
    same(layer(x), out)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_is_the_one_piece_forward_bit_for_bit(mixer, dtype):
    layer, one_piece, _, x = mixer
    if dtype == "bfloat16":
        layer.bfloat16()
    x = paddle.to_tensor(x).astype(dtype)
    want = one_piece(layer, x)
    assert float(jnp.linalg.norm(want._val.astype(jnp.float32))) > 0.0
    same(layer(x), want)


def test_the_gradients_are_the_one_piece_forwards(mixer):
    """The input's and every leaf's gradient through the tape, parts against
    one piece: the same operations in the same order."""
    layer, one_piece, _, x = mixer
    w = paddle.to_tensor(np.random.default_rng(4).normal(size=(BATCH, SEQ, HIDDEN))
                         .astype(np.float32))
    grads = []
    for forward in (layer, lambda v: one_piece(layer, v)):
        t = paddle.to_tensor(x, stop_gradient=False)
        (forward(t) * w).sum().backward()
        grads.append([t.grad] + [p.grad for p in layer.parameters()])
        layer.clear_gradients()
    assert len(grads[0]) > 4 and all(g is not None for g in grads[0])
    for got, want in zip(*grads):
        same(got, want)
