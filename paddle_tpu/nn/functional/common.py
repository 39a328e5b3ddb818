"""Common functionals: linear/dropout/embedding/interpolate/etc.
(python/paddle/nn/functional/common.py, input.py parity)."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ...core.dispatch import apply, unwrap
from ...core.random import next_key_data
from ...core.tensor import Tensor

__all__ = [
    "linear", "dropout", "dropout2d", "dropout3d", "alpha_dropout",
    "embedding", "one_hot", "label_smooth", "pad", "interpolate", "upsample",
    "pixel_shuffle", "pixel_unshuffle", "channel_shuffle", "unfold", "fold",
    "cosine_similarity", "bilinear", "class_center_sample", "zeropad2d",
    "rotary_position_embedding", "yarn_frequencies", "yarn_scales",
]


def linear(x, weight, bias=None, name=None):
    """weight shape (in, out) — reference layout (nn/layer/common.py Linear)."""
    if bias is not None:
        return apply(lambda v, w, b: jnp.matmul(v, w) + b, x, weight, bias,
                     name="linear")
    return apply(lambda v, w: jnp.matmul(v, w), x, weight, name="linear")


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return apply(lambda v: v * (1.0 - p), x, name="dropout_infer")
        return x
    if p == 1.0:
        return apply(lambda v: jnp.zeros_like(v), x, name="dropout")
    kd = next_key_data()

    def prim(v, key_data):
        key = jax.random.wrap_key_data(key_data)
        shape = list(v.shape)
        if axis is not None:
            axes = [axis] if isinstance(axis, int) else list(axis)
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, v / (1.0 - p), 0.0).astype(v.dtype)
        return jnp.where(keep, v, 0.0).astype(v.dtype)

    return apply(prim, x, kd, name="dropout")


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    axis = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p=p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    axis = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p=p, axis=axis, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0.0:
        return x
    kd = next_key_data()
    alpha = 1.6732632423543772848170429916717
    scale = 1.0507009873554804934193349852946
    alpha_p = -alpha * scale

    def prim(v, key_data):
        keep = jax.random.bernoulli(jax.random.wrap_key_data(key_data),
                                    1.0 - p, v.shape)
        a = (1.0 / np.sqrt((1.0 - p) * (1.0 + p * alpha_p ** 2))).astype(np.float32)
        b = -a * alpha_p * p
        return (jnp.where(keep, v, alpha_p) * a + b).astype(v.dtype)

    return apply(prim, x, kd, name="alpha_dropout")


def yarn_frequencies(dim, theta, rope_scaling):
    """(dim / 2,) float32: the angle a position turns pair n by under YaRN
    (Peng et al. 2023, "NTK-by-parts"), as DeepSeek-V2's modelling code forms
    it. theta_n = theta^(-2n/dim) is blended with theta_n / factor: pairs
    that turn more than `beta_fast` times over the original context keep
    their frequency, those that turn fewer than `beta_slow` times are slowed
    by `factor`, a linear ramp between (low = floor, high = ceil of the pair
    index at which a pair turns beta_fast, beta_slow times). Worked in
    float64 and rounded once."""
    factor, original = rope_scaling["factor"], rope_scaling["original_max_position_embeddings"]
    plain = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_turning(rotations):
        return dim * np.log(original / (rotations * 2 * np.pi)) / (2 * np.log(theta))
    low = max(np.floor(pair_turning(rope_scaling.get("beta_fast", 32))), 0)
    high = min(np.ceil(pair_turning(rope_scaling.get("beta_slow", 1))), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return (plain * keep + plain / factor * (1.0 - keep)).astype(np.float32)


def yarn_scales(rope_scaling):
    """(what cos and sin are multiplied by, what the softmax scale is
    multiplied by) under YaRN: with m(a) = 0.1 a ln(factor) + 1 (1 at
    factor <= 1), m(mscale) / m(mscale_all_dim) and m(mscale_all_dim)^2."""
    def m(a):
        factor = rope_scaling["factor"]
        return 1.0 if factor <= 1 else 0.1 * a * float(np.log(factor)) + 1.0
    every = m(rope_scaling.get("mscale_all_dim", 0))
    return m(rope_scaling.get("mscale", 1)) / every, every * every


def _rope_tables(dim, seq, theta, position_offset, rope_scaling):
    """(cos, sin), each (seq, dim / 2) float32, of positions
    position_offset ... at theta^(-2n/dim), or under YaRN (`rope_scaling`) at
    `yarn_frequencies` and times `yarn_scales`' first factor, or the
    `attention_factor` the dict gives in its place: what `_rotate_pairs`
    reads. jax.numpy out."""
    if rope_scaling is None:
        inv, table_scale = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim), 1.0
    else:
        inv = jnp.asarray(yarn_frequencies(dim, theta, rope_scaling))
        table_scale = rope_scaling.get("attention_factor")
        if table_scale is None:
            table_scale = yarn_scales(rope_scaling)[0]
    t = jnp.arange(position_offset, position_offset + seq, dtype=jnp.float32)
    angle = t[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if table_scale != 1.0:
        cos, sin = cos * table_scale, sin * table_scale
    return cos, sin


def _rotate_pairs(v, cos, sin, interleaved):
    """`v` (batch, seq, heads, dim) with each pair of entries turned by its
    angle, float32 arithmetic, v's dtype out; cos, sin (seq, dim / 2).
    Half-split pairing: entry n with entry n + dim / 2. `interleaved`: entry
    2n with entry 2n + 1, the result stored half-split. jax.numpy in,
    jax.numpy out."""
    f = v.astype(jnp.float32)
    half = v.shape[-1] // 2
    a, b = (f[..., 0::2], f[..., 1::2]) if interleaved else (f[..., :half], f[..., half:])
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(v.dtype)


def rotary_position_embedding(q, k, theta=10000.0, position_offset=0,
                              name=None, position_ids=None, sections=None,
                              rope_scaling=None, interleaved=False,
                              rotary_dim=None):
    """Rotary positions (Su et al. 2021) on `q` and `k`, each (batch, seq,
    heads, head_dim); their head counts may differ (one key head under many
    query heads). Angles and the rotation are float32; the results keep
    their dtypes. `position_offset` is the position of the first row (a
    cached decode step).

    Pairing. By default the rotate-half convention over the whole head:
    entry i pairs with entry i + head_dim/2. `interleaved=True` pairs entry
    2n with entry 2n + 1 (the layout of DeepSeek-V2's weights) and stores
    the result half-split (the turned pair n at n and n + head_dim/2), as
    that model's code leaves it: a reordering that queries and keys share, so
    their products are those of the interleaved order.

    Frequencies. Pair n at position t turns by t * theta^(-2n/head_dim) by
    default; by YaRN's blend where `rope_scaling` is a dict of `type` "yarn"
    (`yarn_frequencies`; cos and sin then carry `yarn_scales`' first factor,
    and the caller owes the softmax its second; a dict with an
    `attention_factor` has cos and sin carry that, and the softmax is owed
    nothing).

    `rotary_dim` (a `partial_rotary_factor` times head_dim) turns entries
    0 ... rotary_dim - 1 of each head, pairing and frequencies as above over
    a head of that size, and passes the rest as they are.

    `position_ids` gives the positions instead: (batch, seq), or (streams,
    batch, seq) with `sections`, how many of the head_dim / 2 frequency pairs
    each stream turns, in order (multimodal rotary positions: (16, 24, 24)
    gives pairs 0-15 the first stream's position, 16-39 the second's, 40-63
    the third's). Equal streams give what one stream gives."""
    if rope_scaling is not None:
        kind = rope_scaling.get("type", rope_scaling.get("rope_type"))
        if kind != "yarn":
            raise ValueError(f"rope_scaling of type {kind!r}: only 'yarn' is computed")

    def prim(qv, kv, *pos):
        if rotary_dim is not None and rotary_dim < qv.shape[-1]:
            turned = turn_heads(qv[..., :rotary_dim], kv[..., :rotary_dim], *pos)
            return tuple(jnp.concatenate([t, v[..., rotary_dim:]], axis=-1)
                         for t, v in zip(turned, (qv, kv)))
        return turn_heads(qv, kv, *pos)

    def turn_heads(qv, kv, *pos):
        d, s = qv.shape[-1], qv.shape[1]
        if rope_scaling is not None or interleaved:
            if pos:
                raise ValueError("position_ids under rope_scaling or the "
                                 "interleaved pairing are not computed")
            cos, sin = _rope_tables(d, s, theta, position_offset, rope_scaling)
            return (_rotate_pairs(qv, cos, sin, interleaved),
                    _rotate_pairs(kv, cos, sin, interleaved))
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        if not pos:
            t = jnp.arange(position_offset, position_offset + s, dtype=jnp.float32)
            angle = jnp.concatenate([t[:, None] * inv[None, :]] * 2, axis=-1)
            cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
        else:
            t = pos[0].astype(jnp.float32)
            if t.ndim == 3:
                parts = (t.shape[0],) if sections is None else tuple(sections)
                if len(parts) != t.shape[0] or sum(parts) != d // 2:
                    raise ValueError(
                        f"sections {sections} do not split {d // 2} frequency "
                        f"pairs over {t.shape[0]} position streams")
                stream = np.repeat(np.arange(len(parts)), parts)
                # (batch, seq, pairs): pair i reads its stream's position
                t = jnp.moveaxis(t, 0, -1)[..., stream]
            else:
                t = t[..., None]
            angle = jnp.concatenate([t * inv] * 2, axis=-1)[:, :, None, :]
            cos, sin = jnp.cos(angle), jnp.sin(angle)

        def turn(v):
            f = v.astype(jnp.float32)
            half = jnp.concatenate([-f[..., d // 2:], f[..., :d // 2]], axis=-1)
            return (f * cos + half * sin).astype(v.dtype)

        return turn(qv), turn(kv)

    extra = [] if position_ids is None else [position_ids]
    return apply(prim, q, k, *extra, name=name or "rope")


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """Reference: operators/lookup_table_v2 — gather rows; positions equal to
    padding_idx produce zero vectors (and contribute zero gradient).
    sparse=True yields the weight grad as SelectedRows (|tokens| rows instead
    of a dense |vocab| table) — eager mode only; under tracing/static build
    the dense scatter-add path is used (XLA fuses it)."""
    if padding_idx is not None and padding_idx < 0:
        padding_idx = weight.shape[0] + padding_idx

    def prim(w, idx):
        out = jnp.take(w, idx.astype(jnp.int32), axis=0)
        if padding_idx is not None:
            mask = (idx != padding_idx)[..., None].astype(out.dtype)
            out = out * mask
        return out

    if sparse:
        from ...core import autograd as _ag
        from ...core.dispatch import get_static_builder
        from ...core.tensor import _TraceHooks
        import jax.core as jax_core
        wv, idx = unwrap(weight), unwrap(x)
        # plain eager only: static build, jit tracing, and to_static
        # discovery (hooked reads) all need the dense scatter-add grad so
        # the compiled program's grad-state structure stays dense
        eager = (get_static_builder() is None
                 and _TraceHooks.on_read is None
                 and not isinstance(wv, jax_core.Tracer)
                 and not isinstance(idx, jax_core.Tracer)
                 # the SelectedRows cotangent can only be accumulated on a
                 # LEAF weight; a computed weight's upstream vjp needs arrays
                 and getattr(weight, "_grad_node", None) is None)
        if eager and _ag.is_grad_enabled() and isinstance(weight, Tensor) \
                and not weight.stop_gradient:
            return _sparse_embedding(idx, weight, padding_idx, prim)
    return apply(prim, weight, unwrap(x), name="embedding")


def _sparse_embedding(idx, weight, padding_idx, prim):
    """Manual tape node whose weight-cotangent is a SelectedRows."""
    from ...core.autograd import GradNode
    from ...core.selected_rows import SelectedRows

    wv = weight._val
    out_val = prim(wv, idx)
    rows = idx.reshape(-1).astype(jnp.int32)

    def vjp_fn(ct):
        vals = ct.reshape(-1, wv.shape[1]).astype(wv.dtype)
        if padding_idx is not None:
            keep = (rows != padding_idx)[:, None].astype(vals.dtype)
            vals = vals * keep
        return (SelectedRows(rows, vals, height=wv.shape[0]),)

    node = GradNode(vjp_fn=vjp_fn, inputs=[weight],
                    out_meta=[(out_val.shape, out_val.dtype)],
                    multi_output=False, name="embedding_sparse_grad")
    out = Tensor(out_val, stop_gradient=False)
    out._grad_node = node
    out._out_index = 0
    return out


def one_hot(x, num_classes, name=None):
    v = unwrap(x)
    return Tensor(jax.nn.one_hot(v.astype(jnp.int32), num_classes,
                                 dtype=jnp.float32))


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def prim(l, *rest):
        k = l.shape[-1]
        if rest:
            return (1.0 - epsilon) * l + epsilon * rest[0]
        return (1.0 - epsilon) * l + epsilon / k
    if prior_dist is not None:
        return apply(prim, label, prior_dist, name="label_smooth")
    return apply(prim, label, name="label_smooth")


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):  # noqa: A002
    from ...tensor.manipulation import pad as _pad
    return _pad(x, pad, mode=mode, value=value, data_format=data_format)


def zeropad2d(x, padding, data_format="NCHW", name=None):
    return pad(x, padding, mode="constant", value=0.0, data_format=data_format)


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None):
    xv = unwrap(x)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    nd = xv.ndim
    nsp = nd - 2
    if channel_last:
        in_spatial = xv.shape[1:-1]
    else:
        in_spatial = xv.shape[2:]
    if size is not None:
        if isinstance(size, Tensor):
            size = [int(s) for s in np.asarray(size._value)]
        out_spatial = tuple(int(s.item() if isinstance(s, Tensor) else s) for s in
                            (size if isinstance(size, (list, tuple)) else [size]))
    else:
        if isinstance(scale_factor, (int, float)):
            scale_factor = [scale_factor] * nsp
        out_spatial = tuple(int(np.floor(i * s)) for i, s in
                            zip(in_spatial, scale_factor))

    jmode = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
             "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]

    def prim(v):
        if channel_last:
            out_shape = (v.shape[0],) + out_spatial + (v.shape[-1],)
        else:
            out_shape = v.shape[:2] + out_spatial
        if jmode == "nearest":
            return jax.image.resize(v, out_shape, method="nearest")
        if align_corners:
            # jax.image.resize has no align_corners; emulate with manual coords
            return _resize_align_corners(v, out_shape, jmode, channel_last)
        return jax.image.resize(v, out_shape, method=jmode)

    return apply(prim, x, name="interpolate")


def _resize_align_corners(v, out_shape, method, channel_last):
    """align_corners resize: output o samples input o*(in-1)/(out-1). Uses
    jax.image.scale_and_translate so linear AND cubic kernels are honored."""
    nd = v.ndim
    sp_axes = list(range(1, nd - 1)) if channel_last else list(range(2, nd))
    scales = []
    for ax in sp_axes:
        in_s, out_s = v.shape[ax], out_shape[ax]
        scales.append(1.0 if out_s <= 1 or in_s <= 1
                      else (out_s - 1.0) / (in_s - 1.0))
    kernel = {"linear": "linear", "cubic": "cubic"}.get(method, "linear")
    # scale_and_translate samples input at (o + 0.5 - t)/s - 0.5; choosing
    # t = 0.5 - 0.5*s makes that o/s — the align_corners mapping.
    translations = [0.5 - 0.5 * s for s in scales]
    return jax.image.scale_and_translate(
        v, out_shape, tuple(sp_axes),
        jnp.asarray(scales, dtype=jnp.float32),
        jnp.asarray(translations, dtype=jnp.float32),
        method=kernel)


def upsample(x, size=None, scale_factor=None, mode="nearest",
             align_corners=False, align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode,
                       data_format)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    r = upscale_factor

    def prim(v):
        if data_format == "NCHW":
            n, c, h, w = v.shape
            out = v.reshape(n, c // (r * r), r, r, h, w)
            out = out.transpose(0, 1, 4, 2, 5, 3)
            return out.reshape(n, c // (r * r), h * r, w * r)
        n, h, w, c = v.shape
        out = v.reshape(n, h, w, r, r, c // (r * r))
        out = out.transpose(0, 1, 3, 2, 4, 5)
        return out.reshape(n, h * r, w * r, c // (r * r))

    return apply(prim, x, name="pixel_shuffle")


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    r = downscale_factor

    def prim(v):
        if data_format == "NCHW":
            n, c, h, w = v.shape
            out = v.reshape(n, c, h // r, r, w // r, r)
            out = out.transpose(0, 1, 3, 5, 2, 4)
            return out.reshape(n, c * r * r, h // r, w // r)
        n, h, w, c = v.shape
        out = v.reshape(n, h // r, r, w // r, r, c)
        out = out.transpose(0, 1, 3, 2, 4, 5)
        return out.reshape(n, h // r, w // r, c * r * r)

    return apply(prim, x, name="pixel_unshuffle")


def channel_shuffle(x, groups, data_format="NCHW", name=None):
    def prim(v):
        if data_format == "NCHW":
            n, c, h, w = v.shape
            return v.reshape(n, groups, c // groups, h, w) \
                    .transpose(0, 2, 1, 3, 4).reshape(n, c, h, w)
        n, h, w, c = v.shape
        return v.reshape(n, h, w, groups, c // groups) \
                .transpose(0, 1, 2, 4, 3).reshape(n, h, w, c)
    return apply(prim, x, name="channel_shuffle")


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col (reference: operators/unfold_op.cc)."""
    from .conv import _norm_tuple
    k = _norm_tuple(kernel_sizes, 2)
    s = _norm_tuple(strides, 2)
    d = _norm_tuple(dilations, 2)
    if isinstance(paddings, int):
        p = [(paddings, paddings), (paddings, paddings)]
    elif len(paddings) == 2:
        p = [(paddings[0], paddings[0]), (paddings[1], paddings[1])]
    else:
        p = [(paddings[0], paddings[2]), (paddings[1], paddings[3])]

    def prim(v):
        n, c, h, w = v.shape
        patches = jax.lax.conv_general_dilated_patches(
            v, filter_shape=k, window_strides=s,
            padding=p, rhs_dilation=d,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        # patches: (N, C*kh*kw, oh, ow) -> (N, C*kh*kw, oh*ow)
        return patches.reshape(n, c * k[0] * k[1], -1)

    return apply(prim, x, name="unfold")


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1,
         name=None):
    from .conv import _norm_tuple
    out_hw = _norm_tuple(output_sizes, 2)
    k = _norm_tuple(kernel_sizes, 2)
    s = _norm_tuple(strides, 2)
    d = _norm_tuple(dilations, 2)
    p = _norm_tuple(paddings, 2) if not isinstance(paddings, int) else (paddings, paddings)

    def prim(v):
        n, ckk, L = v.shape
        c = ckk // (k[0] * k[1])
        oh = (out_hw[0] + 2 * p[0] - d[0] * (k[0] - 1) - 1) // s[0] + 1
        ow = (out_hw[1] + 2 * p[1] - d[1] * (k[1] - 1) - 1) // s[1] + 1
        vv = v.reshape(n, c, k[0], k[1], oh, ow)
        out = jnp.zeros((n, c, out_hw[0] + 2 * p[0], out_hw[1] + 2 * p[1]),
                        dtype=v.dtype)
        for i in range(k[0]):
            for j in range(k[1]):
                hi = i * d[0]
                wj = j * d[1]
                out = out.at[:, :, hi:hi + oh * s[0]:s[0],
                             wj:wj + ow * s[1]:s[1]].add(vv[:, :, i, j])
        return out[:, :, p[0]:out.shape[2] - p[0], p[1]:out.shape[3] - p[1]]

    return apply(prim, x, name="fold")


def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None):
    def prim(a, b):
        num = jnp.sum(a * b, axis=axis)
        den = jnp.sqrt(jnp.sum(a * a, axis=axis)) * jnp.sqrt(jnp.sum(b * b, axis=axis))
        return num / jnp.maximum(den, eps)
    return apply(prim, x1, x2, name="cosine_similarity")


def bilinear(x1, x2, weight, bias=None, name=None):
    def prim(a, b, w, *mb):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        if mb:
            out = out + mb[0]
        return out
    if bias is not None:
        return apply(prim, x1, x2, weight, bias, name="bilinear")
    return apply(prim, x1, x2, weight, name="bilinear")


def class_center_sample(label, num_classes, num_samples, group=None):
    raise NotImplementedError("class_center_sample: PS-oriented; out of scope")
