"""Mixture-of-Experts layer (TPU-native; GShard/Switch formulation).

The reference snapshot ships only the expert-parallel exchange ops
(global_scatter/global_gather, operators/collective/global_scatter_op.cc) with
no full MoE layer; this provides the layer the way a TPU framework should:
top-k gating → fixed-capacity einsum dispatch → per-expert MLP (batched over
the expert dim) → weighted combine. Under SPMD the expert dimension is
annotated to shard over the 'expert' (or 'model') mesh axis and XLA lowers
the dispatch/combine einsums into all-to-alls over ICI.
"""
from __future__ import annotations

import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import apply
from ..core.tensor import Tensor
from ..distributed.utils import combine_tokens, dispatch_tokens
from ..profiler import metrics as _metrics

__all__ = ["MoELayer", "DroplessMoELayer"]


class MoELayer(nn.Layer):
    """Top-k gated MoE over d_model → d_hidden → d_model expert MLPs.

    capacity_factor bounds tokens per expert per batch: capacity =
    ceil(k * N / E * capacity_factor); overflowing tokens pass through
    (residual) with zero expert contribution (Switch semantics).
    """

    def __init__(self, d_model, d_hidden, num_experts, top_k=2,
                 capacity_factor=1.25, gate_noise=0.0, expert_axis=None):
        super().__init__()
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.top_k = min(top_k, num_experts)
        self.capacity_factor = capacity_factor
        if gate_noise < 0:
            from ..framework.errors import InvalidArgumentError
            raise InvalidArgumentError(
                f"gate_noise must be >= 0, got {gate_noise}")
        self.gate_noise = gate_noise
        self.expert_axis = expert_axis  # mesh axis name for expert sharding
        self.gate = nn.Linear(d_model, num_experts, bias_attr=False)
        # batched expert parameters: (E, d_model, d_hidden) / (E, d_hidden, d_model)
        from ..nn import initializer as I
        self.w1 = self.create_parameter(
            [num_experts, d_model, d_hidden],
            default_initializer=I.KaimingNormal(fan_in=d_model))
        self.b1 = self.create_parameter(
            [num_experts, 1, d_hidden], is_bias=True)
        self.w2 = self.create_parameter(
            [num_experts, d_hidden, d_model],
            default_initializer=I.KaimingNormal(fan_in=d_hidden))
        self.b2 = self.create_parameter(
            [num_experts, 1, d_model], is_bias=True)
        self.aux_loss = None

    def forward(self, x):
        # x: (..., d_model) → flatten tokens
        orig_shape = list(x.shape)
        n_tokens = 1
        for s in orig_shape[:-1]:
            n_tokens *= int(s)
        xf = x.reshape([n_tokens, self.d_model])
        E = self.num_experts
        capacity = max(1, int(self.top_k * n_tokens / E
                              * self.capacity_factor))

        logits = self.gate(xf)                       # (N, E)
        if self.gate_noise > 0 and self.training:
            # GShard-style jittered gating: seeded through the global
            # generator (paddle.seed reproducible, consumed per step like
            # dropout) and OFF in eval mode so inference routing is
            # deterministic.
            from ..core.random import next_key_data
            kd = next_key_data()
            scale = float(self.gate_noise)

            def jitter(lg, key_data):
                key = jax.random.wrap_key_data(key_data)
                return lg + scale * jax.random.normal(key, lg.shape,
                                                      lg.dtype)
            logits = apply(jitter, logits, kd, name="moe_gate_noise")
        probs = nn.functional.softmax(logits, axis=-1)

        # load-balancing auxiliary loss (GShard eq.4): E * sum_e f_e * p_e
        def aux(pr):
            me = jnp.mean(pr, axis=0)
            # fraction of tokens whose argmax is e
            ce = jnp.mean(jax.nn.one_hot(jnp.argmax(pr, axis=1), E,
                                         dtype=pr.dtype), axis=0)
            return jnp.sum(me * ce) * E
        self.aux_loss = apply(aux, probs, name="moe_aux_loss")

        combined = None
        residual_w = None
        for k in range(self.top_k):
            def topk_idx(pr, kk=k):
                # k-th choice per token (mask out previous choices)
                top = jax.lax.top_k(pr, kk + 1)[1]
                return top[:, kk]
            idx_k = apply(topk_idx, probs, name=f"moe_top{k}")
            buf, combine, keep = dispatch_tokens(xf, idx_k, E, capacity)
            expert_out = self._experts(buf)          # (E, C, d_model)
            out_k = combine_tokens(expert_out, combine)  # (N, d_model)

            def gate_w(pr, ik, kp):
                w = jnp.take_along_axis(pr, ik[:, None].astype(jnp.int32),
                                        axis=1)[:, 0]
                return (w * kp.astype(pr.dtype))[:, None]
            w_k = apply(gate_w, probs, idx_k, keep, name="moe_gate_w")
            term = out_k * w_k
            combined = term if combined is None else combined + term
            residual_w = w_k if residual_w is None else residual_w + w_k

        # Switch-style residual: tokens the experts didn't (fully) absorb
        # pass through scaled by the unapplied gate mass — a fully dropped
        # token (all top-k over capacity) comes out as x unchanged.
        def residual(xv, cw):
            return xv * jnp.clip(1.0 - cw, 0.0, 1.0)
        combined = combined + apply(residual, xf, residual_w,
                                    name="moe_residual")
        out = combined.reshape(orig_shape)
        return out

    def _experts(self, buf):
        """Per-expert MLP batched over E; annotated for expert-axis SPMD."""
        axis = self.expert_axis

        def prim(b, w1, b1, w2, b2):
            if axis is not None:
                try:
                    from jax.sharding import PartitionSpec as P
                    b = jax.lax.with_sharding_constraint(
                        b, P(axis, None, None))
                except Exception:
                    pass
            h = jnp.einsum("ecd,edh->ech", b, w1) + b1
            h = jax.nn.gelu(h)
            return jnp.einsum("ech,ehd->ecd", h, w2) + b2

        return apply(prim, buf, self.w1, self.b1, self.w2, self.b2,
                     name="moe_experts")


# ---------------------------------------------------------------------------
# the dropless layer

def _route_plan(scores, bias, *, top_k, lookup, n_held, tm, rows):
    """Which experts every token picks and where each picked (token, expert)
    pair lands in a buffer of `rows` rows laid out for the grouped products:
    the pairs of one held expert are contiguous, in token order, and start at
    a multiple of `tm` (an expert nobody picked still owns one tile). All
    integers; nothing here is differentiated.

    Returns idx (N, k) the experts picked; pair_row (N, k) the buffer row of
    each pair, `rows` where its expert is not held here; row_pair (rows,)
    the pair a buffer row holds and row_valid whether it holds one;
    tile_group (rows / tm,) the held expert of each tile; num_tiles the
    tiles in use; counts (n_held,) the rows of each held expert."""
    return _plan(scores, bias, top_k=top_k, lookup=tuple(int(e) for e in lookup),
                 n_held=n_held, tm=tm, rows=rows)


# one program where it runs eagerly (to_static's discovery pass), not one an
# operation: the plan is some eighty of them
@functools.partial(jax.jit, static_argnames=("top_k", "lookup", "n_held", "tm", "rows"))
def _plan(scores, bias, *, top_k, lookup, n_held, tm, rows):
    n = scores.shape[0]
    pairs = n * top_k
    _, idx = jax.lax.top_k(scores + bias.astype(scores.dtype), top_k)
    local = jnp.asarray(lookup, jnp.int32)[idx].reshape(-1)   # held expert's slot, or -1
    held = local >= 0
    key = jnp.where(held, local, n_held)
    # The key takes n_held + 1 values, so a pair's place in a stable sort by it
    # is a count and a running sum, and every table below has n_held entries:
    # read through `of` (a compare and a sum), never by a gather of thousands
    # of indices, which XLA runs one index at a time.
    of = key[:, None] == jnp.arange(n_held, dtype=key.dtype)       # (pairs, held)
    counts = jnp.sum(of, axis=0, dtype=jnp.int32)
    tiles = jnp.maximum((counts + tm - 1) // tm, 1)
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tm
    sorted_start = jnp.cumsum(counts) - counts
    # the pairs of its own expert before a pair, in pair order
    before = jnp.sum(jnp.where(of, jnp.cumsum(of, axis=0, dtype=jnp.int32) - 1, 0),
                     axis=1)
    pair_row = jnp.where(held, jnp.sum(jnp.where(of, row_start, 0), axis=1) + before,
                         rows)
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(rows // tm, dtype=jnp.int32),
                         side="right"), n_held - 1).astype(jnp.int32)
    # An expert's rows are a run of the sorted pairs moved down by its tiles'
    # padding: one roll an expert, kept where the row is one of its own.
    row = jnp.arange(rows, dtype=jnp.int32)[:, None]
    mine = (row >= row_start) & (row < row_start + counts)          # (rows, held)
    row_valid = jnp.any(mine, axis=1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)         # sorted position -> pair
    order = jnp.concatenate([order, jnp.zeros((rows - pairs,), jnp.int32)])
    row_pair = jnp.zeros((rows,), jnp.int32)
    for e in range(n_held):
        row_pair = jnp.where(mine[:, e], jnp.roll(order, row_start[e] - sorted_start[e]),
                             row_pair)
    return (idx.astype(jnp.int32), pair_row.reshape(n, top_k).astype(jnp.int32),
            row_pair.astype(jnp.int32), row_valid, tile_group,
            tile_end[-1].astype(jnp.int32), counts)


# The five moves of rows between the tokens and the sorted buffer: the buffer
# from the tokens and that move's transpose (_gather_rows), the tokens from the
# buffer's weighted rows and that move's two backward moves (_combine_rows).
# Each has two paths with one meaning. `kernel` false: the jnp rules below, XLA
# gathers over the whole buffer; the path off the TPU and the oracle of the
# other. `kernel` true: ops/pallas/row_moves.py, one copy a row that is there,
# under run-time bounds; rows of tiles at or past `num_tiles` are then never
# written (the grouped products never read them). `held` is what
# row_moves.held_pairs makes of pair_row, () on the jnp path.

def _count_moves(kernel, n=1):
    """The path `n` traced row moves took, in the registry."""
    _metrics.get_registry().inc_counter(
        "moe.row_kernel_total" if kernel else "moe.row_xla_total", n)


def _tile_rows(row_valid, tm):
    return jnp.sum(row_valid.reshape(-1, tm), axis=1, dtype=jnp.int32)


def _gather(x, row_pair, row_valid, pair_row, num_tiles, tm, kernel):
    _count_moves(kernel)
    if not kernel:
        return jnp.where(row_valid[:, None], x[row_pair // pair_row.shape[1]], 0)
    from ..ops.pallas import row_moves
    return row_moves.rows_from_tokens(
        row_moves.pack_rows(x, tm=tm), row_pair, _tile_rows(row_valid, tm),
        num_tiles, k=pair_row.shape[1], h=x.shape[1], dtype=x.dtype, tm=tm)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _gather_rows(x, row_pair, row_valid, pair_row, held, num_tiles, tm, kernel):
    """The buffer's rows from the tokens: row r holds the token of the pair
    row_pair[r], zero where it holds no pair. Its transpose adds each token's
    rows back, and is written as a gather over `pair_row` (a token's pairs and
    their rows) because a scatter-add of thousands of rows runs one row at a
    time."""
    return _gather(x, row_pair, row_valid, pair_row, num_tiles, tm, kernel)


def _gather_rows_fwd(x, row_pair, row_valid, pair_row, held, num_tiles, tm, kernel):
    return (_gather(x, row_pair, row_valid, pair_row, num_tiles, tm, kernel),
            (pair_row, held, num_tiles))


def _gather_rows_bwd(tm, kernel, res, g):
    pair_row, held, num_tiles = res
    _count_moves(kernel)
    if kernel:
        from ..ops.pallas import row_moves
        dx = row_moves.tokens_from_rows(
            row_moves.pack_rows(g, num_tiles, tm=tm), pair_row, held,
            h=g.shape[1], dtype=g.dtype)
    else:
        back = jnp.take(g, pair_row, axis=0, mode="fill", fill_value=0)  # (N, k, H)
        dx = jnp.sum(back.astype(jnp.float32), axis=1).astype(g.dtype)
    return dx, None, None, None, None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def _combine(y, w, pair_row, held, num_tiles, tm, kernel):
    """(out, what the backward pass keeps of y): y itself, or its packed rows."""
    _count_moves(kernel)
    if not kernel:
        picked = jnp.take(y, pair_row, axis=0, mode="fill", fill_value=0)
        return jnp.sum(picked.astype(jnp.float32) * w[..., None],
                       axis=1).astype(y.dtype), y
    from ..ops.pallas import row_moves
    packed = row_moves.pack_rows(y, num_tiles, tm=tm)
    return row_moves.tokens_from_rows(packed, pair_row, held, w, h=y.shape[1],
                                      dtype=y.dtype), packed


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _combine_rows(y, w, pair_row, row_pair, row_valid, held, num_tiles, tm, kernel):
    """out[t] = sum_j w[t, j] * y[pair_row[t, j]], a pair whose expert is
    not held adding nothing: float32 sum, y's dtype out. The transpose is a
    gather too: row r gets w * the cotangent of the one token it serves."""
    return _combine(y, w, pair_row, held, num_tiles, tm, kernel)[0]


def _combine_rows_fwd(y, w, pair_row, row_pair, row_valid, held, num_tiles, tm, kernel):
    out, kept = _combine(y, w, pair_row, held, num_tiles, tm, kernel)
    return out, (kept, w, pair_row, row_pair, row_valid, held, num_tiles)


def _combine_rows_bwd(tm, kernel, res, g):
    y, w, pair_row, row_pair, row_valid, held, num_tiles = res
    k = w.shape[1]
    _count_moves(kernel, 2)
    if kernel:
        from ..ops.pallas import row_moves
        dy = row_moves.rows_from_tokens(
            row_moves.pack_rows(g, tm=tm), row_pair, _tile_rows(row_valid, tm),
            num_tiles, w, k=k, h=g.shape[1], dtype=g.dtype, tm=tm)
        dw = row_moves.pair_dots(y, pair_row, held, g)
    else:
        row_w = w.reshape(-1)[row_pair]
        dy = jnp.where(row_valid[:, None],
                       g[row_pair // k].astype(jnp.float32) * row_w[:, None],
                       0).astype(y.dtype)
        picked = jnp.take(y, pair_row, axis=0, mode="fill", fill_value=0)
        dw = jnp.sum(picked.astype(jnp.float32)
                     * g[:, None, :].astype(jnp.float32), axis=-1)
    return dy, dw.astype(w.dtype), None, None, None, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)

# every live dropless layer, for the registry's pull-style readings below
_LAYERS = weakref.WeakSet()


def _layer_totals(name):
    """`name`'s device counter of every live layer that keeps it (the
    balance loss's two only where the layer computes one)."""
    return [float(getattr(layer, name)._val) for layer in _LAYERS
            if hasattr(layer, name)]


def _worst_mean_a_call(name):
    """The largest, over the live layers that keep `name` and have been
    called, of that counter over the layer's calls; 0 where none has."""
    return max((float(getattr(layer, name)._val) / float(layer.calls_total._val)
                for layer in _LAYERS
                if hasattr(layer, name) and float(layer.calls_total._val)), default=0.0)


# Routing counters kept on the device and fetched only when the registry is
# asked (a snapshot, an export): a step pays three scalar adds and no host
# round trip. Totals over every live layer since it was built.
_metrics.get_registry().register_counter_fn(
    "moe.rows_here_total", lambda: sum(_layer_totals("rows_total")))
_metrics.get_registry().register_counter_fn(
    "moe.layer_calls_total", lambda: sum(_layer_totals("calls_total")))
_metrics.get_registry().register_counter_fn(
    "moe.balance_loss_total", lambda: sum(_layer_totals("balance_total")))


def _register_gauges():
    """At import and again whenever a layer is built: the registry's `reset`
    drops gauge functions, and their owner registers them again."""
    registry = _metrics.get_registry()
    registry.register_gauge_fn("moe.live_layers_count", lambda: len(_LAYERS))
    registry.register_gauge_fn(
        "moe.load_max_over_mean_ratio", lambda: _worst_mean_a_call("imbalance_total"))
    registry.register_gauge_fn(
        "moe.router_max_over_mean_ratio",
        lambda: _worst_mean_a_call("router_imbalance_total"))


_register_gauges()


def _balance(scores, picked, sequences, alpha):
    """(the sequence-wise balance loss, the picks of each published expert
    (E,) float32). scores (N, E) float32, the router's over every published
    expert; picked (N, k) their top k; the N tokens are `sequences` rows of
    N / sequences. A sequence b: f_e = E / (k T) x the picks of e among its T
    tokens, P_e = the mean of its scores of e; the loss is alpha x the mean
    over the sequences of sum_e f_e P_e (DeepSeek-V2, `seq_aux`). f is a
    count: the gradient flows through P alone."""
    n, e = scores.shape
    k, t = picked.shape[1], n // sequences
    chosen = picked[:, :, None] == jnp.arange(e, dtype=picked.dtype)     # (N, k, E)
    picks = jnp.sum(chosen, axis=1, dtype=jnp.float32).reshape(sequences, t, e)
    picks = jnp.sum(picks, axis=1)                                       # (B, E)
    f = jax.lax.stop_gradient(picks * (e / (k * t)))
    mean_score = jnp.mean(scores.reshape(sequences, t, e), axis=1)
    return (alpha * jnp.mean(jnp.sum(f * mean_score, axis=1)),
            jnp.sum(picks, axis=0))


class DroplessMoELayer(nn.Layer):
    """Sigmoid-routed, dropless expert layer that computes the part of the
    result its own experts give (DeepSeek-V3-style routing, as the LFM2
    mixture models use it).

    Every token is scored over all `num_experts` (the published count):
    s = sigmoid(x W_g) in float32, or with `score="softmax"` the softmax of
    x W_g over all of them (Qwen3-MoE-style routing: the weights are then the
    softmax's values renormalised over the picked). The `top_k` experts are the largest of
    s + expert_bias (a parameter that takes no gradient; a balancing rule
    outside this layer may move it); their weights are the un-biased scores
    normalised over the k picked, times `routed_scaling_factor`; with
    `renormalize=False` the scores as the router gave them, times the factor
    (DeepSeek-V2's `norm_topk_prob: false`: a softmax's top k then sum to
    less than 1). The layer
    holds `held_experts` (ids among the published ones; all by default) as
    stacked SwiGLU weights w1, w3 (held, d_model, d_hidden) and w2 (held,
    d_hidden, d_model), and returns sum over the picked experts *held here*
    of w_e * w2_e(silu(w1_e x) * w3_e x); the normalisation still runs over
    all k. With every expert held that is the whole layer; with a share,
    the shares' results add up to it (tests/test_dropless_moe.py), which is
    what an expert-parallel rank computes before the exchange. With
    `shared_width` a shared expert, one more SwiGLU every token goes through
    (scopes `linear`, `swiglu`), is added after the combine; every rank holds
    it whole, so the shares add up to the layer with it counted once.
    `absent="stand_in"` (default "drop": the above) gives an expert that is
    not held here the held expert of slot (its id mod the number held) as its
    stand-in, so the sum runs over all k picked and the rows here are tokens
    x k whatever the router picks: the rows a rank of a deployment is sent by
    all the ranks when routing is even, computed with the weights it has.
    That is no share of the published layer (experts a stride apart then
    share weights). There is no
    capacity and no dropped pair: the (token, expert) pairs are sorted by
    expert into a buffer sized for the worst routing (every pair held here),
    each held expert's rows tile-aligned, and one grouped product per
    projection (ops/pallas/grouped_matmul.py) runs over the tiles in use,
    so the work follows the rows routed here. So do the moves of rows into
    and out of the buffer on a TPU (ops/pallas/row_moves.py: one copy a row
    that is there); off it they are XLA's gathers and cost the buffer's size
    (`moe.row_kernel_total`, `moe.row_xla_total` count the moves traced by
    the path they took).

    Scopes: `moe_route` (scores, top-k, the plan, the gather), `moe_experts`
    (the grouped products), `swiglu`, `moe_combine`. Counters, on the device,
    moved by `record_load` with what `forward` returns beside the result
    (`rows_total`, `calls_total`, `imbalance_total`; the registry's
    `moe.rows_here_total`, `moe.layer_calls_total`, `moe.load_max_over_mean_ratio`).

    `balance_alpha` fixes at construction how many values `forward` returns:
    None (no such loss) gives (out, load), as every model built without it
    unpacks; a number, 0.0 included, gives (out, load, balance_loss, picks)
    and the two counters below. It is DeepSeek-V2's
    sequence-wise balance loss (`seq_aux`; `_balance`), a float32 scalar for
    the caller to add to its training loss, made from the scores the layer
    already has and the picks the plan already made, under the scope
    `moe_balance_loss`. Its f runs over the `num_experts` published experts,
    whatever is held here and whatever stands in for what: the router is
    whole on every rank, so every share computes the same loss. The first
    axis of a three-dimensional `x` counts the sequences. `picks` (the picks
    of each published expert) go with `load` to `record_load`, which then
    also moves `balance_total` and `router_imbalance_total` (the registry's
    `moe.balance_loss_total`, to be divided by `moe.layer_calls_total`, and
    `moe.router_max_over_mean_ratio`: the most-picked published expert's
    picks over the mean of all, which `moe.load_max_over_mean_ratio` cannot
    see where stand-ins fold the published experts onto the held slots).
    """

    def __init__(self, d_model, d_hidden, num_experts, top_k,
                 held_experts=None, routed_scaling_factor=1.0,
                 weight_attr=None, shared_width=None, score="sigmoid",
                 absent="drop", renormalize=True, balance_alpha=None):
        super().__init__()
        from ..ops.pallas.grouped_matmul import ROW_TILE
        if score not in ("sigmoid", "softmax"):
            from ..framework.errors import InvalidArgumentError
            raise InvalidArgumentError(
                f"score {score!r} is neither 'sigmoid' nor 'softmax'")
        if absent not in ("drop", "stand_in"):
            from ..framework.errors import InvalidArgumentError
            raise InvalidArgumentError(
                f"absent {absent!r} is neither 'drop' nor 'stand_in'")
        self.score, self.absent = score, absent
        self.renormalize, self.balance_alpha = renormalize, balance_alpha
        held = list(range(num_experts)) if held_experts is None \
            else [int(e) for e in held_experts]
        if len(set(held)) != len(held) or not all(0 <= e < num_experts for e in held):
            from ..framework.errors import InvalidArgumentError
            raise InvalidArgumentError(
                f"held_experts {held} are not distinct ids below {num_experts}")
        self.d_model, self.d_hidden = d_model, d_hidden
        self.num_experts, self.top_k = num_experts, top_k
        self.held_experts = held
        self.routed_scaling_factor = routed_scaling_factor
        self._row_tile = ROW_TILE
        # published expert -> the slot of the stacked weights that computes it
        self._lookup = np.full(num_experts, -1, np.int32) if absent == "drop" \
            else (np.arange(num_experts) % len(held)).astype(np.int32)
        self._lookup[held] = np.arange(len(held), dtype=np.int32)
        self.gate = nn.Linear(d_model, num_experts, weight_attr=weight_attr,
                              bias_attr=False)
        self.expert_bias = self.create_parameter(
            [num_experts], attr=nn.ParamAttr(trainable=False),
            dtype="float32", default_initializer=nn.initializer.Constant(0.0))
        n = len(held)
        self.w1 = self.create_parameter([n, d_model, d_hidden], attr=weight_attr)
        self.w3 = self.create_parameter([n, d_model, d_hidden], attr=weight_attr)
        self.w2 = self.create_parameter([n, d_hidden, d_model], attr=weight_attr)
        # a shared expert: one more SwiGLU of `shared_width` that every token
        # goes through, added after the combine. It is whole on every chip
        # (data parallelism leaves it so), so across shares it counts once
        self.shared = None if shared_width is None else nn.SwiGLUFFN(
            d_model, shared_width, weight_attr=weight_attr)
        for name in ("rows_total", "calls_total", "imbalance_total") + (
                () if balance_alpha is None
                else ("balance_total", "router_imbalance_total")):
            self.register_buffer(name, Tensor(jnp.zeros((), jnp.float32)),
                                 persistable=False)
        _LAYERS.add(self)
        _register_gauges()

    def buffer_rows(self, n_tokens):
        """Rows of the sorted buffer for `n_tokens` tokens: every pair held
        here, each held expert's rows rounded up to a tile."""
        tm = self._row_tile
        pairs = n_tokens * self.top_k
        return (pairs + tm - 1) // tm * tm + len(self.held_experts) * tm

    def forward(self, x):
        """(..., d_model) -> (the same shape, load): `load` is the rows each
        held expert got, float32 (held,). The caller adds it to the counters
        (`record_load`) outside any rematerialised region, where state writes
        are dropped: the layer writes no state itself."""
        from ..nn import functional as F
        from ..ops.pallas import row_moves
        from ..ops.pallas.flash_attention import _interpret
        from ..ops.pallas.grouped_matmul import grouped_matmul
        shape = list(x.shape)
        xf = x.reshape([-1, self.d_model])
        n, k, tm = xf.shape[0], self.top_k, self._row_tile
        rows = self.buffer_rows(n)
        scale = self.routed_scaling_factor
        # sigmoid scores may all be near 0; a softmax's top k never sum to 0
        norm_eps = 1e-6 if self.score == "sigmoid" else 0.0
        # off the TPU the grouped products run interpreted and the row moves
        # are XLA's; on it both are kernels, where the moves take the width
        interp = _interpret(xf._val)
        kernel = not interp and bool(row_moves.words(self.d_model, xf._val.dtype))

        if self.score == "sigmoid":
            def score(v, wg):
                return jax.nn.sigmoid(jnp.matmul(
                    v, wg, preferred_element_type=jnp.float32))
        else:
            def score(v, wg):
                return jax.nn.softmax(jnp.matmul(
                    v, wg, preferred_element_type=jnp.float32), axis=-1)
        scores = apply(score, xf, self.gate.weight, name="moe_route")

        def plan(s, bias):
            out = _route_plan(s, bias, top_k=k, lookup=self._lookup,
                              n_held=len(self.held_experts), tm=tm, rows=rows)
            if kernel:
                out += row_moves.held_pairs(out[1], rows, row_moves.token_block(
                    n, k, self.d_model, xf._val.dtype))
            return out
        (idx, pair_row, row_pair, row_valid, tile_group, num_tiles,
         counts, *held) = apply(plan, scores.detach(), self.expert_bias,
                                name="moe_route")

        def weigh(s, picked):
            # s[t, picked[t, j]] read through a compare and a sum over the
            # experts (one term is not zero: exact), as the plan reads its
            # tables; its transpose is then no scatter either
            chosen = picked[:, :, None] == jnp.arange(s.shape[1], dtype=picked.dtype)
            w = jnp.sum(jnp.where(chosen, s[:, None, :], 0), axis=2)
            if not self.renormalize:
                return w * scale
            return w / (jnp.sum(w, axis=1, keepdims=True) + norm_eps) * scale
        w = apply(weigh, scores, idx, name="moe_route")
        xs = apply(lambda v, rp, rv, pr, nt, *hp: _gather_rows(
            v, rp, rv, pr, hp, nt, tm, kernel),
            xf, row_pair, row_valid, pair_row, num_tiles, *held, name="moe_route")

        def product(a, wts, tg, nt):
            return grouped_matmul(a, wts, tg, nt, tm, interp)
        h = F.swiglu(
            apply(product, xs, self.w1, tile_group, num_tiles, name="moe_experts"),
            apply(product, xs, self.w3, tile_group, num_tiles, name="moe_experts"))
        y = apply(product, h, self.w2, tile_group, num_tiles, name="moe_experts")
        out = apply(lambda yv, wv, pr, rp, rv, nt, *hp: _combine_rows(
            yv, wv, pr, rp, rv, hp, nt, tm, kernel),
            y, w, pair_row, row_pair, row_valid, num_tiles, *held,
            name="moe_combine").reshape(shape)
        if self.shared is not None:
            out = out + self.shared(x)
        load = apply(lambda c: c.astype(jnp.float32), counts, name="moe_route")
        if self.balance_alpha is None:
            return out, load
        sequences = shape[0] if len(shape) == 3 else 1
        balance, picks = apply(
            lambda s, picked: _balance(s, picked, sequences, self.balance_alpha),
            scores, idx, name="moe_balance_loss")
        return out, load, balance, picks.detach()

    def record_load(self, load, balance=None, picks=None):
        """Add one call's rows per held expert to the device counters; with
        them, where the layer computes a balance loss, the loss and the
        published experts' picks."""
        def add(rows, calls, imbalance, c):
            total = jnp.sum(c)
            return (rows + total, calls + 1.0, imbalance
                    + jnp.max(c) * c.shape[0] / jnp.maximum(total, 1.0))

        def add_balance(total, worst, loss, p):
            return total + loss, worst + jnp.max(p) * p.shape[0] / jnp.maximum(jnp.sum(p), 1.0)
        totals = (self.rows_total, self.calls_total, self.imbalance_total)
        from ..core import autograd
        with autograd.no_grad():
            new = list(apply(add, *totals, load, name="moe_route"))
            if balance is not None:
                totals += (self.balance_total, self.router_imbalance_total)
                new += apply(add_balance, *totals[3:], balance.detach(), picks,
                             name="moe_balance_loss")
        for t, v in zip(totals, new):
            t._value = v._val
