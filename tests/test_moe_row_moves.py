"""The row moves of the dropless expert layer (ops/pallas/row_moves.py) against
the jnp rules of incubate/moe.py, which stay the path off the TPU and are the
oracle here: each kernel, interpreted, forward and through `jax.vjp`, in both
float types, under routings that fill a tile exactly, overflow it by one row,
put every pair on one expert or none here; rows of tiles past `num_tiles`
poisoned, since nobody may read them. And `_route_plan`, rewritten without
its gathers, against the function it replaced, integer for integer."""
import functools
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
from paddle_tpu.incubate import moe  # noqa: E402
from paddle_tpu.ops.pallas import flash_attention, grouped_matmul, row_moves  # noqa: E402
from paddle_tpu.profiler import metrics  # noqa: E402

TM, K, E = 32, 4, 8
HELD = [1, 4, 6]                          # three experts held, slots 0..2
# (dtype, columns, picks a token): a row of 1024 words, whole (8, 128) tiles,
# in both types at 4 picks; the Kimi Linear width, 1152 words, at its 8 picks
# (9 lane chunks, looped over in the kernels that write tokens: no copy of
# such a row starts or ends on a tile); a float32 row of 384 words (3 lane
# chunks)
ROWS = [(jnp.float32, 1024, 4), (jnp.bfloat16, 2048, 4), (jnp.bfloat16, 2304, 8),
        (jnp.float32, 384, 4)]
ROW_IDS = ["f32", "bf16", "bf16-1152w-8picks", "f32-384w"]


def lookup(e):
    table = np.full(e, -1, np.int32)
    table[HELD] = np.arange(3)
    return table


# ---------------------------------------------------------------------------
# the plan: today's function against the one it replaced

def plan_with_sorts_and_gathers(scores, bias, *, top_k, lookup, n_held, tm, rows):
    """`_route_plan` as PR 27 wrote it: two argsorts, gathers of every row."""
    n = scores.shape[0]
    pairs = n * top_k
    _, idx = jax.lax.top_k(scores + bias.astype(scores.dtype), top_k)
    local = jnp.asarray(lookup)[idx].reshape(-1)
    held = local >= 0
    key = jnp.where(held, local, n_held)
    counts = jnp.sum(key[:, None] == jnp.arange(n_held, dtype=key.dtype),
                     axis=0, dtype=jnp.int32)
    order = jnp.argsort(key, stable=True)
    rank = jnp.argsort(order)
    tiles = jnp.maximum((counts + tm - 1) // tm, 1)
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tm
    sorted_start = jnp.cumsum(counts) - counts
    slot = jnp.maximum(local, 0)
    pair_row = jnp.where(held, row_start[slot] + rank - sorted_start[slot], rows)
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(rows // tm, dtype=jnp.int32),
                         side="right"), n_held - 1).astype(jnp.int32)
    row_group = jnp.repeat(tile_group, tm, total_repeat_length=rows)
    at = jnp.arange(rows, dtype=jnp.int32) - row_start[row_group]
    row_valid = at < counts[row_group]
    row_pair = jnp.where(
        row_valid, order[jnp.clip(sorted_start[row_group] + at, 0, pairs - 1)], 0)
    return (idx.astype(jnp.int32), pair_row.reshape(n, top_k).astype(jnp.int32),
            row_pair.astype(jnp.int32), row_valid, tile_group,
            tile_end[-1].astype(jnp.int32), counts)


def routing(case, n, seed=0, k=K):
    """(scores, bias) over 2k experts that route `n` tokens, k picks each, as
    `case` says."""
    e = 2 * k
    rng = np.random.default_rng(seed)
    scores, bias = rng.random((n, e)).astype(np.float32), np.zeros(e, np.float32)
    absent = [x for x in range(e) if x not in HELD]
    if case == "one-expert":        # every token: held expert 4 and absent ones
        bias[[4] + absent[:k - 1]] = 10.0
    elif case == "none-here":       # every token: experts held elsewhere
        bias[absent[:k]] = 10.0
    elif case == "all-here":        # every token: the three held, the rest absent
        bias[HELD + absent[:k - 3]] = 10.0
    return jnp.asarray(scores), jnp.asarray(bias)


def plan_of(case, n, seed=0, plan=None, k=K):
    rows = -(-n * k // TM) * TM + 3 * TM
    scores, bias = routing(case, n, seed, k)
    return (plan or moe._route_plan)(scores, bias, top_k=k, lookup=lookup(2 * k),
                                     n_held=3, tm=TM, rows=rows)


CASES = [("even", 80), ("one-expert", 64), ("one-expert", 65), ("none-here", 50),
         ("all-here", 48), ("even", 33)]


@pytest.mark.parametrize("case,n", CASES + [("even", 96 + s) for s in range(20)],
                         ids=lambda v: str(v))
def test_plan_gives_the_integers_it_gave_with_sorts_and_gathers(case, n):
    got = jax.jit(functools.partial(plan_of, case, n, n))()
    want = plan_of(case, n, n, plan_with_sorts_and_gathers)
    for name, a, b in zip(("idx", "pair_row", "row_pair", "row_valid", "tile_group",
                           "num_tiles", "counts"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def test_the_cases_are_the_routings_they_are_named_for():
    counts = {c: np.asarray(plan_of(c, n)[6]) for c, n in CASES[:5]}
    tiles = {c: int(plan_of(c, n)[5]) for c, n in CASES[:5]}
    assert counts["one-expert"].tolist() == [0, 65, 0] and tiles["one-expert"] == 5
    assert np.asarray(plan_of("one-expert", 64)[6]).tolist() == [0, 64, 0]
    assert int(plan_of("one-expert", 64)[5]) == 4      # two full tiles, two empty ones
    assert counts["none-here"].tolist() == [0, 0, 0] and tiles["none-here"] == 3
    assert counts["all-here"].tolist() == [48, 48, 48]
    assert counts["even"].min() > 0


# ---------------------------------------------------------------------------
# the kernels, interpreted, against the jnp rules

@pytest.fixture
def interpreted(monkeypatch):
    """The kernels as a TPU would take them, run by the Pallas interpreter."""
    for name in ("pack_rows", "rows_from_tokens", "tokens_from_rows", "pair_dots"):
        monkeypatch.setattr(row_moves, name, functools.partial(
            getattr(row_moves, name), interpret=True))


def poisoned(a, num_tiles, tm=TM):
    """`a` with NaN in every row of the tiles past the ones in use."""
    past = jnp.arange(a.shape[0])[:, None] >= num_tiles * tm
    return jnp.where(past, jnp.nan, a).astype(a.dtype)


def close(got, want, dtype, what, tol=None):
    tol = tol or ({"rtol": 1e-5, "atol": 1e-5} if dtype == jnp.float32
                  else {"rtol": 2e-2, "atol": 2e-2})
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all(), what
    np.testing.assert_allclose(got, want, err_msg=what, **tol)


@pytest.mark.parametrize("dtype,h,k", ROWS, ids=ROW_IDS)
@pytest.mark.parametrize("case,n", CASES, ids=lambda v: str(v))
def test_each_move_and_its_transpose_match_the_jnp_rule(interpreted, case, n, dtype, h, k):
    _, pair_row, row_pair, row_valid, _, num_tiles, _ = plan_of(case, n, k=k)
    rows, used = row_pair.shape[0], int(num_tiles) * TM
    held = row_moves.held_pairs(pair_row, rows, row_moves.token_block(n, k, h, dtype))
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((n, h)), dtype)
    y = poisoned(jnp.asarray(rng.standard_normal((rows, h)), dtype), num_tiles)
    w = jnp.asarray(rng.random((n, k)), jnp.float32)
    g_tokens = jnp.asarray(rng.standard_normal((n, h)), dtype)

    def gather(kernel):
        return jax.vjp(lambda v: moe._gather_rows(
            v, row_pair, row_valid, pair_row, held if kernel else (), num_tiles,
            TM, kernel), x)

    (xs, back), (xs_ref, back_ref) = gather(True), gather(False)
    # the tiles in use, bit for bit, their padding rows zero; the rest unread
    np.testing.assert_array_equal(np.asarray(xs[:used], np.float32),
                                  np.asarray(xs_ref[:used], np.float32))
    assert not np.asarray(xs[:used], np.float32)[~np.asarray(row_valid[:used])].any()
    close(back(y)[0], back_ref(y)[0], dtype, "gather's transpose")

    def combine(kernel):
        return jax.vjp(lambda a, b: moe._combine_rows(
            a, b, pair_row, row_pair, row_valid, held if kernel else (), num_tiles,
            TM, kernel), y, w)

    (out, back), (out_ref, back_ref) = combine(True), combine(False)
    close(out, out_ref, dtype, "combine")
    (dy, dw), (dy_ref, dw_ref) = back(g_tokens), back_ref(g_tokens)
    np.testing.assert_array_equal(np.asarray(dy[:used], np.float32),
                                  np.asarray(dy_ref[:used], np.float32))
    assert dw.dtype == w.dtype
    # a float32 sum of a row's thousand products, in another order
    close(dw, dw_ref, jnp.float32, "dw", {"rtol": 1e-4, "atol": 1e-3})
    if case == "none-here":
        assert not np.asarray(out, np.float32).any() and not np.asarray(dw).any()


@pytest.mark.parametrize("dtype,h,per_row", [
    (jnp.float32, 1024, 8), (jnp.bfloat16, 2048, 8), (jnp.bfloat16, 2304, 9),
    (jnp.float32, 384, 3), (jnp.float32, 4224, 33)],
    ids=ROW_IDS[:2] + ["bf16-1152w", "f32-384w", "f32-4224w-looped"])
def test_packed_rows_are_the_rows(interpreted, dtype, h, per_row):
    """pack_rows is a bijection a row at a time: what rows_from_tokens reads
    back through the identity plan is the array, for a row count that is no
    multiple of the tile as well; the packed form is the array's bytes
    whatever the row's width (33 lane chunks: more than a kernel's text
    unrolls)."""
    n = 40
    x = jnp.asarray(np.random.default_rng(1).standard_normal((n, h)), dtype)
    packed = row_moves.pack_rows(x, tm=TM)
    assert packed.shape == (64 * per_row, 128) and packed.dtype == jnp.uint32
    tile_rows = jnp.asarray([32, 8], jnp.int32)
    back = row_moves.rows_from_tokens(packed, jnp.arange(64, dtype=jnp.int32),
                                      tile_rows, 2, k=1, h=h, dtype=dtype, tm=TM)
    np.testing.assert_array_equal(np.asarray(back[:n], np.float32),
                                  np.asarray(x, np.float32))
    assert not np.asarray(back[n:], np.float32).any()


def test_the_rows_the_kernels_take():
    assert row_moves.words(2048, jnp.bfloat16) == 1024
    assert row_moves.words(2304, jnp.bfloat16) == 1152      # 9 lane chunks
    assert row_moves.words(1024, jnp.bfloat16) == 512       # half a tile a row
    assert row_moves.words(384, jnp.float32) == 384
    assert row_moves.words(2048, jnp.float16) is None       # another type
    assert row_moves.words(384, jnp.bfloat16) is None       # a lane chunk and a half
    assert row_moves.words(2305, jnp.bfloat16) is None      # an odd width
    assert row_moves.words(192, jnp.float32) is None
    # the block of tokens follows the picks and the row's bytes: 8 x 256 rows of
    # 16 KB would take the whole of the kernels' VMEM
    assert row_moves.token_block(8192, 8, 2304, jnp.bfloat16) == 256
    assert row_moves.token_block(8192, 8, 4096, jnp.float32) == 128
    assert row_moves.token_block(40, 4, 1024, jnp.float32) == 64


# the parent's (commit 1f27c0b) six kernels traced at the LFM2 cell's shapes
# (8192 tokens, 4 picks, 2048 bf16, a buffer of 34,816 rows): SHA-256 of the
# jaxpr's text with the source positions taken out. A row of whole tiles takes
# the kernels it took, letter for letter, whatever other rows are given.
PARENT_KERNELS = {
    "pack": "caac66fb57e4ea7bbb89286c0cb1af17c49f033ebead7acafa0d91f1502e7af5",
    "gather": "f00b59ce054953804126dd2a8db7b5718294a13cb20d0bb9c69e5de3cc243e85",
    "weighted-rows": "481ecf32095ead3e8f10e432e6a380c18c5bc732dfe9b4d646a0f10dc7a82473",
    "add-back": "c9e15216f0ec3eee6e80b03d948a1d1e27c4eaf084eeb1a4679eff061d2fc76a",
    "combine": "c671bdafdc809a31317e843dd278f1da086260a664138f2d0f8bad8385a4e6ca",
    "pair-dots": "124f8334a2fa01cb2fe674eceaf77341ba2efc890d83e0e818a4049f1a49d932",
}


@pytest.mark.parametrize("move", list(PARENT_KERNELS))
def test_rows_of_whole_tiles_trace_the_parents_kernels(move):
    n, k, h, dt = 2 * 4096, 4, 2048, jnp.bfloat16
    rows = n * k + 8 * grouped_matmul.ROW_TILE
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    tokens3, rows3 = (sds((r * 8, 128), jnp.uint32) for r in (n, rows))
    row_pair, tile_rows = sds((rows,), i32), sds((rows // grouped_matmul.ROW_TILE,), i32)
    used, pair_row, w = sds((), i32), sds((n, k), i32), sds((n, k), jnp.float32)
    held = (sds((n * k,), i32), sds((n * k,), i32), sds((n // row_moves.TOKEN_BLOCK + 1,), i32))
    f, args, kw = {
        "pack": (row_moves.pack_rows, (sds((rows, h), dt), used), {}),
        "gather": (row_moves.rows_from_tokens, (tokens3, row_pair, tile_rows, used),
                   dict(k=k, h=h, dtype=dt)),
        "weighted-rows": (row_moves.rows_from_tokens, (tokens3, row_pair, tile_rows, used, w),
                          dict(k=k, h=h, dtype=dt)),
        "add-back": (row_moves.tokens_from_rows, (rows3, pair_row, held), dict(h=h, dtype=dt)),
        "combine": (row_moves.tokens_from_rows, (rows3, pair_row, held, w), dict(h=h, dtype=dt)),
        "pair-dots": (row_moves.pair_dots, (rows3, pair_row, held, sds((n, h), dt)), {}),
    }[move]
    text = str(jax.make_jaxpr(lambda *a: f(*a, **kw))(*args))
    text = re.sub(r" at [^\s\]]*:\d+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_KERNELS[move]


# ---------------------------------------------------------------------------
# the layer: which path, and that the kernels' path is the same layer

@pytest.fixture
def as_on_a_tpu(monkeypatch, interpreted):
    """The layer choosing as it does on a TPU (kernels for the row moves),
    every kernel interpreted, and every row the kernels never write poisoned."""
    monkeypatch.setattr(flash_attention, "_interpret", lambda x=None: False)
    product = grouped_matmul.grouped_matmul
    monkeypatch.setattr(grouped_matmul, "grouped_matmul",
                        lambda a, w, tg, nt, tm, interp: poisoned(
                            product(a, w, tg, nt, tm, True), nt, tm))
    gathered = row_moves.rows_from_tokens
    monkeypatch.setattr(row_moves, "rows_from_tokens",
                        lambda x3, rp, tr, nt, *a, **kw: poisoned(
                            gathered(x3, rp, tr, nt, *a, **kw), nt, kw["tm"]))


def layer_and_input(held, dtype, h, k):
    paddle.seed(11)
    layer = moe.DroplessMoELayer(h, 64, max(E, 2 * k), k, held_experts=held)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 40, h)), dtype)
    return layer.to(dtype=jnp.dtype(dtype).name), x


def run_layer(layer, x):
    f32 = functools.partial(np.asarray, dtype=np.float32)
    xt = paddle.to_tensor(x, stop_gradient=False)
    out, load = layer(xt)
    paddle.sum(paddle.sin(out)).backward()
    grads = {n: f32(p.grad._val) for n, p in layer.named_parameters()
             if p.grad is not None}
    layer.clear_gradients()
    return f32(out._val), f32(xt.grad._val), grads, f32(load._val)


@pytest.mark.parametrize("dtype,h,k", [(jnp.float32, 1024, 2)] + ROWS[2:],
                         ids=["f32"] + ROW_IDS[2:])
@pytest.mark.parametrize("held", [[1, 4, 6], [7]], ids=["three-held", "one-held"])
def test_layer_through_the_kernels_is_the_layer_through_xla(held, request, dtype, h, k):
    layer, x = layer_and_input(held, dtype, h, k)
    # bfloat16: the kernels' float32 sums, in another order, rounded as stored
    tol = {"rtol": 2e-5, "atol": 2e-5} if dtype == jnp.float32 else {"rtol": 3e-2, "atol": 3e-2}
    reg = metrics.get_registry()
    before = reg.counter_value("moe.row_kernel_total"), reg.counter_value("moe.row_xla_total")
    want = run_layer(layer, x)
    assert reg.counter_value("moe.row_xla_total") == before[1] + 5     # the five moves
    assert reg.counter_value("moe.row_kernel_total") == before[0]
    request.getfixturevalue("as_on_a_tpu")
    got = run_layer(layer, x)
    assert reg.counter_value("moe.row_kernel_total") == before[0] + 5
    assert reg.counter_value("moe.row_xla_total") == before[1] + 5
    for a, b, what in zip(got[:2] + (got[3],), want[:2] + (want[3],), ("out", "dx", "load")):
        assert np.isfinite(a).all(), what
        np.testing.assert_allclose(a, b, err_msg=what, **tol)
    assert got[2].keys() == want[2].keys() and "w1" in got[2]
    for name in want[2]:
        assert np.isfinite(got[2][name]).all(), name
        np.testing.assert_allclose(got[2][name], want[2][name], err_msg=name, **tol)


def test_a_width_the_kernels_do_not_take_stays_with_xla(as_on_a_tpu):
    paddle.seed(3)
    layer = moe.DroplessMoELayer(192, 32, E, 2)       # a lane chunk and a half a row
    reg = metrics.get_registry()
    before = reg.counter_value("moe.row_kernel_total"), reg.counter_value("moe.row_xla_total")
    out, _ = layer(paddle.to_tensor(np.ones((1, 8, 192), np.float32)))
    assert np.isfinite(np.asarray(out._val)).all()
    assert reg.counter_value("moe.row_kernel_total") == before[0]
    assert reg.counter_value("moe.row_xla_total") == before[1] + 2


def test_a_compiled_step_on_the_cpu_holds_the_xla_moves():
    """`moe.row_xla_total` and `moe.row_kernel_total` say which path a step
    holds: on the CPU a `to_static` step of a small LFM2 traces the jnp rules."""
    from paddle_tpu.text.models.lfm2 import LFM2ForCausalLM
    paddle.seed(0)
    model = LFM2ForCausalLM(
        vocab_size=64, hidden_size=64, num_layers=2, layer_types=["conv", "full_attention"],
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        held_experts=[0, 1, 2, 3])
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())

    @paddle.jit.to_static
    def step(ids, labels):
        loss = model(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    reg = metrics.get_registry()
    before = reg.counter_value("moe.row_kernel_total"), reg.counter_value("moe.row_xla_total")
    ids = paddle.to_tensor(np.random.default_rng(0).integers(0, 64, (2, 32)))
    losses = [float(step(ids, ids)) for _ in range(4)]
    assert np.isfinite(losses).all()
    assert reg.counter_value("moe.row_xla_total") > before[1]
    assert reg.counter_value("moe.row_kernel_total") == before[0]
    assert "moe.row_xla_total" in reg.snapshot()["counters"]
