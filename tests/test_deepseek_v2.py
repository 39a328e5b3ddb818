"""The DeepSeek-V2 model (paddle_tpu/text/models/deepseek_v2.py) against the
plain reference (benchmarks/reference/deepseek_v2.py) on seeded weights, at a
small size on the CPU: YaRN's frequencies and scales by hand, the pairing
against a rotation written with complex numbers, the mixer, the expert layer
and its shares, the whole model's loss and gradients leaf by leaf and three
AdamW steps in float32 and in bfloat16 under both ways of cutting the expert
layer, the faults the comparison has to catch, the scopes and counters a
rematerialised step stages, and the edited layers at their old arguments
against the expressions they were before.

Tolerances. In float32 the program does the reference's arithmetic in
another order (a sorted buffer against a dense sum, one softmax against
blocks of rows): 1e-5 of a leaf's norm holds the loss and the layers
(8e-7 to 4e-6 measured), 1e-4 every leaf's gradient through three blocks
(to 5e-5 measured: the projections drawn at N(0, 0.5) make the softmax
sharp, and a sharp softmax carries rounding further). The bfloat16 bands are
beside their test. Every fault below moves a number by at least 30 times the
tolerance it is held to."""
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
from benchmarks.reference import deepseek_v2 as ref  # noqa: E402

CELL = "deepseek-v2-lite.pretrain-1chip-b1-s8192"
SEED = 7
TOL, GRAD_TOL = 1e-5, 1e-4
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}


def tiny(**over):
    """The cell's configuration with its widths cut, here and nowhere else:
    3 layers (the dense one and two expert layers), 16 published experts of
    which 4 are held, 3 picked a token, rows of 64 tokens."""
    cell = harness.load_cell(CELL)
    cfg = cell["cfg"]
    cfg.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
               intermediate_size=96, moe_intermediate_size=32, vocab_size=600,
               n_routed_experts_published=16, n_routed_experts=4,
               held_experts=[0, 1, 2, 3], num_experts_per_tok=3, num_layers=3,
               first_layer=0, weights_dtype="float32", recompute=False)
    cfg.update(over)
    cell["job"].update(batch=2, seq=64)
    return cell


def seeded(cell, dtype="float32", sharp=True):
    """Seeded leaves: with `sharp` the query and key/value projections at
    N(0, 0.5), so that attention is far from uniform and a fault in a
    rotation or in the softmax's scale shows; the other matrices 8 times the
    benchmark's 0.02 (unit-size products at hidden 64); the expert bias off
    zero."""
    shapes = {}
    for k, (shape, init) in cell["family"].reference.param_shapes(cell["cfg"]).items():
        if sharp and k.endswith(("q_w", "kv_a_w", "kv_b_w")):
            init = 0.5
        elif not isinstance(init, str):
            init = 8 * init
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

def test_yarn_frequencies_by_hand():
    full = {"qk_rope_head_dim": 64, "qk_nope_head_dim": 128, "rope_theta": 10000,
            "rope_scaling": YARN}
    assert ref.correction_range(full) == (10, 23)
    f = F.yarn_frequencies(64, 10000, YARN)
    np.testing.assert_array_equal(f, ref.frequencies(full))
    theta = lambda n: 10000.0 ** (-2 * n / 64)           # noqa: E731
    # pairs 0-10 keep their frequency, 23-31 are divided by 40, a ramp between:
    # gamma_16 = 1 - 6/13
    by_hand = {0: 1.0, 10: theta(10), 16: theta(16) * (7 / 13 + 6 / 13 / 40),
               23: theta(23) / 40, 31: theta(31) / 40}
    for n, want in by_hand.items():
        assert f[n] == pytest.approx(want, rel=1e-6), n
    assert np.all(f[:11] == (10000.0 ** (-np.arange(0, 22, 2) / 64)).astype(np.float32))
    # m(0.707) = 0.1 x 0.707 x ln 40 + 1 = 1.2608; cos and sin carry m / m = 1
    table, softmax = F.yarn_scales(YARN)
    assert table == 1.0 and softmax == pytest.approx(1.2608 ** 2, rel=1e-4)
    assert ref.scales(full) == (table, pytest.approx(192 ** -0.5 * softmax))
    assert 192 ** -0.5 * softmax == pytest.approx(0.11472, abs=5e-6)
    layer = paddle.nn.MultiHeadLatentAttention(
        64, 2, 32, 128, 64, 128, rope={"theta": 10000.0, "rope_scaling": YARN})
    assert layer.scale == pytest.approx(0.11472, abs=5e-6)
    assert paddle.nn.MultiHeadLatentAttention(64, 2, 32, 128, 64, 128).scale is None


def test_the_pairing_against_complex_numbers():
    """Pair n is entries (2n, 2n + 1): as a complex number turned by
    exp(i t f_n). The program stores the turned parts half-split, so its
    products, not its entries, are those of the complex rotation."""
    rng = np.random.default_rng(0)
    q = rng.normal(size=(2, 24, 3, 16)).astype(np.float32)
    k = rng.normal(size=(2, 24, 1, 16)).astype(np.float32)   # one key head under three
    f = F.yarn_frequencies(16, 10000, YARN).astype(np.float64)
    turn = np.exp(1j * np.arange(24)[:, None] * f[None, :])[None, :, None, :]

    def as_complex(v):
        return (v[..., 0::2] + 1j * v[..., 1::2]) * turn
    want = np.einsum("bthn,bshn->bhts", as_complex(q), np.conj(as_complex(k))).real
    got_q, got_k = F.rotary_position_embedding(
        paddle.to_tensor(q), paddle.to_tensor(k), theta=10000.0,
        rope_scaling=YARN, interleaved=True)
    assert got_q.shape == list(q.shape) and got_k.shape == list(k.shape)
    got = np.einsum("bthd,bshd->bhts", np.asarray(got_q._val), np.asarray(got_k._val))
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the half-split pairing of the same frequencies is another rotation
    half_q, half_k = F.rotary_position_embedding(
        paddle.to_tensor(q), paddle.to_tensor(k), theta=10000.0, rope_scaling=YARN)
    half = np.einsum("bthd,bshd->bhts", np.asarray(half_q._val), np.asarray(half_k._val))
    assert np.abs(half - want).max() > 0.1
    # the reference leaves the entries where they are: the complex numbers themselves
    cfg = {"qk_rope_head_dim": 16, "rope_theta": 10000, "rope_scaling": YARN}
    kept = np.asarray(ref.rotate(jnp.asarray(q), jnp.arange(24, dtype=jnp.float32), cfg, 1.0))
    np.testing.assert_allclose(kept[..., 0::2] + 1j * kept[..., 1::2], as_complex(q),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# the layers

def test_the_mixer_against_the_reference(cell, leaves, model):
    cfg = cell["cfg"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64))
    want = ref.mla(leaves, "l1.", x, cfg, jnp.matmul)
    want_dx = jax.grad(lambda v: jnp.sum(ref.mla(leaves, "l1.", v, cfg, jnp.matmul) * w))(x)
    t = paddle.to_tensor(np.asarray(x), stop_gradient=False)
    out = model[0].model.layers[1].self_attn(t)
    assert norm_gap(out._val, want) < TOL
    (out * paddle.to_tensor(np.asarray(w))).sum().backward()
    assert norm_gap(t.grad._val, want_dx) < GRAD_TOL


@pytest.mark.parametrize("absent", ["drop", "stand_in"])
def test_the_expert_layer_and_its_balance_loss(absent):
    cell = tiny(absent_experts=absent)
    cfg, p = cell["cfg"], seeded(cell)
    layer = build(cell, p)[0].model.layers[1].mlp
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64))
    want, want_loss = ref.expert_ff(p, "l1.", x, cfg, jnp.matmul)
    out, load, loss, picks = layer(paddle.to_tensor(np.asarray(x)))
    assert norm_gap(out._val, want) < TOL
    assert float(loss.item()) == pytest.approx(float(want_loss), rel=TOL)
    # un-renormalised: the picked weights of a token sum to less than 1
    s, idx, weights = ref.route(p, "l1.", x, cfg, jnp.matmul)
    assert float(jnp.max(jnp.sum(weights, axis=-1))) < 1.0
    assert float(jnp.sum(picks._val)) == 2 * 64 * 3
    np.testing.assert_array_equal(
        np.asarray(picks._val),
        np.bincount(np.asarray(idx).ravel(), minlength=16).astype(np.float32))
    # every pick is a row here under stand-ins; under drop, the held experts' own
    rows = 2 * 64 * 3 if absent == "stand_in" else int(np.isin(np.asarray(idx), [0, 1, 2, 3]).sum())
    assert float(jnp.sum(load._val)) == rows


def expert_layer(cfg, p, held, absent="drop", stand_ins=None):
    """A DroplessMoELayer of the tiny widths holding `held` of the published
    experts of layer 1's leaves `p` (whose e_w* hold all of them)."""
    from paddle_tpu.incubate.moe import DroplessMoELayer
    layer = DroplessMoELayer(
        64, 32, cfg["n_routed_experts_published"], cfg["num_experts_per_tok"],
        held_experts=held, shared_width=64, score="softmax", absent=absent,
        renormalize=False, balance_alpha=cfg["aux_loss_alpha"])
    rows = jnp.asarray(held if stand_ins is None else stand_ins)
    layer.set_state_dict({
        "gate.weight": paddle.Tensor(p["l1.gate_w"]),
        "expert_bias": paddle.Tensor(p["l1.expert_bias"]),
        **{f"w{n}": paddle.Tensor(p[f"l1.e_w{n}"][rows]) for n in (1, 2, 3)},
        **{f"shared.w{n}.weight": paddle.Tensor(p[f"l1.s_w{n}"]) for n in (1, 2, 3)}})
    return layer


def test_the_shares_tie_to_the_uncut_layer():
    """64 experts in 8 shares of 8, as the deployment has them. Under "drop"
    the routed parts that the 8 shares give, the shared experts (whole on
    every share) counted once, add up to the uncut reference layer, and the
    balance loss, over the 64 published experts, is the same on every share.
    Under "stand_in" a share equals the uncut layer built with the
    stand-ins' weights: expert e computed with the leaves of held slot
    e mod 8."""
    whole = tiny(n_routed_experts_published=64, n_routed_experts=64,
                 held_experts=list(range(64)), num_experts_per_tok=6)
    cfg, p = whole["cfg"], seeded(whole)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 64))
    want, want_loss = ref.expert_ff(p, "l1.", x, cfg, jnp.matmul)
    tx = paddle.to_tensor(np.asarray(x))
    total, losses = 0.0, []
    for share in range(8):
        layer = expert_layer(cfg, p, list(range(8 * share, 8 * share + 8)))
        out, _, loss, _ = layer(tx)
        shared = layer.shared(tx)._val
        total = total + out._val - shared
        losses.append(float(loss.item()))
    assert norm_gap(total + shared, want) < TOL
    assert norm_gap(total, ref.routed_part(
        p, "l1.", x, *ref.route(p, "l1.", x, cfg, jnp.matmul)[1:], cfg, jnp.matmul)) < TOL
    assert max(losses) - min(losses) < 1e-9 and losses[0] == pytest.approx(
        float(want_loss), rel=TOL)
    # stand-ins: share 0 (experts 0-7) against the uncut layer whose expert e
    # has the weights of expert e mod 8
    stood = dict(p, **{f"l1.e_w{n}": p[f"l1.e_w{n}"][jnp.arange(64) % 8] for n in (1, 2, 3)})
    want, want_loss = ref.expert_ff(stood, "l1.", x, cfg, jnp.matmul)
    out, load, loss, _ = expert_layer(cfg, p, list(range(8)), absent="stand_in")(tx)
    assert norm_gap(out._val, want) < TOL
    assert float(loss.item()) == pytest.approx(float(want_loss), rel=TOL)
    assert float(jnp.sum(load._val)) == 2 * 64 * 6
    # and the reference given the same share says the same
    cut = dict(cfg, held_experts=list(range(8)), absent_experts="stand_in")
    held = dict(p, **{f"l1.e_w{n}": p[f"l1.e_w{n}"][:8] for n in (1, 2, 3)})
    assert norm_gap(ref.expert_ff(held, "l1.", x, cut, jnp.matmul)[0], want) < TOL


# ---------------------------------------------------------------------------
# the whole model

def three_steps(cell, p, check):
    """Three AdamW steps of the program beside the reference's; `check(step,
    loss, want, grads or None, names, tensors)` after each backward."""
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
        # the change of each leaf over three steps, against the reference's:
        # 0.013 and 0.021 of it read in the worst leaf (Adam's first steps
        # move an entry by the learning rate whatever its gradient's size, so
        # an entry whose gradient is rounding moves by its sign)
        moved = float(jnp.linalg.norm(jnp.ravel(end[leaf] - start[leaf])))
        assert float(jnp.linalg.norm(jnp.ravel(tensors[key]._val - end[leaf]))) \
            < 5e-2 * moved, leaf


@pytest.mark.parametrize("absent", ["drop", "stand_in"])
def test_loss_gradients_and_three_adamw_steps_in_bfloat16(absent):
    """bf16 weights and activations with float32 masters against the float32
    reference from the same (bf16-rounded) weights, every matrix at the
    benchmark's one std. Bands, from readings at this size in both modes:
    each of the three steps' losses within 2e-3 of the reference's (6e-4 and
    7e-4 read; an entry of bf16 is 4e-3, and the later losses carry the two
    updates), a leaf's first gradient within 0.2 of its norm (0.084 and 0.106
    read, the widest an expert's or a router's matrix: near-ties pick another
    expert in bf16). The served bf16 weights cannot show an update of 1e-4 to
    a gain of 1, so the updates are held through the losses that follow them."""
    cell = tiny(absent_experts=absent, weights_dtype="bfloat16")
    worst = {"loss": 0.0, "grad": 0.0}

    def check(step, loss, want, grads, names, tensors):
        worst["loss"] = max(worst["loss"], abs(loss - want) / want)
        if step:
            return
        for leaf, key in names.items():
            if not leaf.endswith("expert_bias"):
                worst["grad"] = max(worst["grad"],
                                    norm_gap(tensors[key].grad._val, grads[leaf]))

    three_steps(cell, seeded(cell, "bfloat16", sharp=False), check)
    assert worst["loss"] < 2e-3 and worst["grad"] < 0.2, worst


FAULTS = ["plain_frequencies", "no_mscale", "half_split_pairs", "key_rotated_by_head",
          "renormalised", "balance_over_batch", "no_balance_loss"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_is_not_within_the_tolerances(cell, leaves, model, fault):
    """The reference with one fault put in, against the program: the loss or
    a leaf's gradient is out by at least 30 times what the sound reference is
    held to. The balance term is a thousandth of the loss, so its faults
    show in the loss's fifth digit and in the routers' gradients."""
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


# ---------------------------------------------------------------------------
# what a step stages

@pytest.fixture(scope="module")
def traced_step():
    """One training step over three rematerialised blocks (a dense one and
    two with experts), on a platform rule that says TPU so that attention
    takes the flash pair; the model and the registry's gauges after it."""
    from paddle_tpu.profiler import metrics
    with pytest.MonkeyPatch.context() as patch:
        rematerialised_step.flash_on_a_cpu(patch)
        cell = tiny(recompute=True, absent_experts="stand_in", qk_nope_head_dim=64,
                    qk_rope_head_dim=64, v_head_dim=64)
        cell["job"].update(batch=1, seq=128)
        model, _ = build(cell, seeded(cell))
        (x, y), = batch(cell)
        got = rematerialised_step.traced_step(
            model, cell["family"].loss_of, paddle.to_tensor(x), paddle.to_tensor(y))
        return dict(got, model=model, layers=cell["cfg"]["num_layers"],
                    gauges=metrics.get_registry().snapshot()["gauges"])


def test_a_rematerialised_step_stages_the_scopes_and_moves_the_counters(traced_step):
    """`flash_attention`, the attention core, on forward and backward
    instructions of a `to_static` step whose blocks are rematerialised and on
    none of a rerun: the core is outside the blocks' regions. `mla_rope`,
    `mla_kv`, the products, the norms, `moe_balance_loss` and the expert
    layer's scopes are inside them, on forward, rerun and backward
    instructions; the registry's two readings of the router move with the
    step."""
    moved, names = traced_step["moved"]["both"], traced_step["names"]
    assert moved["attention.flash_total"] > 0
    layers = [b.mlp for b in traced_step["model"].model.layers if not b.is_dense]
    calls = sum(float(m.calls_total._val) for m in layers)
    assert calls == 2 and sum(float(m.rows_total._val) for m in layers) == 2 * 128 * 3
    # the term summed over the layers and calls: about alpha each (f P sums
    # to 1 under a router in balance, more under one that is not)
    total = sum(float(m.balance_total._val) for m in layers)
    assert 0.001 * calls <= total < 0.004 * calls
    assert moved["moe.balance_loss_total"] == pytest.approx(total, rel=1e-5)
    # the most-picked of 16 published experts over the mean: 1 to 16 / 3
    assert 1.0 <= traced_step["gauges"]["moe.router_max_over_mean_ratio"] <= 16 / 3
    assert rematerialised_step.passes_of(names, "flash_attention") == {"forward", "backward"}
    assert "transpose(jvp(jvp(flash_attention)))" not in traced_step["text"]
    for scope in ("mla_rope", "mla_kv", "linear", "rms_norm", "moe_balance_loss",
                  "moe_route", "moe_experts"):
        # the forward, the rematerialised forward and the backward
        assert {"forward", "rerun"} <= rematerialised_step.passes_of(names, scope), scope
    for scope in ("mla_rope", "mla_kv", "linear", "rms_norm", "moe_experts"):
        assert "backward" in rematerialised_step.passes_of(names, scope), scope
    assert "checkpoint" not in traced_step["text"]  # a custom_vjp region keeps the names


@pytest.mark.parametrize("which", ["eager", "traced"])
def test_a_rematerialised_step_runs_its_attention_core_once(traced_step, which):
    """A layer a pass of the step's body (the eager discovery pass; each
    trace of the step program) one flash forward: none in a region's
    discovery, first run or rerun, which made it three a layer a pass while a
    block was one region."""
    runs = traced_step["passes"][which] * traced_step["layers"]
    assert runs > 0
    assert traced_step["moved"][which]["attention.flash_total"] == runs


@pytest.mark.parametrize("absent", ["drop", "stand_in"])
def test_a_rematerialised_model_is_the_plain_model(absent):
    """Two regions round the attention core and the core on the tape are the
    plain block's arithmetic: both parts of the loss and every leaf's
    gradient, under both ways of cutting the expert layer (a region runs as
    one program, so float32 sums come in another order: to 1.02e-6 of a
    leaf's norm read)."""
    cell = tiny(absent_experts=absent)
    leaves = seeded(cell)
    (x, y), = batch(cell)
    x, y = paddle.to_tensor(x), paddle.to_tensor(y)
    got = {}
    for recompute in (False, True):
        model, names = build(dict(cell, cfg=dict(cell["cfg"], recompute=recompute)),
                             leaves)
        loss, lm, balance = model(x, labels=y)
        got[recompute] = (float(lm.item()), float(balance.item()),
                          rematerialised_step.grads_by_leaf(model, names, loss))
    (lm, balance, grads), (lm_r, balance_r, grads_r) = got[False], got[True]
    assert abs(lm_r - lm) <= 1e-6 * lm and abs(balance_r - balance) <= 1e-6 * balance
    rematerialised_step.assert_the_same_gradients(grads, grads_r, tol=4e-6)


# ---------------------------------------------------------------------------
# the edited layers at their old arguments

def old_rotary(qv, kv, theta, position_offset=0):
    """`rotary_position_embedding`'s arithmetic before this model (PR 43)."""
    d, s = qv.shape[-1], qv.shape[1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    t = jnp.arange(position_offset, position_offset + s, dtype=jnp.float32)
    angle = jnp.concatenate([t[:, None] * inv[None, :]] * 2, axis=-1)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]

    def turn(v):
        f = v.astype(jnp.float32)
        half = jnp.concatenate([-f[..., d // 2:], f[..., :d // 2]], axis=-1)
        return (f * cos + half * sin).astype(v.dtype)
    return turn(qv), turn(kv)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rotary_positions_at_their_old_arguments_bit_for_bit(dtype):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, 40, 4, 32)), dtype)
    k = jnp.asarray(rng.normal(size=(2, 40, 2, 32)), dtype)
    for offset in (0, 5):
        got = F.rotary_position_embedding(paddle.Tensor(q), paddle.Tensor(k),
                                          theta=1e6, position_offset=offset)
        want = old_rotary(q, k, 1e6, offset)
        for g, w in zip(got, want):
            assert g._val.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g._val.astype(jnp.float32)),
                                          np.asarray(w.astype(jnp.float32)))
    # position ids and sections still go the old way (the Keye model's call)
    ids = jnp.broadcast_to(jnp.arange(40)[None, None, :], (3, 2, 40))
    got = F.rotary_position_embedding(paddle.Tensor(q), paddle.Tensor(k), theta=1e6,
                                      position_ids=paddle.Tensor(ids), sections=(4, 6, 6))
    for g, w in zip(got, old_rotary(q, k, 1e6)):
        np.testing.assert_array_equal(np.asarray(g._val.astype(jnp.float32)),
                                      np.asarray(w.astype(jnp.float32)))


def test_latent_attention_without_rotation_bit_for_bit():
    """`MultiHeadLatentAttention` at its old arguments (the Kimi Linear
    model's call) against its forward as it was before this model."""
    from paddle_tpu.core.dispatch import apply
    from paddle_tpu.tensor import manipulation as M
    paddle.seed(3)
    layer = paddle.nn.MultiHeadLatentAttention(64, 4, 32, 16, 8, 16, 1e-5)
    assert layer.rope is None and layer.scale is None
    x = paddle.to_tensor(np.random.default_rng(2).normal(size=(2, 48, 64)).astype(np.float32))
    b, s, heads, nope, dv, rank = 2, 48, 4, 16, 16, 32
    q = M.reshape(layer.q_proj(x), [b, s, heads, nope + 8])
    latent, shared = apply(lambda c: (c[..., :rank], c[..., rank:]), layer.kv_a_proj(x))
    kv = M.reshape(layer.kv_b_proj(layer.kv_a_norm(latent)), [b, s, heads, nope + dv])

    def keys_values(kv_, shared_):
        pe = jnp.broadcast_to(shared_[:, :, None, :], kv_.shape[:3] + shared_.shape[-1:])
        return jnp.concatenate([kv_[..., :nope], pe], axis=-1), kv_[..., nope:]
    k, v = apply(keys_values, kv, shared)
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True, training=True)
    want = layer.o_proj(M.reshape(out, [b, s, heads * dv]))
    np.testing.assert_array_equal(np.asarray(layer(x)._val), np.asarray(want._val))
    assert "mla_rope" not in jax.make_jaxpr(lambda v: layer(paddle.Tensor(v))._val)(
        x._val).pretty_print(name_stack=True)


def test_no_balance_coefficient_is_a_balance_loss_of_zero():
    """`aux_loss_alpha=None` is 0.0 in the configuration: the blocks unpack
    the expert layer's four values, so the model has to build it with a
    number."""
    from paddle_tpu.text.models import DeepseekV2Config, DeepseekV2ForCausalLM
    cfg = DeepseekV2Config(
        vocab_size=50, hidden_size=32, num_layers=2, num_attention_heads=2,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        intermediate_size=48, moe_intermediate_size=16, n_routed_experts=4,
        num_experts_per_tok=2, n_shared_experts=1, aux_loss_alpha=None)
    assert cfg.aux_loss_alpha == 0.0
    paddle.seed(5)
    ids = paddle.to_tensor(np.random.default_rng(5).integers(0, 50, (2, 12)))
    loss, lm_loss, balance = DeepseekV2ForCausalLM(cfg)(ids, labels=ids)
    assert float(balance._val) == 0.0 and float(loss._val) == float(lm_loss._val)


def test_the_expert_layer_at_its_old_arguments():
    """Without `balance_alpha` the layer returns (out, load), keeps no
    counter of the balance loss, and renormalises as before."""
    from paddle_tpu.incubate.moe import DroplessMoELayer
    paddle.seed(4)
    layer = DroplessMoELayer(64, 32, 16, 3, held_experts=[0, 1, 2, 3], score="softmax")
    assert layer.renormalize and layer.balance_alpha is None
    assert not hasattr(layer, "balance_total")
    x = paddle.to_tensor(np.random.default_rng(3).normal(size=(2, 16, 64)).astype(np.float32))
    out, load = layer(x)
    layer.record_load(load)
    assert float(layer.calls_total._val) == 1.0
    same = DroplessMoELayer(64, 32, 16, 3, held_experts=[0, 1, 2, 3], score="softmax",
                            renormalize=False)
    same.set_state_dict(layer.state_dict())
    # renormalised weights sum to 1 a token, the softmax's own to less: the
    # two layers differ by a factor a token
    assert norm_gap(same(x)[0]._val, out._val) > 0.1
