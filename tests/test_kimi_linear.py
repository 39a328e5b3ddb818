"""The Kimi Linear model (paddle_tpu/text/models/kimi_linear.py) against the
plain reference (benchmarks/reference/kimi_linear.py) on seeded weights, at a
small size in float32 on the CPU: each mixer, the expert layer with its
shared expert and its shares, the whole model's loss and gradients leaf by
leaf, three AdamW steps, and the scopes a rematerialised step stages.

Tolerances. In float32 the program does the reference's arithmetic in
another order (chunks against tokens, a sorted buffer against a dense sum):
1e-4 of a leaf's norm holds every reading (2e-6 to 3e-5 measured), and bf16
operands (4e-3 an entry) fail it by an order of magnitude."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import paddle_tpu as paddle  # noqa: E402
import rematerialised_step  # noqa: E402
from benchmarks import harness  # noqa: E402
from benchmarks.reference import adamw  # noqa: E402

CELL = "kimi-linear-48b-a3b.pretrain-1chip-b2-s4096"
# 11 until PR 40: that draw holds a router near-tie (token 56 of the first
# layer: its eighth and ninth experts closer in score plus bias than the
# 2.4e-6 by which float32 rounding in the mixer before it moves a score), so
# any change in the order of a sum upstream picks another expert for it and
# moves the loss by 3e-5; the op's running sums as a product did, while
# agreeing with the token recurrence as closely as before (5e-7 against 9e-7
# of the largest output). The parent passes at 12 as well.
SEED = 12
TOL = 1e-4


def tiny(**over):
    cell = harness.load_cell(CELL)
    cfg = cell["cfg"]
    cfg.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               intermediate_size=96, moe_intermediate_size=32, vocab_size=600,
               num_experts=4, held_experts=[0, 1, 2, 3], gate_rank=8,
               weights_dtype="float32", recompute=False)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"], num_heads=4,
                                     head_dim=16)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg.update(over)
    cell["job"].update(batch=2, seq=128)
    return cell


def seeded(cell, scale=8.0):
    """Seeded float32 leaves; the matrices 8 times the benchmark's 0.02 so
    that at hidden 64 the mixers' projections are of unit size as they are at
    hidden 2304, and `a_log`, `dt_bias` and the expert bias off zero."""
    cfg = cell["cfg"]
    p = harness.init_params(cell["family"].reference.param_shapes(cfg), SEED, "float32")
    rng = np.random.default_rng(SEED)
    out = {}
    for k, v in p.items():
        if v.ndim >= 2:
            v = scale * v
        elif k.endswith(("a_log", "dt_bias")):
            v = jnp.asarray(rng.normal(0, 0.5, v.shape), jnp.float32)
        elif k.endswith("expert_bias"):
            v = jnp.asarray(rng.normal(0, 0.05, v.shape), jnp.float32)
        out[k] = v
    return out


def build(cell, p):
    family, cfg = cell["family"], cell["cfg"]
    model = family.build_model(cfg)
    names = family.program_names(cfg)
    missing, unexpected = model.set_state_dict(
        {names[k]: paddle.Tensor(v) for k, v in p.items()})
    assert not missing and not unexpected
    return model, names


def norm_gap(a, b):
    return float(jnp.linalg.norm(jnp.ravel(a - b)) / max(float(jnp.linalg.norm(jnp.ravel(b))), 1e-12))


@pytest.fixture(scope="module")
def cell():
    return tiny()


@pytest.fixture(scope="module")
def leaves(cell):
    return seeded(cell)


@pytest.fixture(scope="module")
def model(cell, leaves):
    return build(cell, leaves)


@pytest.mark.parametrize("layer, kind", [(0, "kda"), (3, "mla")])
def test_each_mixer_against_the_reference(cell, leaves, model, layer, kind):
    ref, cfg = cell["family"].reference, cell["cfg"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 64))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 64))
    mixer = getattr(ref, kind)
    want = mixer(leaves, f"l{layer}.", x, cfg, jnp.matmul)
    want_dx = jax.grad(lambda v: jnp.sum(mixer(leaves, f"l{layer}.", v, cfg, jnp.matmul) * w))(x)
    t = paddle.to_tensor(np.asarray(x), stop_gradient=False)
    out = model[0].model.layers[layer].self_attn(t)
    assert norm_gap(out._val, want) < TOL
    (out * paddle.to_tensor(np.asarray(w))).sum().backward()
    assert norm_gap(t.grad._val, want_dx) < TOL


def test_the_expert_layer_with_its_shared_expert(cell, leaves, model):
    ref, cfg = cell["family"].reference, cell["cfg"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 64))
    want = ref.expert_ff(leaves, "l1.", x, cfg, jnp.matmul)
    out, load = model[0].model.layers[1].mlp(paddle.to_tensor(np.asarray(x)))
    assert norm_gap(out._val, want) < TOL
    assert float(jnp.sum(load._val)) > 0


def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    """16 experts in 4 shares of 4: the routed parts of the four shares, with
    the shared expert (whole on every share) counted once, are the uncut
    reference layer."""
    from paddle_tpu.incubate.moe import DroplessMoELayer
    whole = tiny(num_experts=16, held_experts=list(range(16)))
    ref, cfg = whole["family"].reference, whole["cfg"]
    p = seeded(whole)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 64))
    want = ref.expert_ff(p, "l0.", x, cfg, jnp.matmul)
    tx = paddle.to_tensor(np.asarray(x))
    shared, total = None, 0.0
    for share in range(4):
        held = list(range(4 * share, 4 * share + 4))
        layer = DroplessMoELayer(64, 32, 16, cfg["num_experts_per_token"],
                                 held_experts=held, shared_width=32,
                                 routed_scaling_factor=cfg["routed_scaling_factor"])
        layer.set_state_dict({
            "gate.weight": paddle.Tensor(p["l0.gate_w"]),
            "expert_bias": paddle.Tensor(p["l0.expert_bias"]),
            **{f"w{n}": paddle.Tensor(p[f"l0.e_w{n}"][jnp.asarray(held)]) for n in (1, 2, 3)},
            **{f"shared.w{n}.weight": paddle.Tensor(p[f"l0.s_w{n}"]) for n in (1, 2, 3)}})
        shared = layer.shared(tx)._val
        total = total + layer(tx)[0]._val - shared
    assert norm_gap(total + shared, want) < TOL
    # and a layer without one is the routed part alone
    assert norm_gap(total, ref.routed_part(p, "l0.", x, cfg, jnp.matmul)) < TOL


def test_loss_gradients_and_three_adamw_steps(cell, leaves):
    family, cfg = cell["family"], cell["cfg"]
    ref = family.reference
    model, names = build(cell, leaves)
    stream = family.Stream(cfg, cell["job"], SEED)
    batches = [stream.next() for _ in range(3)]
    o = cfg["optimizer"]
    opt = paddle.optimizer.AdamW(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=model.parameters())
    p, state = dict(leaves), adamw.init(leaves)
    tensors = model.state_dict()
    for step, (x, y) in enumerate(batches):
        want, grads = jax.value_and_grad(
            lambda q: ref.loss_fn(q, jnp.asarray(x), jnp.asarray(y), cfg))(p)
        loss = family.loss_of(model, paddle.to_tensor(x), paddle.to_tensor(y))
        assert abs(float(loss.item()) - float(want)) < 2e-5 * float(want)
        loss.backward()
        if step == 0:
            for leaf, key in names.items():
                if leaf.endswith("expert_bias"):
                    assert tensors[key].grad is None       # no gradient, by design
                    continue
                assert norm_gap(tensors[key].grad._val, grads[leaf]) < 3 * TOL, leaf
        opt.step()
        opt.clear_grad()
        p, state = adamw.update(
            p, grads, state, lr=o["learning_rate"], beta1=o["beta1"],
            beta2=o["beta2"], eps=o["epsilon"], weight_decay=o["weight_decay"])
    for leaf, key in names.items():
        moved = float(jnp.linalg.norm(jnp.ravel(p[leaf] - leaves[leaf])))
        if leaf.endswith("expert_bias"):
            assert norm_gap(tensors[key]._val, leaves[leaf]) == 0.0
            continue
        # the change of each leaf over three steps, against the reference's
        assert float(jnp.linalg.norm(jnp.ravel(tensors[key]._val - p[leaf]))) \
            < 2e-2 * moved, leaf


def test_a_rematerialised_model_is_the_plain_model(cell, leaves):
    """Two regions round each mixer's core and the core on the tape are the
    plain block's arithmetic: the loss and every leaf's gradient, over three
    KDA layers and a latent one, dense and expert feed-forwards. A region
    runs as one program and the plain block op by op, so float32 sums come
    in another order: the decay's leaves (`a_log`, `dt_bias`: sums over every
    token of exp and softplus terms) read 5e-6 to 2.4e-5 of their norm, as
    they did while the whole block was one region (5e-6 to 1.1e-5), every
    other leaf under 4e-6."""
    family = cell["family"]
    x, y = (paddle.to_tensor(a) for a in
            family.Stream(cell["cfg"], cell["job"], SEED).next())
    got = {}
    for recompute in (False, True):
        model, names = build(dict(cell, cfg=dict(cell["cfg"], recompute=recompute)),
                             leaves)
        assert model.training
        loss = family.loss_of(model, x, y)
        got[recompute] = float(loss.item()), rematerialised_step.grads_by_leaf(
            model, names, loss)
    (loss, grads), (loss_r, grads_r) = got[False], got[True]
    assert abs(loss_r - loss) <= 1e-6 * loss
    rematerialised_step.assert_the_same_gradients(grads, grads_r, tol=5e-5)


@pytest.fixture(scope="module")
def traced_step():
    """One training step over a rematerialised KDA block and a rematerialised
    latent block, on a platform rule that says TPU: the latent layer takes
    the flash pair and the mixer's q, k and v streams the short convolution's
    pass (heads of a whole lane chunk, as the published model has them)."""
    with pytest.MonkeyPatch.context() as patch:
        rematerialised_step.flash_on_a_cpu(patch)
        cell = tiny(recompute=True, num_layers=2, first_layer=6,
                    qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=64)
        cell["cfg"]["linear_attn_config"].update(num_heads=2, head_dim=128)
        family, cfg = cell["family"], cell["cfg"]
        model, _ = build(cell, seeded(cell))
        assert [b.is_kda for b in model.model.layers] == [True, False]
        x, y = (paddle.to_tensor(a) for a in family.Stream(cfg, cell["job"], SEED).next())
        return rematerialised_step.traced_step(model, family.loss_of, x, y)


def test_a_rematerialised_step_stages_the_scopes(traced_step):
    """The mixers' cores, `kda` and `flash_attention`, on forward and backward
    instructions of a step whose blocks are rematerialised, and on none of a
    rerun: the cores are outside the blocks' regions. What the regions hold
    is staged in all three passes: the products, the norms, `mla_kv`, the
    decay and the expert layer. The mixer's q, k and v streams are the fused
    op (scope `short_conv`) forward and backward; the rerun of the first
    region drops their forward, since nothing but the core read its result
    and the stream's backward recomputes from the region's input."""
    from benchmarks import program_trace
    names, moved = traced_step["names"], traced_step["moved"]["both"]
    assert moved["attention.flash_total"] > 0 and moved["kda.calls_total"] > 0
    assert moved["short_conv.kernel_total"] > 0
    assert moved.get("short_conv.xla_total", 0.0) == 0
    for scope in ("kda", "flash_attention"):
        assert rematerialised_step.passes_of(names, scope) == {"forward", "backward"}, scope
    assert "transpose(jvp(jvp(kda)))" not in traced_step["text"]
    assert "transpose(jvp(jvp(flash_attention)))" not in traced_step["text"]
    for scope in ("linear", "rms_norm", "moe_experts", "mla_kv", "kda_gate"):
        assert rematerialised_step.passes_of(names, scope) == {
            "forward", "rerun", "backward"}, scope
    # the streams are the fused op: its forward once, its backward once, and
    # no l2_norm left beside them
    conv = [n for n in names if program_trace.scope_of(n + "/op") == "short_conv"]
    assert any(n.startswith("jit(pure_fn)/jvp(short_conv)/jit(stream_forward)")
               for n in conv)
    assert not [n for n in conv if "transpose(" in n and "jit(stream_forward)" in n]
    assert any("jit(stream_backward)" in n and "transpose(jvp(transpose(" in n
               for n in conv)
    assert not [n for n in names if program_trace.scope_of(n + "/op") == "l2_norm"]
    assert "checkpoint" not in traced_step["text"]  # a custom_vjp region keeps the names


@pytest.mark.parametrize("which", ["eager", "traced"])
def test_a_rematerialised_step_runs_its_mixer_cores_once(traced_step, which):
    """A layer a pass of the step's body (the eager discovery pass; each
    trace of the step program) one call of the KDA op in the KDA layer and
    one flash forward in the latent layer: none in a region's discovery,
    first run or rerun, which made it three a pass while a block was one
    region."""
    passes = traced_step["passes"][which]
    assert passes > 0
    moved = traced_step["moved"][which]
    assert moved["kda.calls_total"] == passes
    assert moved["kda.tokens_total"] == passes * 2 * 128
    assert moved["attention.flash_total"] == passes
    # the streams stay inside the first region: q, k and v in its discovery,
    # its first run and its rerun
    assert moved["short_conv.kernel_total"] == passes * 3 * 3
