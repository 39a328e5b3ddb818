"""The Keye-VL-2.0 language model (paddle_tpu/text/models/keye_vl2.py) against
the plain reference (benchmarks/reference/keye_vl2.py) on seeded weights, at a
small size in float32 on the CPU: both parts of the loss, every leaf's
gradient, which loss feeds which leaf, three AdamW steps, unequal position
streams, the expert layer's shares, the scopes and counters a rematerialised
step stages, and that a program which attends to every causal key, or drops
the index loss, is told from the model.

Tolerances. In float32 the program does the reference's arithmetic in
another order (chunks of queries against blocks of rows, a sorted buffer
against a dense sum): 1e-4 of a leaf's norm holds every reading (6e-7 to
2e-5 measured), and bf16 operands (4e-3 an entry) fail it by an order of
magnitude."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import paddle_tpu as paddle  # noqa: E402
from benchmarks import harness  # noqa: E402
from benchmarks.reference import adamw  # noqa: E402

CELL = "keye-vl2-30b-a3b.pretrain-1chip-b1-s8192"
SEED = 11
TOL = 1e-4
BATCH, SEQ = 2, 128


def tiny(**over):
    """16 published experts of which 4 are held, two layers, a set of 32 keys
    in rows of 128: three queries in four choose, as in the cell."""
    cell = harness.load_cell(CELL)
    cfg = cell["cfg"]
    cfg.update(hidden_size=64, head_dim=16, num_attention_heads=4,
               num_key_value_heads=2, moe_intermediate_size=32,
               num_experts_published=16, num_experts=4, held_experts=[0, 1, 2, 3],
               num_experts_per_tok=2, vocab_size=600, num_layers=2,
               weights_dtype="float32", recompute=False)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], mrope_section=[2, 3, 3])
    cfg["sa_config"] = dict(cfg["sa_config"], indexer_head_dim=8,
                            indexer_num_heads=2, topk=32)
    cfg.update(over)
    cell["job"].update(batch=BATCH, seq=SEQ)
    return cell


def seeded(cell, scale=8.0):
    """Seeded float32 leaves; the matrices 8 times the benchmark's 0.02 so
    that at hidden 64 the projections are of unit size as they are at hidden
    2048, and the LayerNorm's bias off zero."""
    p = harness.init_params(cell["family"].reference.param_shapes(cell["cfg"]),
                            SEED, "float32")
    rng = np.random.default_rng(SEED)
    return {k: scale * v if v.ndim >= 2 else
            jnp.asarray(rng.normal(0, 0.1, v.shape), jnp.float32)
            if k.endswith("index_k_norm_b") else v for k, v in p.items()}


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


def batch(cell, n=1):
    stream = cell["family"].Stream(cell["cfg"], cell["job"], SEED)
    out = [stream.next() for _ in range(n)]
    return out[0] if n == 1 else out


def image_positions():
    """An image's patch grid in the middle of the row: the temporal stream
    stands still over it while height and width walk the grid."""
    pos = np.broadcast_to(np.arange(SEQ)[None, None], (3, BATCH, SEQ)).copy()
    pos[0, :, 32:96] = 32
    pos[1, :, 32:96] = 32 + np.arange(64) // 8
    pos[2, :, 32:96] = 32 + np.arange(64) % 8
    pos[:, :, 96:] -= 64 - 8
    return pos


@pytest.fixture(scope="module")
def cell():
    c = tiny()
    c["family"].reference.QUERY_ROWS = 64       # two blocks of rows in a row of 128
    return c


@pytest.fixture(scope="module")
def leaves(cell):
    return seeded(cell)


@pytest.fixture(scope="module")
def reference_grads(cell, leaves):
    """(lm, index, d lm, d index): the reference's two losses and the
    gradient of each apart."""
    ref, cfg = cell["family"].reference, cell["cfg"]
    x, y = (jnp.asarray(a) for a in batch(cell))
    part = lambda i: jax.value_and_grad(  # noqa: E731
        lambda p: ref.loss_parts(p, x, y, cfg)[i])(leaves)
    (lm, d_lm), (index, d_index) = part(0), part(1)
    return float(lm), float(index), d_lm, d_index


@pytest.mark.parametrize("recompute", [False, True], ids=["plain", "rematerialised"])
def test_both_losses_and_every_leafs_gradient(cell, leaves, reference_grads, recompute):
    lm, index, d_lm, d_index = reference_grads
    over = dict(cell, cfg=dict(cell["cfg"], recompute=recompute))
    model, names = build(over, leaves)
    x, y = (paddle.to_tensor(a) for a in batch(cell))
    loss, got_lm, got_index = model(x, labels=y)
    assert abs(float(got_lm.item()) - lm) < 2e-5 * lm
    assert abs(float(got_index.item()) - index) < 2e-5 * index
    assert index > 0.01                           # the index disagrees with the heads
    assert abs(float(loss.item()) - (lm + index)) < 2e-5 * lm
    loss.backward()
    tensors = model.state_dict()
    for leaf, key in names.items():
        if leaf.endswith("expert_bias"):
            assert tensors[key].grad is None      # no gradient, by design
            continue
        want = d_index[leaf] if ".index_" in leaf else d_lm[leaf]
        assert norm_gap(tensors[key].grad._val, want) < TOL, leaf


def test_which_loss_feeds_which_leaf(cell, leaves, reference_grads):
    """The indexer's leaves take nothing from the language-model loss and no
    other leaf anything from the index loss: in the reference, and in the
    program when each part is differentiated alone."""
    _, _, d_lm, d_index = reference_grads
    for leaf in d_lm:
        mine, other = (d_index, d_lm) if ".index_" in leaf else (d_lm, d_index)
        assert float(jnp.max(jnp.abs(other[leaf]))) == 0.0, leaf
        assert leaf.endswith("expert_bias") or float(jnp.max(jnp.abs(mine[leaf]))) > 0.0, leaf
    for part in (1, 2):                           # the model returns (sum, lm, index)
        model, names = build(cell, leaves)
        x, y = (paddle.to_tensor(a) for a in batch(cell))
        model(x, labels=y)[part].backward()
        tensors = model.state_dict()
        for leaf, key in names.items():
            grad = tensors[key].grad
            zero = grad is None or float(jnp.max(jnp.abs(grad._val))) == 0.0
            feeds = (".index_" in leaf) == (part == 2) and not leaf.endswith("expert_bias")
            assert zero != feeds, (leaf, part)


def test_three_adamw_steps(cell, leaves):
    family, cfg = cell["family"], cell["cfg"]
    ref = family.reference
    model, names = build(cell, leaves)
    # the benchmark's other cells' rate: at the configuration's 7.3e-6 three
    # steps move a float32 leaf by less than this comparison resolves
    o = dict(cfg["optimizer"], learning_rate=1e-4)
    opt = paddle.optimizer.AdamW(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=model.parameters())
    p, state = dict(leaves), adamw.init(leaves)
    tensors = model.state_dict()
    for x, y in batch(cell, 3):
        want, grads = jax.value_and_grad(
            lambda q: ref.loss_fn(q, jnp.asarray(x), jnp.asarray(y), cfg))(p)
        loss = family.loss_of(model, paddle.to_tensor(x), paddle.to_tensor(y))
        assert abs(float(loss.item()) - float(want)) < 2e-5 * float(want)
        loss.backward()
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


def test_unequal_position_streams(cell, leaves):
    ref, cfg = cell["family"].reference, cell["cfg"]
    x, y = batch(cell)
    pos = image_positions()
    want = ref.loss_parts(leaves, jnp.asarray(x), jnp.asarray(y), cfg,
                          positions=jnp.asarray(pos))
    text = ref.loss_parts(leaves, jnp.asarray(x), jnp.asarray(y), cfg)
    assert abs(float(want[1]) - float(text[1])) > 1e-4   # the streams matter
    model, _ = build(cell, leaves)
    _, lm, index = model(paddle.to_tensor(x), labels=paddle.to_tensor(y),
                         position_ids=paddle.to_tensor(pos.astype(np.int32)))
    assert abs(float(lm.item()) - float(want[0])) < 2e-5 * float(want[0])
    assert abs(float(index.item()) - float(want[1])) < 2e-5 * float(want[1])
    # under rematerialisation the positions ride in the first region's closure
    over = dict(cell, cfg=dict(cell["cfg"], recompute=True))
    model, _ = build(over, leaves)
    loss, _, index = model(paddle.to_tensor(x), labels=paddle.to_tensor(y),
                           position_ids=paddle.to_tensor(pos.astype(np.int32)))
    assert abs(float(index.item()) - float(want[1])) < 2e-5 * float(want[1])
    loss.backward()


@pytest.mark.parametrize("positions", [None, "streams"], ids=["text", "streams"])
def test_a_rematerialised_model_is_the_plain_model(cell, leaves, positions):
    """Two regions round the core and the core on the tape are the plain
    block's arithmetic: both losses and every leaf's gradient, with the
    positions given (they ride in the first region's closure) and absent."""
    x, y = (paddle.to_tensor(a) for a in batch(cell))
    pos = None if positions is None else paddle.to_tensor(image_positions().astype(np.int32))
    got = {}
    for recompute in (False, True):
        over = dict(cell, cfg=dict(cell["cfg"], recompute=recompute))
        model, names = build(over, leaves)
        loss, lm, index = model(x, labels=y, position_ids=pos)
        loss.backward()
        tensors = model.state_dict()
        got[recompute] = (float(lm.item()), float(index.item()),
                          {leaf: tensors[key].grad for leaf, key in names.items()})
    (lm, index, grads), (lm_r, index_r, grads_r) = got[False], got[True]
    assert abs(lm_r - lm) <= 1e-6 * lm and abs(index_r - index) <= 1e-6 * index
    for leaf, grad in grads.items():
        if grad is None:
            assert leaf.endswith("expert_bias") and grads_r[leaf] is None
            continue
        assert float(jnp.linalg.norm(grad._val)) > 0.0, leaf
        assert norm_gap(grads_r[leaf]._val, grad._val) <= 1e-6, leaf


def test_the_shares_over_all_eight_sets_add_up_to_the_uncut_layer():
    """16 experts in 8 shares of 2, softmax-routed: the parts of the result
    that the eight shares give add up to the uncut reference layer."""
    from paddle_tpu.incubate.moe import DroplessMoELayer
    whole = tiny(num_experts=16, held_experts=list(range(16)), absent_experts="drop")
    ref, cfg = whole["family"].reference, whole["cfg"]
    p = seeded(whole)
    x = jax.random.normal(jax.random.PRNGKey(4), (BATCH, SEQ, 64))
    want = ref.expert_ff(p, "l0.", x, cfg, jnp.matmul)
    total = 0.0
    for share in range(8):
        held = [2 * share, 2 * share + 1]
        layer = DroplessMoELayer(64, 32, 16, cfg["num_experts_per_tok"],
                                 held_experts=held, score="softmax")
        layer.set_state_dict({
            "gate.weight": paddle.Tensor(p["l0.gate_w"]),
            "expert_bias": paddle.Tensor(p["l0.expert_bias"]),
            **{f"w{n}": paddle.Tensor(p[f"l0.e_w{n}"][jnp.asarray(held)]) for n in (1, 2, 3)}})
        out, _ = layer(paddle.to_tensor(np.asarray(x)))
        total = total + out._val
        # and the reference, given the same share, computes the same part
        part = ref.expert_ff({**p, **{f"l0.e_w{n}": p[f"l0.e_w{n}"][jnp.asarray(held)]
                                      for n in (1, 2, 3)}}, "l0.", x, cfg, jnp.matmul, held)
        assert norm_gap(out._val, part) < TOL
    assert norm_gap(total, want) < TOL


@pytest.mark.parametrize("held", [[0, 1, 2, 3], [8, 9, 10, 11], [5, 2]],
                         ids=["rank0", "rank2", "scattered"])
def test_held_experts_stand_in_for_the_absent_ones(held):
    """`absent="stand_in"`: an expert that is not held is computed by the
    held slot (its id mod the number held), so every pick of every token is
    a row here whatever the router picks, and layer and reference give the
    uncut layer whose 16 experts carry the stand-ins' weights."""
    from paddle_tpu.incubate.moe import DroplessMoELayer
    share = tiny(held_experts=held, absent_experts="stand_in")
    ref, cfg = share["family"].reference, share["cfg"]
    p = seeded(tiny(num_experts=len(held), held_experts=held))
    x = jax.random.normal(jax.random.PRNGKey(5), (BATCH, SEQ, 64))
    layer = DroplessMoELayer(64, 32, 16, cfg["num_experts_per_tok"],
                             held_experts=held, score="softmax", absent="stand_in")
    layer.set_state_dict({
        "gate.weight": paddle.Tensor(p["l0.gate_w"]),
        "expert_bias": paddle.Tensor(p["l0.expert_bias"]),
        **{f"w{n}": paddle.Tensor(p[f"l0.e_w{n}"]) for n in (1, 2, 3)}})
    out, load = layer(paddle.to_tensor(np.asarray(x)))
    assert float(load._val.sum()) == BATCH * SEQ * cfg["num_experts_per_tok"]
    assert norm_gap(out._val, ref.expert_ff(p, "l0.", x, cfg, jnp.matmul)) < TOL
    slots = np.asarray(ref.expert_slots(cfg))
    assert all(slots[e] == i for i, e in enumerate(held)) and slots.min() == 0
    uncut = dict(cfg, held_experts=list(range(16)), absent_experts="drop")
    tied = {**p, **{f"l0.e_w{n}": p[f"l0.e_w{n}"][slots] for n in (1, 2, 3)}}
    assert norm_gap(out._val, ref.expert_ff(tied, "l0.", x, uncut, jnp.matmul)) < TOL
    out.sum().backward()
    assert all(float(jnp.abs(getattr(layer, w).grad._val).sum()) > 0 for w in ("w1", "w2", "w3"))
    with pytest.raises(Exception, match="neither"):
        DroplessMoELayer(64, 32, 16, 2, held_experts=held, absent="fold")


@pytest.mark.parametrize("fault", ["all_causal_keys", "no_index_loss"])
def test_a_faulty_program_is_told_from_the_model(cell, leaves, reference_grads, fault):
    """What the cell's limits have to catch: a program that attends to every
    causal key moves the value and output projections' gradients by tens of
    per cent; one that drops the index loss loses it from the loss and
    leaves the indexer without a gradient."""
    ref, cfg = cell["family"].reference, cell["cfg"]
    lm, index, d_lm, d_index = reference_grads
    x, y = (jnp.asarray(a) for a in batch(cell))
    if fault == "all_causal_keys":
        bad_lm, bad = jax.value_and_grad(
            lambda p: ref.loss_parts(p, x, y, cfg, all_causal_keys=True)[0])(leaves)
        gaps = {k: abs(float(jnp.linalg.norm(bad[k])) - float(jnp.linalg.norm(d_lm[k])))
                / float(jnp.linalg.norm(d_lm[k])) for k in d_lm if k.endswith(("v_w", "o_w"))}
        assert max(gaps.values()) > 0.05, gaps
    else:
        assert index / (lm + index) > 1e-3        # first_loss_gap sees it go
        assert all(float(jnp.linalg.norm(d_lm[k])) == 0.0 for k in d_lm if ".index_" in k)


@pytest.fixture(scope="module")
def traced_step():
    """One training step over rematerialised blocks, run once eagerly (the
    discovery pass) and traced once, on a platform rule that says TPU so that
    attention over the sets takes the flash pair and the index its kernels
    (interpreted on a CPU). What each pass called of the sparse-attention
    core, what the counters moved by, and the scopes of the lowered text."""
    import re
    from paddle_tpu.jit.to_static import _flatten_tensors
    from paddle_tpu.ops import attention
    from paddle_tpu.ops.pallas import flash_attention
    from paddle_tpu.ops.pallas import sparse_index as kernels
    from paddle_tpu.profiler import metrics
    calls, bodies = [], []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention, "_platform", lambda: "tpu")
        patch.setattr(attention, "FLASH_MIN_SEQ_K", 128)
        patch.setattr(attention, "FLASH_MIN_SEQ_Q", 128)
        for module, name in ((kernels, "index_sets"), (kernels, "index_loss_walk"),
                             (flash_attention, "flash_attention_set_fwd")):
            patch.setattr(module, name, lambda *a, _f=getattr(module, name), _n=name, **kw:
                          calls.append((_n, kw.get("with_grads"))) or _f(*a, **kw))
        cell = tiny(recompute=True, head_dim=64)
        cell["cfg"]["rope_scaling"]["mrope_section"] = [8, 12, 12]
        cell["cfg"]["sa_config"]["indexer_head_dim"] = 64     # the index's kernels too
        family = cell["family"]
        model, _ = build(cell, seeded(cell))
        opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())

        @paddle.jit.to_static
        def step(x, y):
            bodies.append(len(calls))                 # one run of the body a pass
            loss = family.loss_of(model, x, y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        def counters():
            return dict(metrics.get_registry().snapshot()["counters"])
        x, y = (paddle.to_tensor(a) for a in batch(cell))
        before = counters()
        step(x, y)                                    # the eager discovery pass
        eager, discovered = list(calls), counters()
        (prog,) = step.programs.values()
        step._build(prog, (x, y), {})                 # traces; compiles nothing
        built, after = list(calls), counters()
        text = prog.jitted_donate.lower(
            tuple(t._val for t in prog.mutated), tuple(t._val for t in prog.ro),
            tuple(t._val for t in _flatten_tensors(((x, y), {}), []))
        ).as_text(debug_info=True)

    def moved(then, now):
        return {k: now[k] - then.get(k, 0.0) for k in now}
    return {"layers": cell["cfg"]["num_layers"], "text": text,
            "names": set(re.findall(r'loc\("(jit\(pure_fn\)/[^"]*)"', text)),
            # the body's runs: the eager pass, `_build`'s traces, `lower`'s
            "passes": {"eager": 1, "traced": sum(len(eager) <= at < len(built) for at in bodies)},
            "calls": {"eager": eager, "traced": built[len(eager):], "lowered": calls[len(built):]},
            "moved": {"eager": moved(before, discovered), "traced": moved(discovered, after),
                      "both": moved(before, after)}}


def test_a_rematerialised_step_stages_the_scopes_and_moves_the_counters(traced_step):
    """`dsa_index`, `dsa_index_loss`, `flash_attention` and `moe_experts` on
    forward, rerun and backward instructions of a step whose blocks are
    rematerialised; attention over the sets takes the flash pair where the
    platform rule says TPU; the device counters follow the steps."""
    from benchmarks import kernel_costs_keye, program_trace
    moved, names = traced_step["moved"]["both"], traced_step["names"]
    assert moved["attention.flash_total"] > 0
    assert moved["dsa.calls_total"] == 2 and moved["dsa.queries_total"] == 2 * BATCH * SEQ
    assert moved["dsa.selected_pairs_total"] >= 2 * BATCH * kernel_costs_keye.set_pairs(SEQ, 32)
    assert moved["dsa.tiles_skipped_total"] == 0  # one tile a row at this size
    staged = {name for name, _ in traced_step["calls"]["lowered"]}
    assert {"index_sets", "index_loss_walk"} <= staged     # interpreted on a CPU
    for scope in ("dsa_index", "dsa_index_loss", "flash_attention", "moe_experts", "rope"):
        mine = [n for n in names if program_trace.scope_of(n + "/op") == scope]
        assert any(n.startswith(f"jit(pure_fn)/jvp({scope})") for n in mine), scope
        assert any(f"transpose(jvp(" in n for n in mine) or scope == "dsa_index", scope
    assert "checkpoint" not in traced_step["text"]  # a custom_vjp region keeps the names


@pytest.mark.parametrize("which", ["eager", "traced"])
def test_a_rematerialised_step_runs_its_sparse_attention_core_once(traced_step, which):
    """The core is outside the block's two regions: a layer a pass of the
    step's body (the eager discovery pass; each trace of the step program)
    one sets kernel, one flash forward over the sets and one walk of the
    index loss, the one that forms the gradients with the value; no walk for
    the loss alone, and no second forward in a region's discovery, first run
    or rerun."""
    runs = traced_step["passes"][which] * traced_step["layers"]
    assert runs > 0
    calls = traced_step["calls"][which]
    assert sorted(calls) == sorted(runs * [
        ("index_sets", None), ("flash_attention_set_fwd", None),
        ("index_loss_walk", True)]), calls
    assert traced_step["moved"][which]["attention.flash_total"] == runs
