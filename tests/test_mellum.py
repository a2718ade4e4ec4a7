"""models/mellum.py and ops/moe.py's ExpertShare on the CPU at tiny sizes,
float32, seeded weights: against the plain reference of
bench/families/mellum.py with the choice held (loss, every gradient, the
choices themselves); the four shares of an expert layer against the uncut
layer; a router that sends every token to the same experts (nothing is
dropped); the YaRN table against numbers worked by hand; and what the layer
sows, which leaves the step program as it was.
"""

import hashlib
import json
import math
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families
from ray_tpu.models import mellum, remat
from ray_tpu.models.loss import loss_fn
from ray_tpu.models.mellum import Mellum, MellumConfig, YarnScaling, yarn_inv_freq
from ray_tpu.ops import attention
from ray_tpu.ops.moe import KEPT_PRODUCTS, ExpertShare
from ray_tpu.parallel.mesh import kernel_tally, make_mesh
from ray_tpu.parallel.train_step import TrainStep
from ray_tpu.train import _telemetry
from tests._tpu_compile import V5E_LIMIT

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = families.load("mellum")


def _sizes(**changed):
    """The cell's configuration at its rehearsal sizes: four layers 3:1, a
    window shorter than the sequences below, heads x head_dim != hidden."""
    with open(os.path.join(ROOT, "bench", "configs", "mellum2_12b_l4_ep4.json")) as f:
        sizes = json.load(f)
    sizes.update(sizes["rehearsal"])
    sizes.update(changed)
    return sizes


def _batch(sizes, rows=2, t=96, seed=0):
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, sizes["vocab_size"], (rows, t + 1)), jnp.int32)
    return tokens[:, :-1], tokens[:, 1:]


@pytest.mark.parametrize("first_expert", [0, 4])
def test_system_agrees_with_the_reference_with_the_choice_held(first_expert):
    sizes = _sizes(first_expert_held=first_expert)
    model = Mellum(FAMILY.build(sizes, "float32"))
    idx, targets = _batch(sizes)
    params = model.init(jax.random.PRNGKey(1), idx)["params"]

    def system(p):
        logits, sown = model.apply({"params": p}, idx, mutable=["choices"])
        return loss_fn(logits, targets), sown["choices"]

    with jax.default_matmul_precision("highest"):
        (want, sown), want_g = jax.value_and_grad(system, has_aux=True)(params)
    names = FAMILY.layer_names(sizes)
    held = {name: jax.tree.leaves(sown[name])[0] for name in names}
    assert all(c.shape == (2, 96, sizes["num_experts_per_tok"]) for c in held.values())
    got, got_g = jax.value_and_grad(
        lambda p: families.reference_loss(FAMILY, p, idx, targets, sizes, held))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    flat_w, flat_g = jax.tree.leaves(want_g), jax.tree.leaves(got_g)
    scale = max(float(jnp.abs(a).max()) for a in flat_w)
    for a, b in zip(flat_w, flat_g):
        assert float(jnp.abs(a - b).max()) <= 2e-5 * scale

    # the reference's own choices, layer by layer on the same input
    _, outer = families.split_params(FAMILY, params, sizes)
    x, agree = FAMILY.embed(outer, idx, sizes), []
    for name in names:
        own = FAMILY.choice(x, params[name], sizes)
        agree.append(float((held[name][..., :, None] == own[..., None, :]).any(-1).mean()))
        x, _ = families.layer_with_aux(FAMILY, x, params[name], sizes, held[name])
    assert min(agree) >= 0.995


def _expert_layer(first, held, x, params):
    layer = ExpertShare(x.shape[-1], params["gate"].shape[-1], 8, 2, first, held, jnp.float32)
    share = {"router": params["router"],
             **{k: params[k][first:first + held] for k in ("gate", "up", "down")}}
    with jax.default_matmul_precision("highest"):
        return layer.apply({"params": share}, x, mutable=["choices", "moe_load"])


def _dense_experts(x, params, k=2):
    """Every expert on every token, weighted by the token's gate."""
    hi = jax.lax.Precision.HIGHEST
    probs = jax.nn.softmax(jnp.einsum("btc,ce->bte", x, params["router"]["kernel"], precision=hi))
    top_p, idx = jax.lax.top_k(probs, k)
    gates = top_p / top_p.sum(-1, keepdims=True)
    y = 0.0
    for e in range(params["gate"].shape[0]):
        weight = jnp.where(idx == e, gates, 0.0).sum(-1)
        h = jax.nn.silu(jnp.einsum("btc,cf->btf", x, params["gate"][e], precision=hi)) \
            * jnp.einsum("btc,cf->btf", x, params["up"][e], precision=hi)
        y = y + weight[..., None] * jnp.einsum("btf,fc->btc", h, params["down"][e], precision=hi)
    return y, idx


@pytest.fixture(scope="module")
def expert_params():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, 24), jnp.float32)
    layer = ExpertShare(24, 16, 8, 2, dtype=jnp.float32)
    return x, layer.init(jax.random.PRNGKey(4), x)["params"]


def test_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(expert_params):
    x, params = expert_params
    want, idx = _dense_experts(x, params)
    shares = [_expert_layer(first, 2, x, params) for first in (0, 2, 4, 6)]
    np.testing.assert_allclose(np.asarray(sum(y for y, _ in shares)), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    rows = np.concatenate([np.asarray(s["moe_load"]["rows"][0]) for _, s in shares])
    np.testing.assert_array_equal(rows, np.bincount(np.asarray(idx).ravel(), minlength=8))
    for _, sown in shares:  # every share sows the choices over the whole layer
        np.testing.assert_array_equal(np.asarray(sown["choices"]["experts"][0]), np.asarray(idx))
    whole, _ = _expert_layer(0, 8, x, params)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_every_token_to_the_same_experts_and_none_is_dropped(expert_params):
    x, params = expert_params
    x = jnp.abs(x)
    kernel = jnp.zeros_like(params["router"]["kernel"]).at[:, 5].set(2.0).at[:, 2].set(1.0)
    params = {**params, "router": {"kernel": kernel}}
    want, idx = _dense_experts(x, params)
    assert set(np.asarray(idx).ravel()) == {2, 5}
    got, sown = _expert_layer(0, 8, x, params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    rows = np.asarray(sown["moe_load"]["rows"][0])
    assert rows[2] == rows[5] == x.shape[0] * x.shape[1] and rows.sum() == 2 * rows[2]
    # the share that holds neither expert computes nothing, and says so
    none, sown = _expert_layer(6, 2, x, params)
    assert float(jnp.abs(none).max()) == 0.0 and int(sown["moe_load"]["rows"][0].sum()) == 0
    # gradients too: against the dense layer's
    loss = lambda f: lambda x: (f(x)[0] ** 2).sum()
    g_got = jax.grad(loss(lambda x: _expert_layer(0, 8, x, params)))(x)
    g_want = jax.grad(loss(lambda x: _dense_experts(x, params)))(x)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want), rtol=1e-4, atol=1e-6)


def test_yarn_table_against_numbers_worked_by_hand():
    """head_dim 128, theta 5e5, factor 16 over 8,192 original positions:
    corr(32) = 128 ln(8192 / 64 pi) / (2 ln 5e5) = 18.08 -> low 18,
    corr(1) = 128 ln(8192 / 2 pi) / (2 ln 5e5) = 34.98 -> high 35."""
    ln = math.log
    assert math.floor(128 * ln(8192 / (64 * math.pi)) / (2 * ln(5e5))) == 18
    assert math.ceil(128 * ln(8192 / (2 * math.pi)) / (2 * ln(5e5))) == 35
    yarn = YarnScaling(16.0, 8192, 1.2772588722239782)
    got = yarn_inv_freq(128, 5e5, yarn)
    assert got.shape == (64,) and got.dtype == np.float32
    plain = lambda i: 5e5 ** (-2 * i / 128)
    for i in (0, 7, 18):       # ramp 0: the plain frequency
        assert got[i] == pytest.approx(plain(i), rel=1e-5)
    for i in (35, 50, 63):     # ramp 1: divided by the factor
        assert got[i] == pytest.approx(plain(i) / 16, rel=1e-5)
    for i in (19, 26, 34):     # between: (i - 18) / 17 of the way
        ramp = (i - 18) / 17
        assert got[i] == pytest.approx(plain(i) * ((1 - ramp) + ramp / 16), rel=1e-5)
    assert got[26] == pytest.approx(2.7044e-3, rel=1e-4)  # e^(-0.40625 x 13.1224) = 4.8395e-3, x (9/17 + 8/272)
    # the reference computes its own, from the same published formula
    sizes = _sizes()
    theirs = FAMILY._yarn_inv_freq(128, sizes["rope_parameters"]["full_attention"])
    np.testing.assert_allclose(np.asarray(theirs), got, rtol=2e-6)
    cfg = MellumConfig(yarn=yarn)
    inv, factor = cfg.rotary(mellum.FULL)
    assert factor == 1.2772588722239782 and len(inv) == 64
    assert cfg.rotary(mellum.SLIDING) == (None, 1.0)


def test_flops_per_token_at_the_cell_s_size():
    with open(os.path.join(ROOT, "bench", "configs", "mellum2_12b_l4_ep4.json")) as f:
        sizes = json.load(f)
    assert FAMILY.matmul_params(sizes) == 4 * (21_233_664 + 147_456 + 12_386_304) + 56_623_104
    by_hand = 6 * 191_692_800 + 12 * 4096 * (4096 + 3 * 960)
    assert FAMILY.flops_per_token(sizes, 8192) == by_hand == 1_493_041_152
    assert FAMILY.build(sizes, "bfloat16").flops_per_token(8192) == by_hand


def _tiny_step(cfg):
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    return ts, state, {"idx": tok, "targets": tok}


def test_sowing_leaves_the_step_program_as_it_was(monkeypatch):
    """`sow` into a collection that is not mutable does nothing: the step
    lowers to the same text with the "choices" line and without it."""
    cfg = MellumConfig.tiny(num_held=4)
    texts = []
    for sows in (True, False):
        if not sows:
            plain = nn.Module.sow
            monkeypatch.setattr(ExpertShare, "sow", lambda self, col, *a, **k: (
                plain(self, col, *a, **k) if col != "choices" else False))
        ts, state, batch = _tiny_step(cfg)
        texts.append(ts._step.lower(state, batch).as_text())
    assert texts[0] == texts[1] and "stablehlo" in texts[0]


# Each cell's configuration at its cell's shape (rows a chip, sequence
# length) on its cell's mesh, with the rule of models/remat.py given a v5e's
# limit, so that the program is the cell's own: sha256 of the step as
# `_step_text` gives it, taken on the commit that gave the flash calls
# their operands' rows (PR 42: (B, T, H * D) where heads are narrower than
# a vreg, delta made inside the backward call everywhere; the calls are in
# every program, so all changed by design there, as in PR 33, PR 35 and
# PR 37, and were pinned again for the next PR that means to leave them
# alone); the layers
# whose attention is windowed, of all; the names the rule saves there after
# the first rung. The routed cell's was pinned again in PR 44, which changed
# its step by design: the expert layer's two passes that end at the tokens
# sum the buffer's rows in token order (`ops/moe.py:sum_by_token`, a kernel
# and a third sort of the plan) where they gathered a row for every
# assignment, the combine's sums leave in the stream's dtype, and the layer
# sows the rows it walked beside its row counts; and again in PR 45, by
# design too: the layer's products and its plan are named residuals, its
# backward reads them where the step's rows fit the buffer with headroom,
# and the rule keeps the plan, the gate and the up product here.
# The two dense cells' programs never call `ops/moe.py` and stay as they were.
# All three were pinned again in PR 51, which moved every flash call by
# design: masked tiles are cut into sub-tiles of 128 (`FlashTiles.sub_fwd`, `.sub_bwd`) and
# the windowed call takes the causal call's tile, 1,024 where it was 512.
# The routed cell's again in PR 53, by design: q and k of its four layers (heads
# of 128, turned, not normed) go through ops/qk_prep.py's pair into the flash
# calls on rows: the last number, the layers that take the pair; the two dense
# cells (gpt2's own attention; mistral's `attn_fn` under its mesh) take it in none.
# PR 62 moved mistral's and the routed cell's by design: the remat rule takes
# a rung by depth (models/remat.py), and on a v5e mistral's last seven blocks
# of eight save the flash calls' operands and the last six `mlp_up`, where
# all eight saved `mlp_up` and none the operands; the routed cell's last three
# of four save the down product and the last two the gate's, where all four
# saved the gate's and the up's; gpt2_small's plan takes both its rungs
# whole: its step stayed as it was, text for text.
# PR 65 moved the same two by design: the rule is held to the chip's own limit
# to within 64 MiB (15.6875 GiB, not 15), and mistral's eight blocks save the
# operands and the last seven `mlp_up`; the routed cell's four save the gate's
# and the down product and the last three the up's. gpt2_small's stayed again.
PINNED_STEPS = {
    "gpt2_small": ("b747484d7c5664494e19fcc6d7ed0bf5bfb55b05683d899de6fd65410e14e8ac", 32, 1024, 0, 12,
                   ("attn_q", "attn_k", "attn_v", "mlp_up"), 0),
    "mistral_7b_l8": ("6a415a8455834a49d9fb8042f3bc86c5d8978452f899bb315b12fb208387ff7a", 1, 8192, 0, 8,
                      ("mlp_up", "attn_q", "attn_k", "attn_v"), 0),
    "mellum2_12b_l4_ep4": ("8799863c2bfa03166466e2737c3f3f1cec2662207ab23a8180579eed486c9735", 2, 8192, 3, 4,
                           ("moe_plan", "attn_q", "attn_k", "attn_v", "moe_gate", "moe_up", "moe_out"), 4),
}


def _step_text(ts, state, batch):
    """The step lowered for a TPU, with each Mosaic kernel's serialized body
    (it holds source lines) taken out, and the step's jaxpr, which holds the
    kernels' bodies as equations (and the checkpoint policy as a function's
    repr: its address is taken out)."""
    return _traced_text(ts._step.trace(state, batch))


def _traced_text(traced):
    lowered = traced.lower(lowering_platforms=("tpu",)).as_text()
    return (re.sub(r'\\22body\\22: \\22[^\\]*\\22', "body", lowered)
            + re.sub(r" at 0x[0-9a-f]+", "", str(traced.jaxpr)))


@pytest.mark.parametrize("name", sorted(PINNED_STEPS))
def test_old_configurations_lower_to_the_parent_s_step(name, monkeypatch):
    """The step of each cell's configuration, lowered for a TPU on this box,
    runs the forward flash kernel once a layer: the blocks' remat saves its
    output and logsumexp (models/remat.py), where the step before PR 33 ran
    it twice; and one backward call a layer, `…bwd_fused`, where the step
    before PR 35 ran two (none is `flash_bwd_dq` or `flash_bwd_dkv`). A windowed
    layer's calls carry the window in their name. And the program is the
    pinned one: a change that means to leave the cells' programs alone is
    held to it."""
    from tests.test_qk_prep import heads_stay_where_written

    want, rows, seq_len, windowed, layers, saved, prepped = PINNED_STEPS[name]
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        sizes = json.load(f)
    cfg = families.load(sizes["family"]).build(sizes, "bfloat16")
    # the cell's own program: its mesh, and a v5e's limit for the rule
    monkeypatch.setattr(remat, "chip_limit", lambda stream: V5E_LIMIT)
    chips = math.prod(sizes["mesh"].values())
    ts = TrainStep(cfg, make_mesh(sizes["mesh"], devices=jax.devices()[:chips]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((rows * chips, seq_len), jnp.int32)
    text = _step_text(ts, state, {"idx": tok, "targets": tok})
    assert remat.traced(ts.model.config).names == remat.FIRST_RUNG + saved
    calls = {k: n for k, n in kernel_tally(text).items() if k.startswith("flash_")}
    win = f"flash_win{sizes.get('sliding_window')}_"
    kernels = {"fwd": layers - windowed, "bwd_fused": layers - windowed}
    # the plain causal backward call's name ends with those of the two calls
    # it replaced (attention.LEGACY_NAMES), a windowed one's does not
    tail = {"fwd": "", "bwd_fused": "_flash_bwd_dq_flash_bwd_dkv"}
    assert calls == {**{f"flash_{k}{tail[k]}": n for k, n in kernels.items()},
                     **{win + k: windowed for k in kernels if windowed}}
    # q's and k's call a layer that takes ops/qk_prep.py's pair: the forward's
    # once, the plan keeps `attn_q` and `attn_k` there
    tally = kernel_tally(text)
    assert tally["qk_prep_fwd"] == tally["qk_prep_bwd"] == 2 * prepped
    if prepped:
        heads_stay_where_written(text, rows, seq_len, cfg.n_head, cfg.n_kv_head)
    assert hashlib.sha256(text.encode()).hexdigest() == want


def expert_calls(text, layers):
    """Of a lowered step with `layers` routed layers: the call sites of
    megablox's two kernels (under the names the compiler gives the jitted
    functions round them) and the runs of `moe_token_sum`, a layer. Both
    branches of every `cond` are in the text and are counted: of a layer's
    grouped matmuls 3 forward on the buffer with headroom and 3 in the
    branch that overflowed, 3 `gmm` and 3 `tgmm` in the backward that reads
    the products and 6 and 3 in the one that overflowed, and one more `gmm`
    under remat for each product the plan does not keep."""
    import collections

    calls = collections.Counter(re.sub(r"_\d+$", "", fn)
                                for fn in re.findall(r"call @(t?gmm(?:_\d+)?)\(", text))
    calls["moe_token_sum"] = kernel_tally(text)["moe_token_sum"]
    assert all(n % layers == 0 for n in calls.values()), calls
    return {name: n // layers for name, n in calls.items()}


def lowered_cell(name, batch, monkeypatch, limit=V5E_LIMIT):
    """(the cell's own step lowered for a TPU on this box with the rule given
    `limit`, the plan that trace took)."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat, "chip_limit", lambda stream: limit)
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        sizes = json.load(f)
    cfg = families.load(sizes["family"]).build(sizes, "bfloat16")
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct(batch, jnp.int32)
    text = ts._step.trace(state, {"idx": tok, "targets": tok}).lower(
        lowering_platforms=("tpu",)).as_text()
    return text, remat.traced(cfg)


@pytest.mark.parametrize("limit_gib,kept,again", [
    (V5E_LIMIT / remat.GIB, {"moe_gate": 4, "moe_up": 3, "moe_out": 4}, [1, 0, 0, 0]),
    (15, {"moe_gate": 2, "moe_up": 0, "moe_out": 3}, [3, 2, 1, 1]),
    (14.5, {"moe_gate": 2, "moe_up": 0, "moe_out": 1}, [3, 3, 2, 1]),
    (16, {"moe_gate": 4, "moe_up": 4, "moe_out": 4}, [0, 0, 0, 0]),
    (14, {"moe_gate": 0, "moe_up": 0, "moe_out": 0}, [3, 3, 3, 3])])
def test_a_kept_product_s_forward_matmul_runs_once_a_layer(limit_gib, kept, again, monkeypatch):
    """The cell's step under the v5e's limit (15.6875 GiB since PR 65) keeps
    the down product (5.8 ms a GiB) and the gate's (4.1) whole and the up's
    in the last three layers of four, and a layer runs again under remat what
    it does not keep: one matmul in the first layer. Under 15 GiB, the v5e's
    until then, the down product in the last three layers and the gate's in
    the last two (the rule takes a rung by depth, models/remat.py): three
    matmuls in the first layer, two in the second, one in the last two; with half a GiB less the
    down product in the last layer alone; with room for every
    product no layer runs any again; under a limit with room for
    no further rung all three run again in every layer, as before PR 45. The
    route's plan is in the first rung either way."""
    text, plan = lowered_cell("mellum2_12b_l4_ep4", (2, 8192), monkeypatch,
                              int(limit_gib * remat.GIB))
    assert plan.names[:3] == remat.FIRST_RUNG + ("moe_plan",)
    assert {name: plan.depth(name) for name in KEPT_PRODUCTS} == kept
    assert [sum(name not in names for name in KEPT_PRODUCTS) for names in plan.by_layer] == again
    calls = expert_calls(text, 1)
    assert calls == {"gmm": 4 * 15 + sum(again), "tgmm": 4 * 6, "moe_token_sum": 4 * 4}


def test_step_reports_its_expert_load_through_the_telemetry():
    cfg = MellumConfig.tiny(num_held=4, dtype=jnp.float32)
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    try:
        state = ts.init(jax.random.PRNGKey(0))
        idx, targets = _batch({"vocab_size": cfg.vocab_size}, t=64)
        for _ in range(2):
            state, m = ts.step(state, ts.shard_batch({"idx": idx, "targets": targets}))
        jax.block_until_ready(m)
        assert float(m["moe_rows_held"]) == round(
            float(m["moe_held_share"]) * idx.size * cfg.top_k * cfg.n_layer)
        assert 0.3 < float(m["moe_held_share"]) < 0.7   # 4 of 8 experts held
        assert float(m["moe_load_max_over_mean"]) >= 1.0
        # 2 x 64 tokens, top-2, 4 of 8 held: the headroom buffer would be one
        # row tile of 512, every assignment is 256, so the layers take those
        assert float(m["moe_rows_summed_share"]) == 1.0
        report = _telemetry.auto_report_metrics()
        # and no layer's backward read a kept product: none takes the buffer
        # with headroom (and a CPU device states no limit to plan under)
        assert float(m["moe_kept_read_share"]) == 0.0
        for key in ("moe_rows_held", "moe_held_share", "moe_load_max_over_mean",
                    "moe_rows_summed_share", "moe_kept_read_share"):
            assert report[f"telemetry/{key}"] == pytest.approx(float(m[key]))
    finally:
        _telemetry.set_current_recorder(None)


def test_shape_functions_of_the_new_kernels():
    from bench import shapes

    window = shapes.load("flash_window")
    bh, t, d, w = 64, 8192, 128, 1024
    mm = 2 * (w * t - w * w // 2) * d * bh  # one matmul over the scores a window needs
    fwd = window(f"flash_win1024_fwd custom-call -> (bf16[{bh},{t},{d}], f32[{bh},1,{t}])")
    wide = f"bf16[{bh},{t},{d}]"
    bwd = shapes.load("flash_backward")(
        f"transpose_jvp_flash_win1024_bwd_fused_ custom-call -> ({wide}, {wide}, {wide})")
    assert fwd == (2 * mm, bh * (4 * t * d * 2 + t * 4))
    assert bwd == (5 * mm, bh * (7 * t * d * 2 + 2 * t * 4))
    assert fwd[0] / shapes.flash_attention("a custom-call -> (bf16[64,8192,128], f32[64,1,8192])")[0] \
        == pytest.approx(0.234, abs=1e-3)  # of a causal layer's scores
    assert window("flash_fwd custom-call -> (bf16[64,8192,128], f32[64,1,8192])") is None
    assert window("flash_win8192_fwd custom-call -> (bf16[64,8192,128], f32[64,1,8192])") is None

    gmm = shapes.load("moe_gmm")
    meta = "s32[17], s32[111], s32[111], s32[1], "
    rows = 16384 * 8 * 16 // 64  # the even-routing load: the buffer's 49,152 rows / 1.5
    up = gmm("gmm custom-call -> bf16[49152,896]", meta + "bf16[49152,2304], bf16[16,2304,896]")
    assert up == (2 * rows * 2304 * 896, rows * (2304 + 896) * 2 + 16 * 2304 * 896 * 2)
    d_rows = gmm("gmm custom-call -> bf16[49152,2304]",  # rhs transposed inside the call
                 meta + "bf16[49152,896], bf16[16,2304,896]")
    assert d_rows == up
    down = gmm("gmm custom-call -> bf16[49152,2304]", meta + "bf16[49152,896], bf16[16,896,2304]")
    assert down == up
    d_up = gmm("tgmm custom-call -> bf16[16,2304,896]", meta + "bf16[2304,49152], bf16[49152,896]")
    assert d_up == up
    assert gmm("fusion fusion -> f32[128,256]", "f32[128,256], f32[128,256]") is None
    assert gmm("gmm custom-call -> bf16[49152,896]", "") is None


@pytest.mark.parametrize("limit,read", [(1024 * remat.GIB, 1.0), (None, 0.0)],
                         ids=["room_for_every_rung", "no_limit_stated"])
def test_step_says_whether_its_backward_read_kept_products(limit, read, monkeypatch):
    """2 x 512 tokens, top-2 of 8, two held: every layer fits the buffer with
    headroom (1,024 rows of 2,048 assignments). Where the plan of the trace
    keeps the products `moe_kept_read_share` is 1, under a plan that keeps
    none 0, and the booked plan says which it was."""
    monkeypatch.setattr(remat, "chip_limit", lambda stream: limit)
    cfg = MellumConfig.tiny(num_held=2, block_size=512)
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
    state = ts.init(jax.random.PRNGKey(0))
    idx, targets = _batch({"vocab_size": cfg.vocab_size}, t=512)
    _, m = ts.step(state, ts.shard_batch({"idx": idx, "targets": targets}))
    kept = set(remat.traced(cfg).names) & {"moe_gate", "moe_up", "moe_out"}
    assert bool(kept) == bool(read) and "moe_plan" in remat.traced(cfg).names
    assert float(m["moe_rows_summed_share"]) == 0.5
    assert float(m["moe_kept_read_share"]) == read


@pytest.mark.parametrize("routing", ["even", "all_to_the_held_experts"])
def test_rows_beyond_the_buffer_s_headroom_take_the_whole_buffer(expert_params, routing):
    """1,024 tokens, top-2 of 8, experts 2-3 held: the buffer has room for
    1,024 of the 2,048 assignments (1.5 x the even load of 512, in row
    tiles). Even routing fits it; a router that sends every token to both
    held experts does not, and the step takes the buffer of all rows:
    either way the layer is the dense one, values and gradients."""
    _, params = expert_params
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 512, 24), jnp.float32)
    if routing != "even":
        x = jnp.abs(x)
        kernel = jnp.zeros_like(params["router"]["kernel"]).at[:, 2].set(2.0).at[:, 3].set(1.0)
        params = {**params, "router": {"kernel": kernel}}
    got, sown = _expert_layer(2, 2, x, params)
    rows = int(sown["moe_load"]["rows"][0].sum())
    assert (rows <= 1024) == (routing == "even") and (routing == "even" or rows == 2048)
    held_only = {**params, **{k: params[k].at[:2].set(0).at[4:].set(0)
                              for k in ("gate", "up", "down")}}
    want, _ = _dense_experts(x, held_only)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    loss = lambda f: lambda x: (f(x)[0] ** 2).sum()
    g_got = jax.grad(loss(lambda x: _expert_layer(2, 2, x, params)))(x)
    g_want = jax.grad(loss(lambda x: _dense_experts(x, held_only)))(x)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("width,hidden,experts,top_k,held,rows", [
    (2304, 896, 64, 8, 16, 49152), (2048, 1792, 32, 4, 8, 24576)], ids=["mellum", "lfm2"])
def test_no_pass_of_the_headroom_branch_moves_a_row_for_every_assignment(
        width, hidden, experts, top_k, held, rows, monkeypatch):
    """`_expert_rows` on the buffer with headroom alone, the branch that runs
    (the whole layer also holds the branch for a step that overflows it,
    whose buffer is every assignment by design), forward and vjp at the
    cell's size, lowered for a TPU: no array of rows of C has more rows than
    the buffer (the parent gathered 16384 x 8 x 2304 rows in `combine_rows`
    forward and in `dispatch_rows` backward; lfm2 16384 x 4 x 2048), the two
    sums are the kernel's, and no scatter came in beside the few hundred
    integers of megablox's group metadata."""
    import functools

    from ray_tpu.ops import moe

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    n = 16384
    room = -(-int(moe._ROW_HEADROOM * n * top_k * held / experts) // 512) * 512
    assert rows == room < n * top_k

    def both_passes(idx, weights, x, gates, g):
        plan = moe.route_plan(idx, 0, held)
        y, vjp = jax.vjp(functools.partial(moe._expert_rows, moe.SWIGLU, rows, jnp.bfloat16, plan),
                         weights, x, gates)
        return y, vjp(g)

    shape = jax.ShapeDtypeStruct
    weights = {"gate": shape((held, width, hidden), jnp.float32),
               "up": shape((held, width, hidden), jnp.float32),
               "down": shape((held, hidden, width), jnp.float32)}
    text = jax.jit(both_passes).trace(
        shape((n, top_k), jnp.int32), weights, shape((n, width), jnp.bfloat16),
        shape((n, top_k), jnp.float32), shape((n, width), jnp.bfloat16),
    ).lower(lowering_platforms=("tpu",)).as_text()
    of_rows = {math.prod(map(int, dims.split("x")[:-1]))
               for dims in re.findall(rf"tensor<((?:\d+x)+){width}x(?:bf16|f32)>", text)}
    assert max(of_rows) == rows and n in of_rows, sorted(of_rows)
    assert f"{n}x{top_k}x{width}" not in text and f"{n * top_k}x{width}" not in text
    assert kernel_tally(text).get("moe_token_sum") == 2, kernel_tally(text)
    scattered = re.findall(
        r'"stablehlo\.scatter"\(.*?\}\) : \([^)]*\) -> tensor<([^>]*)>', text, re.S)
    assert scattered and all(
        math.prod(int(d) for d in s.split("x")[:-1]) < 1024 for s in scattered), scattered
