"""models/sdar.py (SDAR: a decoder trained by block diffusion) and the
block-diffusion flash pair of ops/attention.py on the CPU at tiny sizes,
seeded weights: the system against the plain reference of
bench/families/sdar.py in float32 (loss, every gradient by leaf, the experts'
choices); the kernel pair in interpret mode against the XLA form, with the
tiles it visits counted against the tiles the mask shows; what the mask means
(the clean half is a causal model's, and blind to the noised half); the
eight shares of a layer against the uncut reference; the family's own
objective through `TrainStep`, its noise, its gauges, its scopes and its
cell's pinned step.
"""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families
from ray_tpu.models import mellum, remat, sdar
from ray_tpu.models.sdar import SDAR, SDARBlock, SDARConfig
from ray_tpu.ops import attention
from ray_tpu.parallel.mesh import kernel_tally, make_mesh
from ray_tpu.parallel.train_step import TrainStep
from ray_tpu.train import _device_profile, _telemetry
from tests._tpu_compile import V5E_LIMIT, V5E_ROOM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = families.load("sdar")
GIB = 1 << 30


def _sizes(rehearse=True, **changed):
    with open(os.path.join(ROOT, "bench", "configs", "sdar_30b_a3b_l5_ep8.json")) as f:
        sizes = json.load(f)
    if rehearse:
        sizes.update(sizes["rehearsal"])
    sizes.update(changed)
    return sizes


def _batch(sizes, rows=2, t=128, seed=0):
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, sizes["vocab_size"], (rows, t + 1)), jnp.int32)
    return tokens[:, :-1], tokens[:, 1:]


def _leaves(tree):
    return {jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


# ---------------------------------------------------------------- the system


@pytest.mark.parametrize("first_expert", [0, 4])
def test_system_agrees_with_the_reference_in_float32(first_expert):
    """The family's objective at step 0 against the reference's pieces
    composed, which are handed idx and next-token targets alone and draw the
    same noise by the configuration's recipe: the loss, every leaf's
    gradient, every layer's choices over the stream's 2T positions."""
    sizes = _sizes(first_expert_held=first_expert)
    model = SDAR(FAMILY.build(sizes, "float32"))
    idx, targets = _batch(sizes)
    params = model.init(jax.random.PRNGKey(1), idx)["params"]

    def system(p):
        loss, _ = sdar.objective(model, p, {"idx": idx, "targets": targets}, jnp.int32(0))
        return loss, model.apply({"params": p}, idx, mutable=["choices"])[1]["choices"]

    (loss, sown), grads = jax.value_and_grad(system, has_aux=True)(params)
    held = {name: jax.tree.leaves(c)[0] for name, c in sown.items()}
    assert sorted(held) == FAMILY.layer_names(sizes)
    assert all(c.shape == (2, 256, sizes["num_experts_per_tok"]) for c in held.values())
    want, want_grads = jax.value_and_grad(
        lambda p: families.reference_loss(FAMILY, p, idx, targets, sizes, held))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    got, ref = _leaves(grads), _leaves(want_grads)
    assert got.keys() == ref.keys()
    for path in got:
        scale = float(jnp.abs(ref[path]).max())
        np.testing.assert_allclose(got[path], ref[path], rtol=2e-4, atol=2e-5 * scale + 1e-9,
                                   err_msg=path)
    names, outer = families.split_params(FAMILY, params, sizes)
    x = FAMILY.embed(outer, idx, sizes)
    for name in names:
        assert (FAMILY.choice(x, params[name], sizes) == held[name]).all()
        x, _ = FAMILY.layer(x, params[name], sizes, held[name])


def test_noise_is_the_recipe_s_and_fresh_every_step():
    """`noise` at step 0 is the reference's `_noise`; block 0 is never masked;
    a block's tokens share a level; another step draws another mask."""
    sizes = _sizes()
    cfg = FAMILY.build(sizes, "float32")
    masked, weight = sdar.noise(cfg, (3, 128), 0)
    want, level = FAMILY._noise(sizes, 3, 128)
    assert (masked == want).all() and not masked[:, :4].any() and masked.any()
    np.testing.assert_allclose(weight, np.where(want, 1 / level, 0.0), rtol=1e-6)
    assert (level.reshape(3, 32, 4) == level.reshape(3, 32, 4)[..., :1]).all()
    assert float(level.min()) >= sizes["noise_eps"]
    again, _ = sdar.noise(cfg, (3, 128), 0)
    other, _ = sdar.noise(cfg, (3, 128), 1)
    assert (again == masked).all() and (other != masked).any()
    assert 0.3 < float(masked[:, 4:].mean()) < 0.7  # t is uniform: half the tokens on average


@pytest.mark.parametrize("seed", [5, 2147489103])
def test_the_mask_token_is_placed_on_one_expert_of_every_rank_a_layer(seed):
    """`placed_row` is a rule over whatever the routers were initialised to:
    at the cell's widths the mask token's 8 experts of a layer are
    `mask_experts`, one on each of the 8 ranks, every one of them 2 or more
    over the best of the rest; the model's own init sets the row so."""
    cfg = FAMILY.build(_sizes(rehearse=False), "float32")
    keys = jax.random.split(jax.random.PRNGKey(seed & 0xFFFFFFFF), cfg.n_layer)
    routers = [jax.nn.initializers.lecun_normal()(k, (cfg.n_embd, cfg.num_experts)) for k in keys]
    row = sdar.placed_row(cfg, routers)
    np.testing.assert_allclose(float(jnp.mean(row * row)), 1.0, rtol=1e-5)
    for layer, router in enumerate(routers):
        logits, mine = row @ router, sdar.mask_experts(cfg, layer)
        assert sorted(map(int, mine // cfg.experts_held)) == list(range(8))  # one a rank
        rest = jnp.delete(logits, mine)
        assert float(logits[mine].min() - rest.max()) > 2.0
    tiny = SDARConfig.tiny(num_held=4, dtype=jnp.float32)
    params = SDAR(tiny).init(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             jnp.zeros((2, 8), jnp.int32))["params"]
    table = params["tok_emb"]["embedding"]
    np.testing.assert_allclose(
        table[tiny.mask_token_id],
        sdar.placed_row(tiny, [params[f"h_{i}"]["moe"]["router"]["kernel"] for i in range(2)]),
        rtol=1e-6)
    assert 0.8 < float(table.std()) < 1.2


def test_the_clean_half_is_a_causal_model_s_and_blind_to_the_noised_half():
    """With blocks of one token the clean half's mask is the causal one: the
    block on the stream gives, for the clean half, what the layer the
    `mellum` family runs gives on x0 alone (same leaves, a full_attention
    layer with the q/k norm and the plain rotary). And at any block length
    the clean half does not change when the noised half does."""
    cfg = SDARConfig.tiny(block_length=1, num_held=4, dtype=jnp.float32)
    t = 32
    x0, xt, other = (jax.random.normal(jax.random.PRNGKey(n), (2, t, cfg.n_embd)) for n in range(3))
    stream = jnp.concatenate([xt, x0], axis=1)
    params = SDARBlock(cfg).init(jax.random.PRNGKey(3), stream)["params"]
    causal = mellum.MellumConfig.tiny(
        layer_types=(mellum.FULL,), qk_norm=True, yarn=None, rope_theta=cfg.rope_theta,
        rms_eps=cfg.rms_eps, num_held=4, dtype=jnp.float32)
    want = mellum.MellumBlock(causal, mellum.FULL).apply({"params": params}, x0)
    got = SDARBlock(cfg).apply({"params": params}, stream)
    np.testing.assert_allclose(got[:, t:], want, rtol=1e-5, atol=1e-5)
    for length in (1, 4):
        block = SDARBlock(dataclasses.replace(cfg, block_length=length))
        a = block.apply({"params": params}, stream)
        b = block.apply({"params": params}, jnp.concatenate([other, x0], axis=1))
        np.testing.assert_array_equal(a[:, t:], b[:, t:])
        assert float(jnp.abs(a[:, :t] - b[:, :t]).max()) > 1e-3


def test_eight_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Each of 8 chips holds one expert of 8 and computes attention whole: the
    shares' outputs, attention counted once, are the uncut layer's (the
    head's slice aside: a layer has none)."""
    sizes = _sizes(num_experts=8)
    whole = FAMILY.build(sizes, "float32")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128, sizes["hidden_size"]))
    params = SDARBlock(whole).init(jax.random.PRNGKey(1), x)["params"]
    want, _ = FAMILY.layer(x, params, sizes)
    after_attention = FAMILY.attend(x, params, sizes)
    total = after_attention
    for e in range(8):
        share = dataclasses.replace(whole, first_expert=e, num_held=1)
        cut = {**params, "moe": {"router": params["moe"]["router"],
                                 **{k: params["moe"][k][e:e + 1] for k in ("gate", "up", "down")}}}
        total = total + SDARBlock(share).apply({"params": cut}, x) - after_attention
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(SDARBlock(whole).apply({"params": params}, x), want,
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------ the kernel pair


def _dense_mask(t, length):
    """`block_diffusion_mask`'s four lines, pair by pair."""
    half = t // 2
    seen = np.zeros((t, t), bool)
    for q in range(t):
        for k in range(t):
            qb, kb = q % half // length, k % half // length
            if q < half:
                seen[q, k] = kb == qb if k < half else kb < qb
            else:
                seen[q, k] = k >= half and kb <= qb
    return seen


@pytest.mark.parametrize("t,length", [(16, 4), (24, 4), (32, 1), (64, 16)])
def test_the_mask_is_its_four_lines(t, length):
    assert (np.asarray(attention.block_diffusion_mask(t, length)) == _dense_mask(t, length)).all()


@pytest.mark.parametrize("length,half,block,d,heads,cut", [
    (4, 256, 128, 128, 2, False),   # tiles taken whole
    (4, 512, 256, 128, 1, True),    # cut into sub-tiles of 128
    (16, 512, 256, 64, 2, True),    # heads side by side in a vreg
    (1, 256, 128, 64, 2, False),
])
def test_flash_pair_agrees_with_the_xla_form(length, half, block, d, heads, cut):
    """flash_bd<L>_fwd and flash_bd<L>_bwd_fused in interpret mode against
    `xla_causal_attention` under the same mask: the output and all three
    gradients."""
    t = 2 * half
    rng = np.random.default_rng(length)
    q, k, v, w = (jnp.asarray(rng.standard_normal((1, t, heads, d)), jnp.float32)
                  for _ in range(4))
    tiles = attention._with_blocks(
        attention.flash_tiles(heads, t, d, q.dtype, blocks=length), t, block, block)
    tiles = tiles.cut(128) if cut else tiles
    assert tiles.blocks == length and (tiles.sub_fwd is not None) == cut
    flash = lambda q, k, v: attention._flash(q, k, v, None, None, tiles, True)
    plain = lambda q, k, v: attention.xla_causal_attention(q, k, v, None, length)
    np.testing.assert_allclose(flash(q, k, v), plain(q, k, v), rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (plain(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5, err_msg=f"d{name}")


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("length,half,block,sub", [(4, 256, 128, None), (4, 1024, 512, 128),
                                                   (16, 8192, 1024, 128), (1, 512, 256, 128)])
def test_the_pair_visits_no_tile_the_mask_hides(length, half, block, sub, backward):
    """The tiles a call visits, by the bounds its kernel loops over
    (`_bd_forward_tiles`, `_bd_backward_tiles`: what `flash_scores` walks),
    are the tiles in which the mask shows any pair, each once; so are the
    sub-tiles of the masked ones. Nothing wholly hidden is computed."""
    t = 2 * half
    tiles = attention.FlashTiles(block, block, 1, None, None, sub, sub, length)
    n = t // block
    visited = np.zeros((n, n), int)  # [query tile, key tile]
    for i in range(n):
        loops = (attention._bd_backward_tiles(i, n // 2) if backward
                 else (attention._bd_forward_tiles(i, n // 2),))
        for loop in loops:
            for plain in (loop.get("plain"), loop.get("plain_after")):
                for j in range(*(plain or (0, 0))):
                    visited[(j, i) if backward else (i, j)] += 1
            for j, _, there in loop.get("edge", ()):
                visited[(j, i) if backward else (i, j)] += bool(there)
            for s in range(len(loop["diag"])):
                visited[(loop["diag_start"] + s, i) if backward else (i, loop["diag_start"] + s)] += 1
    # the mask by its definition at the grain of blocks of positions, exactly
    at = np.arange(t)
    clean, blk = at >= half, at % half // length
    seen = np.where(clean[None, :], np.where(clean[:, None], blk[None, :] <= blk[:, None],
                                             blk[None, :] < blk[:, None]),
                    ~clean[:, None] & (blk[None, :] == blk[:, None])) if t <= 4096 else None
    grain = sub or block
    if seen is not None:
        shown = seen.reshape(n, block, n, block).any((1, 3))
        assert (visited == shown).all()
        by_grain = seen.reshape(t // grain, grain, t // grain, grain).any((1, 3)).sum() * grain ** 2
        computed, needed = attention.flash_scores(tiles, t, backward)
        assert computed == by_grain and needed == seen.sum() == half * half + half * length
    else:  # the cell's size: the counts in closed form
        assert visited.max() == 1 and visited.sum() == (n // 2) * (n // 2 - 1) + 3 * (n // 2)
        computed, needed = attention.flash_scores(tiles, t, backward)
        assert needed == half * half + half * length
        # plain tiles whole; of each of the 3 masked tiles a half, the band's diagonal sub-tiles
        a_side = block // grain
        assert computed == ((n // 2) * (n // 2 - 1) * block ** 2
                            + (n // 2) * (2 * a_side * (a_side + 1) // 2 + a_side) * grain ** 2)


def test_flash_tiles_of_a_doubled_stream():
    tiles = attention.flash_tiles(32, 16384, 128, jnp.bfloat16, blocks=4)
    assert tiles == attention.FlashTiles(1024, 1024, 1, None, None, 128, 128, 4)
    assert attention.flash_tiles(32, 768, 128, jnp.bfloat16, blocks=4).block_q == 384  # divides a half
    for bad in (dict(t=16384, blocks=3), dict(t=16384 + 128, blocks=4), dict(t=16384, blocks=256)):
        with pytest.raises(ValueError):
            attention.flash_tiles(32, bad["t"], 128, jnp.bfloat16, blocks=bad["blocks"])
    with pytest.raises(ValueError):
        attention.flash_tiles(32, 16384, 128, jnp.bfloat16, window=512, blocks=4)
    with pytest.raises(ValueError):  # a tile in both halves
        attention._with_blocks(tiles, 768, 256, 256)
    assert attention.attention_path(16, 4) == "xla"  # TrainStep's init traces at T = 8


# ------------------------------------------------------------------- the step


def test_step_trains_by_the_family_s_objective_and_reports_its_noise():
    """`TrainStep` differentiates `Family.objective` and hands it the state's
    count: each step draws fresh noise (the same batch gives another loss),
    the loss falls, and the diffusion gauges ride the telemetry's summary."""
    cfg = SDARConfig.tiny(num_held=4, dtype=jnp.float32, lr_warmup_steps=0)
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), learning_rate=3e-3)
    try:
        state = ts.init(jax.random.PRNGKey(0))
        idx, targets = _batch({"vocab_size": cfg.vocab_size - 1}, t=64)
        batch = ts.shard_batch({"idx": idx, "targets": targets})
        # a step's loss swings with its noise (1/t weighs a few tokens heavily):
        # what falls is the loss under one step's noise, before and after
        at_step_0 = jax.jit(lambda p: sdar.objective(ts.model, p, batch, jnp.int32(0))[0])
        before = float(at_step_0(state["params"]))
        shares = []
        for step in range(12):
            masked, weight = sdar.noise(cfg, idx.shape, step)
            state, m = ts.step(state, batch)
            shares.append(float(m["diffusion_masked_share"]))
            assert shares[-1] == pytest.approx(float(masked.sum()) / (2 * 60))
            assert float(m["diffusion_weight_max"]) == pytest.approx(float(weight.max()), rel=1e-6)
        assert ts._step._cache_size() == 1  # the count is traced: fresh noise compiles nothing
        assert len(set(shares)) > 6
        assert float(at_step_0(state["params"])) < 0.9 * before
        assert float(m["moe_rows_held"]) > 0
        jax.block_until_ready(m)
        reported = _telemetry.auto_report_metrics()
        assert reported["telemetry/diffusion_masked_share"] == pytest.approx(shares[-1])
        assert reported["telemetry/diffusion_weight_max"] >= 1.0
    finally:
        _telemetry.set_current_recorder(None)


def test_the_step_carries_the_family_s_scopes():
    cfg = SDARConfig.tiny(num_held=4)
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = ts._step.lower(state, {"idx": tok, "targets": tok}).as_text(debug_info=True)
    for scope in ("sdar.noise", "attn.flash_bd", "loss.diffusion"):
        assert scope in text


@pytest.mark.parametrize("op_name,group", [
    ("jit(train_step)/jit(main)/jvp(SDAR)/sdar.noise/select_n", ("sdar.noise", "fwd", "embed")),
    ("jit(train_step)/jit(main)/transpose(jvp(SDAR))/h_3/attn/attn.flash_bd/custom_vjp_call/pallas_call",
     ("h/attn/attn.flash_bd", "bwd", "attn.core")),
    ("jit(train_step)/jit(main)/jvp(jit(train_step))/loss/loss.diffusion/reduce_sum",
     ("loss/loss.diffusion", "fwd", "loss")),
])
def test_device_profile_groups_the_family_s_scopes(op_name, group):
    scope, which = _device_profile.scope_of(op_name)
    assert (scope, which, _device_profile.group_of(scope)) == group


def test_flops_per_token_at_the_cell_s_size():
    sizes = _sizes(rehearse=False)
    cfg = FAMILY.build(sizes, "bfloat16")
    flops = cfg.flops_per_token(8192)
    assert flops == FAMILY.flops_per_token(sizes, 8192)
    core = 5 * 12 * 4096 * (8192 + 4)
    assert flops == 6 * (2 * 5 * 23_855_104 + 18992 * 2048) + core
    assert 0.54 < core / flops < 0.56 and 30.0e12 < flops * 8192 < 30.3e12


def test_remat_plan_of_the_cell(monkeypatch):
    """On a v5e the cell's step keeps the kernels' operands and the expert
    layer's products over the first rung, reckoned under the 14.12 GiB (13.5 until PR 65) a step
    is held to; the blocks are reckoned at the stream's length, the head at
    the batch's."""
    cfg = FAMILY.build(_sizes(rehearse=False), "bfloat16")
    plan = sdar.remat_plan(cfg, remat.StepShape(1, 8192), V5E_LIMIT)
    assert plan.names == remat.FIRST_RUNG + ("moe_plan", "attn_q", "attn_k", "attn_v",
                                             "moe_gate", "moe_up", "moe_out")
    assert plan.reckoned_bytes < V5E_ROOM and plan.block_bytes == int(6.5 * 16384 * 8 * 2048 * 2)
    assert sdar.remat_plan(cfg, remat.StepShape(1, 8192), None).names == \
        remat.FIRST_RUNG + ("moe_plan",)


# The cell's own lowered step (B=1 x T=8192, one chip, a v5e's limit for the
# remat rule) as tests/test_mellum.py:_step_text gives it, taken in PR 61,
# which added it: a change that means to leave this cell's program alone is
# held to it.
SDAR_STEP = "be3bde0ba34b23cd06e6313afe3fb521aa8adf9306139966db94e4b0a8ffebbf"


def test_the_cell_lowers_to_its_pinned_step(monkeypatch):
    from tests.test_mellum import _step_text

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat, "chip_limit", lambda stream: V5E_LIMIT)
    cfg = FAMILY.build(_sizes(rehearse=False), "bfloat16")
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    text = _step_text(ts, state, {"idx": tok, "targets": tok})
    calls = kernel_tally(text)
    assert {k: n for k, n in calls.items() if k.startswith("flash_")} == {
        "flash_bd4_fwd": 5, "flash_bd4_bwd_fused": 5}
    assert calls["qk_prep_fwd"] == 10 and calls["qk_prep_bwd"] == 10
    assert hashlib.sha256(text.encode()).hexdigest() == SDAR_STEP
