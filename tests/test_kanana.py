"""models/kanana.py against the plain reference of bench/families/kanana.py on
seeded weights (loss and every gradient, the choices held), what the
comparison catches when a part is dropped, the gates' epsilon as the layer's
number, the eight shares of the experts with the shared expert counted once
against the uncut layer, the bias as a leaf no gradient moves, the remat
rule's plan for the cell, the cell's lowered step, and the lowered step of
the cell that shares the sigmoid router, pinned on the parent's tree."""

import contextlib
import dataclasses
import hashlib
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families
from ray_tpu.models import kanana, layers, remat
from ray_tpu.models.kanana import Kanana, KananaConfig
from ray_tpu.models.loss import loss_fn
from ray_tpu.ops import attention, short_conv
from ray_tpu.ops.moe import (KEPT_PRODUCTS, SELECTION_BIAS, SELECTION_BIAS_RATE, SIGMOID,
                             ExpertShare)
from ray_tpu.parallel.mesh import kernel_tally, make_mesh
from ray_tpu.parallel.train_step import TrainStep
from ray_tpu.train import _telemetry
from tests.test_lfm2 import _batch, _experts_by_hand, _with_bias  # the sigmoid router's other family
from tests._tpu_compile import V5E_LIMIT, V5E_ROOM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = families.load("kanana")
GIB = remat.GIB


def _sizes(rehearse=True, name="kanana2_30b_l5_ep8", **changed):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        sizes = json.load(f)
    if rehearse:
        sizes.update(sizes["rehearsal"])
    sizes.update(changed)
    return sizes


def _loss(cfg, params, idx, targets):
    return loss_fn(Kanana(cfg).apply({"params": params}, idx), targets)


@pytest.fixture(scope="module")
def seeded():
    sizes = _sizes()
    cfg = FAMILY.build(sizes, "float32")
    idx, targets = _batch(sizes["vocab_size"])
    params = Kanana(cfg).init(jax.random.PRNGKey(1), idx)["params"]
    # the norms' weights off one, so that each one's gradient is a test of
    # its own; the selection bias off zero
    params = jax.tree.map(lambda p: p + 0.05 * jax.random.normal(
        jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else p, params)
    params = _with_bias(params)
    # queries and both parts of the keys large enough that the softmax is no
    # longer flat and each part of the scores counts
    for i in range(cfg.n_layer):
        for w, by in (("q_proj", 3.0), ("kv_b_proj", 2.0)):
            params["p_0"][f"h_{i}"]["attn"][w]["kernel"] *= by
    choices = Kanana(cfg).apply({"params": params}, idx, mutable=["choices"])[1]["choices"]
    held, = jax.tree.leaves(choices["p_0"])  # one entry for the whole group
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.value_and_grad(lambda p: families.reference_loss(
            FAMILY, p, idx, targets, sizes, {"p_0": held}))(params)
    return sizes, params, idx, targets, held, ref_loss, ref_grads


def test_system_agrees_with_the_reference_in_float32(seeded):
    """Loss and every gradient with the choices held. Both sides are float32;
    what differs is the order of the sums (two score products added against
    one over the joined widths, the grouped matmul's rows against every token
    through every expert): 1e-5 of the loss, 2e-4 of each gradient's largest
    entry. The bias is in neither side's loss: its gradient is zero on both."""
    sizes, params, idx, targets, held, ref_loss, ref_grads = seeded
    cfg = FAMILY.build(sizes, "float32")
    assert sorted(params) == ["final_norm", "lm_head", "p_0", "tok_emb"] == sorted(
        FAMILY.layer_names(sizes) + ["final_norm", "lm_head", "tok_emb"])
    assert held.shape == (4, 2, 64, 2)  # four routed blocks' choices, stacked
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: _loss(cfg, p, idx, targets))(params)
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * float(ref_loss)
    flat, ref_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, ref_grads))
    # a block's attention 4 matrices and the latent's norm, its 2 norms; the
    # dense MLP 3; a routed one 5 with its bias and the shared expert's 3;
    # embedding, final norm and head
    assert len(flat) == len(ref_flat) == 5 * 7 + 3 + 4 * 8 + 3
    for path, g in flat.items():
        if path[-1].key == SELECTION_BIAS:
            assert not np.asarray(g).any() and not np.asarray(ref_flat[path]).any()
            continue
        scale = float(jnp.abs(ref_flat[path]).max())
        assert scale > 0, path
        np.testing.assert_allclose(g, ref_flat[path], rtol=0, atol=2e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_reference_s_own_choices_are_the_system_s(seeded):
    sizes, params, idx, _, held, _, _ = seeded
    with jax.default_matmul_precision("highest"):
        own = FAMILY.choice(FAMILY.embed({"tok_emb": params["tok_emb"]}, idx, sizes),
                            params["p_0"], sizes)
    assert own.shape == held.shape
    share = (held[..., :, None] == own[..., None, :]).any(-1).mean((1, 2, 3))
    assert (np.asarray(share) > 0.99).all(), share


def _changed_attention(change):
    """ops/attention.py's `latent_attention` on operands changed first."""
    real = attention.latent_attention
    return lambda q, q2, k, k2, v: real(*change(q, q2, k, k2, v))


def _rms(x):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + 1e-6)


DROPPED = {
    # the scores' second product left out
    "shared_part": lambda q, q2, k, k2, v: (q, jnp.zeros_like(q2), k, k2, v),
    # the shared key normed as the latent is
    "k_pe_normed": lambda q, q2, k, k2, v: (q, q2, k, _rms(k2), v),
    # the value read from a 192-wide slot: its first 64 the padding's
    "v_padded": lambda q, q2, k, k2, v: (
        q, q2, k, k2, jnp.concatenate([jnp.zeros_like(q2), v], -1)[..., :v.shape[-1]]),
}


@pytest.mark.parametrize("what", sorted(DROPPED) + ["rotary", "pairs", "shared_expert", "scaling"])
def test_the_comparison_catches_what_is_dropped(seeded, what, monkeypatch):
    """A program that leaves out the scores' second part, norms the shared
    key, pads the value, leaves out the rotation, turns the 64 as halves and
    not as adjacent pairs, leaves out the shared expert or the routed
    scaling is outside the loss's tolerance of the test above."""
    sizes, params, idx, targets, held, ref_loss, _ = seeded
    cfg = FAMILY.build(sizes, "float32")
    if what in DROPPED:
        monkeypatch.setattr(attention, "latent_attention", _changed_attention(DROPPED[what]))
    elif what == "rotary":
        monkeypatch.setattr(layers, "apply_rope", lambda x, angles: x)
    elif what == "pairs":
        monkeypatch.setattr(layers, "pairs_apart", lambda x: x)
    elif what == "shared_expert":
        params = jax.tree_util.tree_map_with_path(
            lambda path, p: jnp.zeros_like(p) if "shared" in jax.tree_util.keystr(path)
            and "down" in jax.tree_util.keystr(path) else p, params)
    else:
        cfg = dataclasses.replace(cfg, routed_scaling=1.0)
    with jax.default_matmul_precision("highest"):
        loss = _loss(cfg, params, idx, targets)
    assert abs(float(loss) - float(ref_loss)) > 1e-4 * float(ref_loss), what


def test_the_gates_epsilon_is_the_layer_s():
    """At scores small enough that 1e-6 under their sum shows: the layer
    built with 1e-20 computes the source's gates, the default's 1e-6 another
    result, and both by hand."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 16)).at[..., 0].set(1.0)
    layers = {eps: ExpertShare(16, 8, 8, 2, router=SIGMOID, dtype=jnp.float32,
                               hand_up_choices=True, gate_eps=eps) for eps in (1e-20, 1e-6)}
    p = layers[1e-20].init(jax.random.PRNGKey(1), x)["params"]
    # every logit near -12, s about 6e-6: the sum of a token's two near 1e-5
    p["router"]["kernel"] = (0.1 * p["router"]["kernel"]).at[0].set(-12.0)
    s = jax.nn.sigmoid(x @ p["router"]["kernel"])
    idx = jax.lax.top_k(s, 2)[1]
    chosen = jnp.take_along_axis(s, idx, -1)
    results = {}
    for eps, layer in layers.items():
        y, got = layer.apply({"params": p}, x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(idx))
        want = _experts_by_hand(p, x, idx, chosen / (chosen.sum(-1, keepdims=True) + eps))
        np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-6)
        results[eps] = y
    assert ExpertShare(16, 8, 8, 2).gate_eps == 1e-6  # what every other cell's layer keeps
    gap = float(jnp.abs(results[1e-20] - results[1e-6]).max() / jnp.abs(results[1e-20]).max())
    assert gap > 0.01, gap


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The eight shares of the experts, each computed by the program as a
    chip's share of an ep = 8 layer (the router whole on every chip), summed,
    plus the shared expert counted once (every chip computes it alike): the
    uncut MLP of the reference, all experts held. Counted a chip it would be
    eight times too much, which the last line sees."""
    sizes = _sizes(n_routed_experts=8, first_expert_held=0)
    cfg = FAMILY.build(sizes, "float32")
    d, ff, k = sizes["hidden_size"], sizes["moe_intermediate_size"], sizes["num_experts_per_tok"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, d))
    make = lambda first, held: ExpertShare(
        d, ff, 8, k, first, held, jnp.float32, router=SIGMOID, hand_up_choices=True,
        scaling=cfg.routed_scaling, gate_eps=cfg.gate_eps)
    p = make(0, None).init(jax.random.PRNGKey(7), x)["params"]
    p[SELECTION_BIAS] = 0.3 * jax.random.normal(jax.random.PRNGKey(8), (8,))
    shared = layers.SharedExpert(cfg)
    p_shared = shared.init(jax.random.PRNGKey(9), x)["params"]
    with jax.default_matmul_precision("highest"):
        routed, own = FAMILY._routed_mlp(x, p, sizes, None)
        want = routed + FAMILY._swiglu(x, p_shared)
        once = shared.apply({"params": p_shared}, x)
        total = 0.0
        for rank in range(8):
            held = {**p, **{name: p[name][rank:rank + 1] for name in ("gate", "up", "down")}}
            y, chosen = make(rank, 1).apply({"params": held}, x)
            np.testing.assert_array_equal(np.sort(np.asarray(chosen)), np.sort(np.asarray(own)))
            # and the reference given the same share
            ref_share, _ = FAMILY._routed_mlp(
                x, held, {**sizes, "n_routed_experts": 1, "first_expert_held": rank}, None)
            np.testing.assert_allclose(y, ref_share, rtol=1e-4, atol=1e-5)
            total = total + y
    np.testing.assert_allclose(total + once, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(once).max()) > 0.01 and float(jnp.abs(routed).max()) > 0.01
    assert float(jnp.abs(total + 8 * once - want).max()) > 0.05


def test_the_bias_takes_no_gradient_is_not_decayed_and_follows_the_rule():
    """Three steps of TrainStep: the bias of every routed layer after a step
    is the bias before it plus rate * sign(mean load - load), the loads being
    what the model sows on the parameters the step started from; the
    optimizer keeps no moment for it and no decay shrinks it."""
    cfg = KananaConfig.tiny(num_held=4, dtype=jnp.float32, lr_warmup_steps=0)
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False,
                   weight_decay=0.5)
    state = ts.init(jax.random.PRNGKey(0))
    state["params"] = _with_bias(state["params"], scale=0.05)
    masked = jax.tree_util.tree_flatten_with_path(state["opt_state"])[0]
    assert not [p for p, _ in masked if any(getattr(k, "key", None) == SELECTION_BIAS for k in p)]
    idx, targets = _batch(cfg.vocab_size)
    biases = lambda params: {i: params["p_0"][f"h_{i}"]["moe"][SELECTION_BIAS]
                             for i in range(cfg.num_dense_layers, cfg.n_layer)}
    assert sorted(biases(state["params"])) == [1, 2]
    grads = jax.grad(lambda p: _loss(cfg, p, idx, targets))(state["params"])
    assert all(not np.asarray(b).any() for b in biases(grads).values())
    for _ in range(3):
        before = jax.tree.map(np.asarray, biases(state["params"]))
        sown = ts.model.apply({"params": state["params"]}, idx, mutable=["moe_router"])[1]
        state, m = ts.step(state, ts.shard_batch({"idx": idx, "targets": targets}))
        for i, b in biases(state["params"]).items():
            load, = sown["moe_router"]["p_0"][f"h_{i}"]["moe"]["rows"]
            load = np.asarray(load, np.float32)
            assert load.shape == (cfg.num_experts,) and load.sum() == idx.size * cfg.top_k
            want = before[i] + np.float32(SELECTION_BIAS_RATE) * np.sign(load.mean() - load)
            np.testing.assert_allclose(np.asarray(b), want, rtol=0, atol=1e-7)
        assert float(m["moe_bias_abs_max"]) == max(
            float(jnp.abs(b).max()) for b in biases(state["params"]).values())
        assert float(m["moe_router_load_max_over_mean"]) >= 1.0
    assert float(m["moe_held_share"]) > 0


def _lowered_tiny(cfg=None):
    ts = TrainStep(cfg or KananaConfig.tiny(num_held=4),
                   make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    return ts._step.lower(state, {"idx": tok, "targets": tok})


def test_the_stacked_sow_changes_no_program(monkeypatch):
    with_sow = _lowered_tiny().as_text()
    monkeypatch.setattr(kanana.KananaGroup, "sow", lambda self, *args, **kw: None)  # its one sow
    assert _lowered_tiny().as_text() == with_sow


def test_scopes_reach_the_ops_and_change_no_program(monkeypatch):
    with_scopes = _lowered_tiny()
    names = with_scopes.as_text(debug_info=True)
    for scope in ("mla.q", "mla.kv_a", "mla.kv_norm", "mla.kv_b", "mla.rope", "attn.core",
                  "mla.o", "moe.route", "moe.experts", "moe.combine", "moe.shared", "lm_head"):
        assert scope in names, scope
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    without = _lowered_tiny()
    assert "mla.rope" not in without.as_text(debug_info=True)
    assert with_scopes.as_text() == without.as_text()


def test_latent_attention_under_a_mesh_says_so():
    cfg = KananaConfig.tiny(attn_fn=lambda q, k, v: q)
    with pytest.raises(NotImplementedError, match="one device"):
        Kanana(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


class SlicedLatent(nn.Module):
    """The latent layer as PR 62 had it, written out: four `nn.Dense`, each
    writing its parts side by side a head, the parts sliced out of the (B, T,
    .) results and the 64 put pairs apart on the activation. What
    `layers.LatentAttention` cuts on the weights is held to this."""

    config: KananaConfig
    rotary: bool = True

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, C = x.shape
        H, nope, rope = cfg.n_head, cfg.nope_dim, cfg.rope_dim
        dense = lambda width, name: nn.Dense(width, use_bias=False, dtype=cfg.dtype, name=name)
        q = dense(H * (nope + rope), "q_proj")(x).reshape(B, T, H, nope + rope)
        q, q_pe = q[..., :nope], q[..., nope:]
        latent = dense(cfg.kv_latent + rope, "kv_a_proj")(x)
        latent, k_pe = latent[..., :cfg.kv_latent], latent[..., cfg.kv_latent:]
        latent = layers.RMSNorm(cfg.rms_eps, name="kv_a_norm")(latent)
        kv = dense(H * (nope + cfg.v_dim), "kv_b_proj")(latent).reshape(B, T, H, nope + cfg.v_dim)
        k, v = kv[..., :nope], kv[..., nope:]
        if self.rotary:
            angles = layers.rope_angles(rope, cfg.rope_theta, jnp.arange(T))
            q_pe = layers.apply_rope(layers.pairs_apart(q_pe), angles)
            k_pe = layers.apply_rope(layers.pairs_apart(k_pe)[:, :, None], angles)[:, :, 0]
        y = attention.latent_attention(q, q_pe, k, k_pe, v)
        return dense(C, "o_proj")(y.reshape(B, T, H * cfg.v_dim))


def _latent_pair(rotary, dtype=jnp.float32):
    cfg = KananaConfig.tiny(dtype=dtype)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, cfg.n_embd), dtype)
    return x, layers.LatentAttention(cfg, rotary=rotary), SlicedLatent(cfg, rotary=rotary)


@pytest.mark.parametrize("rotary", [True, False])
def test_the_latent_layer_s_tree_is_four_dense_s(rotary):
    """The leaves the layer cuts at use are `nn.Dense`'s: the same paths,
    shapes, dtypes and, at a key, values, so a seed gives the weights it
    gave and the reference reads the tree it read."""
    x, cut, sliced = _latent_pair(rotary)
    got, want = (m.init(jax.random.PRNGKey(1), x)["params"] for m in (cut, sliced))
    flat, want_flat = (dict(jax.tree_util.tree_flatten_with_path(t)[0]) for t in (got, want))
    assert list(flat) == list(want_flat) and len(flat) == 5
    assert {jax.tree_util.keystr(path): leaf.shape for path, leaf in flat.items()} == {
        "['kv_a_norm']['weight']": (32,), "['kv_a_proj']['kernel']": (64, 32 + 8),
        "['kv_b_proj']['kernel']": (32, 4 * (16 + 16)), "['o_proj']['kernel']": (4 * 16, 64),
        "['q_proj']['kernel']": (64, 4 * (16 + 8))}  # `KananaConfig.tiny`'s widths
    for path, leaf in flat.items():
        assert leaf.dtype == want_flat[path].dtype == jnp.float32
        np.testing.assert_array_equal(leaf, want_flat[path], err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rotary", [True, False])
def test_the_cut_on_the_weights_hands_the_pair_what_the_slices_did(rotary, dtype, monkeypatch):
    """q, q_pe, k, k_pe and v as `latent_attention` is handed them equal the
    sliced form's to the bit, in the layouts the calls take: an entry is the
    dot product it was, whichever matmul holds its column. The CPU sums a
    column in an order that depends on the matrix's width, so the stream,
    the weights and the normed latent are put on a grid of eighths, where
    every sum is exact in float32 and an entry can differ only by being
    another column's."""
    x, cut, sliced = _latent_pair(rotary, dtype)
    grid = lambda a, to: jnp.round(to * a) / to
    x = grid(x.astype(jnp.float32), 2).astype(dtype)
    params = sliced.init(jax.random.PRNGKey(1), x)["params"]
    params = jax.tree.map(lambda p: p if p.ndim == 1 else grid(
        jax.random.normal(jax.random.PRNGKey(p.size), p.shape) / 2, 8), params)
    norm = layers.rms_norm
    monkeypatch.setattr(layers, "rms_norm", lambda x, w, eps: grid(norm(x, w, eps), 8))
    handed = []
    real = attention.latent_attention
    monkeypatch.setattr(attention, "latent_attention",
                        lambda *ops: handed.append(ops) or real(*ops))
    want_y, got_y = (m.apply({"params": params}, x) for m in (sliced, cut))
    (want, got), cfg = handed, cut.config
    assert [op.shape for op in got] == [
        (2, 24, 4, cfg.nope_dim), (2, 24, 4, cfg.rope_dim), (2, 24, 4, cfg.nope_dim),
        (2, 24, cfg.rope_dim), (2, 24, 4, cfg.v_dim)]
    for name, g, w in zip(("q", "q_pe", "k", "k_pe", "v"), got, want):
        assert g.dtype == w.dtype == dtype and float(jnp.abs(w.astype(jnp.float32)).max()) > 1
        np.testing.assert_array_equal(g, w, err_msg=name)
    np.testing.assert_array_equal(got_y, want_y)


@pytest.mark.parametrize("rotary", [True, False])
def test_the_cut_layer_s_output_and_gradients_are_the_sliced_form_s(rotary):
    """The output, the input's gradient and every leaf's (the four matrices
    and the latent's norm) in float32: the backward sums two matmuls into dx
    and into the latent's gradient where it summed one, 1e-6 of each one's
    largest entry."""
    x, cut, sliced = _latent_pair(rotary)
    params = sliced.init(jax.random.PRNGKey(1), x)["params"]
    params = jax.tree.map(lambda p: p + 0.05 * jax.random.normal(
        jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else 2.0 * p, params)
    w = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    loss = lambda m: lambda p, x: (m.apply({"params": p}, x) * w).sum()
    with jax.default_matmul_precision("highest"):
        got, want = ((m.apply({"params": params}, x),
                      *jax.grad(loss(m), argnums=(0, 1))(params, x)) for m in (cut, sliced))
    flat, want_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (got, want))
    assert len(flat) == 1 + 5 + 1
    for path, g in flat.items():
        scale = float(jnp.abs(want_flat[path]).max())
        assert scale > 0, path
        np.testing.assert_allclose(g, want_flat[path], rtol=0, atol=1e-6 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_parameters_of_the_cell():
    """The count of ISSUE 43 and PERF.md section 4 by the program's own shapes."""
    sizes = _sizes(rehearse=False)
    cfg = FAMILY.build(sizes, "bfloat16")
    shapes = jax.eval_shape(lambda: Kanana(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    blocks = shapes["p_0"]
    attn = blocks["h_3"]["attn"]
    assert [count(attn[w]) for w in ("q_proj", "kv_a_proj", "kv_b_proj", "o_proj")] == [
        12_582_912, 1_179_648, 4_194_304, 8_388_608]
    assert count(attn) == 26_345_472 + 512 == cfg.attention_params() + 512
    assert count(blocks["h_0"]["mlp"]) == 3 * 2048 * 6144
    assert count(blocks["h_1"]["moe"]) == 262_144 + 128 + 16 * 4_718_592
    assert count(blocks["h_1"]["shared"]) == 3 * 2048 * 1536 == 9_437_184
    # with each block's two norms
    assert [count(blocks[f"h_{i}"]) for i in range(5)] == [64_098_816] + 4 * [111_547_008]
    assert count(shapes["tok_emb"]) == count(shapes["lm_head"]) == 16_032 * 2048
    assert count(shapes) == 575_955_968
    assert 16 * count(shapes) / GIB == pytest.approx(8.58, abs=0.01)
    # 6 x 255.26 M + 5 x 251.7 M at T = 8,192: 2.790 GFLOPs a token, 45% of it the core
    assert cfg.matmul_params() == FAMILY.matmul_params(sizes) == 255_262_720
    flops = cfg.flops_per_token(8192)
    assert flops == FAMILY.flops_per_token(sizes, 8192) == 6 * 255_262_720 + 5 * 251_658_240
    assert 5 * 251_658_240 / flops == pytest.approx(0.451, abs=0.001)


def test_remat_plan_of_the_cell():
    """At the cell's shape under the v5e's limit the rule's choice; with no
    limit the first rung alone; at four times the rows none of the further
    rungs fits. The block's working set is stated from the widths."""
    cfg = FAMILY.build(_sizes(rehearse=False), "bfloat16")
    shape = remat.StepShape(2, 8192)
    chosen = kanana.remat_plan(cfg, shape, V5E_LIMIT)
    first = remat.FIRST_RUNG + ("moe_plan",)  # the routed layers' choices and plans with it
    assert chosen.names[:3] == first
    # every rung of the family; the expert layer's products are none of them
    # (REMAT_RUNGS says why) and its layers are told so
    assert set(chosen.names[3:]) == {n for names, _ in kanana.REMAT_RUNGS for n in names}
    assert not set(chosen.names) & set(KEPT_PRODUCTS)
    assert chosen.reckoned_bytes <= chosen.limit_bytes == V5E_ROOM
    tokens = 2 * 8192
    assert chosen.block_bytes == tokens * (2 * 32 * (2 * 128 + 64 + 2 * 128) * 2
                                           + 6 * 4 * 2048 * 2) == tokens * 172_032
    # the latent pair's output and logsumexp in every layer; the choices and
    # the plan, five int32 and a bool an assignment, in the four routed layers
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kanana, "REMAT_RUNGS", ())
        pair = tokens * 32 * 128 * 2 + tokens * 32 * 4
        assert kanana.remat_plan(cfg, shape, V5E_LIMIT).layer_bytes == (
            (pair,) + (pair + tokens * 6 * 21,) * 4)
    # every rung whole: a depth is out of the layers that make the rung's names
    assert [(k, of) for _, k, of in chosen.depths] == [(5, 5), (4, 4), (1, 1)]
    assert kanana.remat_plan(cfg, shape, None).names == first
    assert kanana.remat_plan(cfg, remat.StepShape(8, 8192), V5E_LIMIT).names == first


def test_the_cell_s_step_runs_the_latent_pair_once_a_layer(monkeypatch):
    """The cell's own step lowered for a TPU on this box: five layers, each
    with flash_mla_fwd and flash_mla_bwd_fused once (the first rung saves the
    output and the logsumexp), no plain causal call, megablox's calls."""
    cfg, traced = _cell_step(monkeypatch)
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    calls = kernel_tally(text)
    assert calls.pop("kernel") and "@gmm" in text and "@tgmm" in text
    # and the expert layer's sums back to the tokens (PR 44): forward and
    # backward in four routed layers, each lowered for both buffers
    assert calls == {"flash_mla_fwd": 5, "flash_mla_bwd_fused": 5,
                     "moe_token_sum": 4 * 2 * 2}, calls
    # the plan keeps none of the expert layer's products and the layer takes
    # the form that names none: its backward makes them inside the branch it
    # takes, 6 `gmm` a buffer where the form that reads them has 3 and 6
    from tests.test_mellum import expert_calls

    assert not set(KEPT_PRODUCTS) & set(remat.traced(cfg).names)
    assert expert_calls(text, 4) == {"gmm": 18, "tgmm": 6, "moe_token_sum": 4}


# The lowered step of lfm2_8b_a1b_l5_ep4.t8192, the cell whose router this
# family shares, as tests/test_mellum.py:_step_text gives it. Pinned again in
# PR 44, which changed every routed cell's step by design (the expert layer's
# sums back to the tokens walk the buffer's rows, `ops/moe.py:sum_by_token`,
# and the layer sows the rows it walked), and in PR 45, by design too (the
# layer's products and plan are named residuals that its backward reads, and
# the rule keeps all of them here): a change to this family's own fields of
# `ExpertShare` leaves it as it is. PR 51 moved it by design: the flash calls cut their masked tiles into sub-tiles of 128 (`FlashTiles.sub_fwd`, `.sub_bwd`).
LFM2_STEP = "3a885a42dd011ada07fe943ab4be9f46a4747f238ed1fe7a2635db61b5946382"


def test_the_sigmoid_router_s_other_cell_lowers_to_the_parent_s_step(monkeypatch):
    from tests.test_mellum import _step_text

    for mod in (attention, short_conv):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat, "chip_limit", lambda stream: V5E_LIMIT)
    sizes = _sizes(rehearse=False, name="lfm2_8b_a1b_l5_ep4")
    cfg = families.load(sizes["family"]).build(sizes, "bfloat16")
    ts = TrainStep(cfg, make_mesh(sizes["mesh"], devices=jax.devices()[:1]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 8192), jnp.int32)
    text = _step_text(ts, state, {"idx": tok, "targets": tok})
    assert "conv_y" in remat.traced(cfg).names
    assert hashlib.sha256(text.encode()).hexdigest() == LFM2_STEP


# This family's own cell (B=2 x T=8192, one chip, a v5e's limit for the remat
# rule), as tests/test_mellum.py:_step_text gives it, taken on PR 46's parent's
# tree before `TrainStep` stopped knowing its families by name; PR 51 moved it by design: the flash calls cut their masked tiles into sub-tiles of 128 (`FlashTiles.sub_fwd`, `.sub_bwd`),
# the latent pair's among them; PR 63 moved it by design: the latent layer cuts its
# projections on their weights (models/layers.py:DenseParts), so each matmul writes what
# the latent pair reads.
KANANA_STEP = "170bafe97d95fb1c34c43092ca728cd6640b9a8b54c0261869aa7a1a14ae07b9"


def _cell_step(monkeypatch):
    """(cfg, the cell's step traced for a TPU on this box under a v5e's limit)."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat, "chip_limit", lambda stream: V5E_LIMIT)
    cfg = FAMILY.build(_sizes(rehearse=False), "bfloat16")
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 8192), jnp.int32)
    return cfg, ts._step.trace(state, {"idx": tok, "targets": tok})


def test_the_cell_lowers_to_its_pinned_step(monkeypatch):
    from tests.test_mellum import _traced_text

    cfg, traced = _cell_step(monkeypatch)
    assert not set(KEPT_PRODUCTS) & set(remat.traced(cfg).names)
    assert hashlib.sha256(_traced_text(traced).encode()).hexdigest() == KANANA_STEP


def test_the_cell_s_latent_layers_cut_their_projections_on_the_weights(monkeypatch):
    from tests.test_kimi_linear import latent_layers_are_cut_on_their_weights
    from tests.test_mellum import _traced_text

    _, traced = _cell_step(monkeypatch)
    # the first block's dense MLP is 6,144 wide too
    latent_layers_are_cut_on_their_weights(traced, _traced_text(traced), layers=5,
                                           elsewhere=((2, 8192, 6144),))


def test_step_reports_the_router_s_two_gauges_through_the_telemetry():
    cfg = KananaConfig.tiny(num_held=4)
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    try:
        state = ts.init(jax.random.PRNGKey(0))
        idx, targets = _batch(cfg.vocab_size)
        state, m = ts.step(state, ts.shard_batch({"idx": idx, "targets": targets}))
        jax.block_until_ready(m)
        assert float(m["moe_bias_abs_max"]) == pytest.approx(SELECTION_BIAS_RATE)
        assert 1.0 <= float(m["moe_router_load_max_over_mean"]) < cfg.num_experts
        report = _telemetry.auto_report_metrics()
        for gauge in ("moe_bias_abs_max", "moe_router_load_max_over_mean", "moe_rows_held",
                      "moe_held_share", "moe_load_max_over_mean"):
            assert report[f"telemetry/{gauge}"] == float(m[gauge]), gauge
        plan = ts.telemetry.remat_plan
        assert plan.names == remat.FIRST_RUNG + ("moe_plan",) and plan.limit_bytes is None  # no chip
        assert plan.block_bytes > 0
    finally:
        _telemetry.set_current_recorder(None)


def test_system_in_bf16_stays_near_the_reference(seeded):
    """bf16 operands, float32 sums: the loss within 2e-3 at this size with
    the choices the bf16 system made held."""
    sizes, params, idx, targets, _, _, _ = seeded
    cfg = FAMILY.build(sizes, "bfloat16")
    logits, sown = Kanana(cfg).apply({"params": params}, idx, mutable=["choices"])
    held, = jax.tree.leaves(sown["choices"]["p_0"])
    with jax.default_matmul_precision("highest"):
        ref = families.reference_loss(FAMILY, params, idx, targets, sizes, {"p_0": held})
    assert abs(float(loss_fn(logits, targets)) - float(ref)) <= 2e-3 * float(ref)
