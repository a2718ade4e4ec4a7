"""models/kimi_linear.py against the plain reference of
bench/families/kimi_linear.py on seeded weights (loss and every gradient, the
choices held and judged), what the comparison catches when a part of the KDA
mixer is dropped, the latent mixer with and without rotary, the shares of the
experts with the shared expert counted once against the uncut layer, the
cell's parameters, the remat rule's plan, the cell's lowered step (its
kernels tallied, its hash pinned) and the gauges through the telemetry."""

import contextlib
import copy
import dataclasses
import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families
from ray_tpu.models import kanana, kimi_linear, layers, remat
from ray_tpu.models.kimi_linear import KDA, MLA, KimiLinear, KimiLinearConfig
from ray_tpu.models.loss import loss_fn
from ray_tpu.ops import attention, kda, kda_norm, short_conv
from ray_tpu.ops.moe import KEPT_PRODUCTS, SELECTION_BIAS, SIGMOID, ExpertShare
from ray_tpu.parallel.mesh import kernel_tally, make_mesh
from ray_tpu.parallel.train_step import TrainStep
from ray_tpu.train import _telemetry
from tests.test_lfm2 import _batch, _with_bias  # the sigmoid router's first family
from tests._tpu_compile import V5E_LIMIT, V5E_ROOM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = families.load("kimi_linear")
GIB = remat.GIB
CELL = "kimi_linear_l5_ep32"


def _sizes(rehearse=True, **changed):
    with open(os.path.join(ROOT, "bench", "configs", f"{CELL}.json")) as f:
        sizes = json.load(f)
    if rehearse:
        sizes.update(sizes["rehearsal"])
    sizes.update(changed)
    return sizes


def _loss(cfg, params, idx, targets):
    return loss_fn(KimiLinear(cfg).apply({"params": params}, idx), targets)


@pytest.fixture(scope="module")
def seeded():
    sizes = _sizes()
    cfg = FAMILY.build(sizes, "float32")
    assert cfg.layer_types == (KDA, KDA, KDA, MLA, KDA) and cfg.kda_chunk == 64
    idx, targets = _batch(sizes["vocab_size"], t=96)  # a chunk and a half
    params = KimiLinear(cfg).init(jax.random.PRNGKey(1), idx)["params"]
    # the norms' weights, A_log and dt_bias off their initial values, so that
    # each one's gradient is a test of its own; the selection bias off zero
    params = jax.tree.map(lambda p: p + 0.05 * jax.random.normal(
        jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else p, params)
    params = _with_bias(params)
    choices = KimiLinear(cfg).apply({"params": params}, idx, mutable=["choices"])[1]["choices"]
    held, = jax.tree.leaves(choices["p_0"])  # one entry for the whole group
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.value_and_grad(lambda p: families.reference_loss(
            FAMILY, p, idx, targets, sizes, {"p_0": held}))(params)
    return sizes, params, idx, targets, held, ref_loss, ref_grads


def test_system_agrees_with_the_reference_in_float32(seeded):
    """Loss and every gradient with the choices held. Both sides are float32;
    what differs is the algebra (the chunked form with its solve against the
    recurrence step by step, the latent scores in two parts against one, the
    grouped matmul's rows against every token through every expert): 1e-5 of
    the loss, 3e-4 of each gradient's largest entry."""
    sizes, params, idx, targets, held, ref_loss, ref_grads = seeded
    cfg = FAMILY.build(sizes, "float32")
    assert sorted(params) == ["final_norm", "lm_head", "p_0", "tok_emb"] == sorted(
        FAMILY.layer_names(sizes) + ["final_norm", "lm_head", "tok_emb"])
    assert held.shape == (4, 2, 96, 2)  # four routed blocks' choices, stacked
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: _loss(cfg, p, idx, targets))(params)
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * float(ref_loss)
    flat, ref_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, ref_grads))
    # a KDA mixer's eleven leaves, the latent one's five, a block's two norms;
    # the dense MLP 3; a routed one 5 with its bias and the shared expert's 3;
    # embedding, final norm and head
    assert len(flat) == len(ref_flat) == 4 * 11 + 5 + 5 * 2 + 3 + 4 * 8 + 3
    for path, g in flat.items():
        if path[-1].key == SELECTION_BIAS:
            assert not np.asarray(g).any() and not np.asarray(ref_flat[path]).any()
            continue
        scale = float(jnp.abs(ref_flat[path]).max())
        assert scale > 0, path
        np.testing.assert_allclose(g, ref_flat[path], rtol=0, atol=3e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_reference_s_own_choices_are_the_system_s(seeded):
    sizes, params, idx, _, held, _, _ = seeded
    with jax.default_matmul_precision("highest"):
        own = FAMILY.choice(FAMILY.embed({"tok_emb": params["tok_emb"]}, idx, sizes),
                            params["p_0"], sizes)
    assert own.shape == held.shape
    share = (held[..., :, None] == own[..., None, :]).any(-1).mean((1, 2, 3))
    assert (np.asarray(share) > 0.99).all(), share


def test_the_head_norm_s_pair_in_the_model_is_the_plain_lines(seeded, monkeypatch):
    """At two heads of 128 (a width the pair takes; the rehearsal's is 16) the
    model with `kda_norm_fwd` / `kda_norm_bwd` forced in interpret mode
    against the model on the plain lines, float32: the loss and every
    gradient within the tolerances the reference is held to above, the gate's
    projections, W_o and `o_norm/weight` among them."""
    sizes, _, idx, targets, _, _, _ = seeded
    sizes = copy.deepcopy(sizes)
    sizes["linear_attn_config"].update(head_dim=128, num_heads=2)
    cfg = FAMILY.build(sizes, "float32")
    assert (cfg.kda_heads, cfg.kda_head_dim) == (2, 128)
    params = KimiLinear(cfg).init(jax.random.PRNGKey(2), idx)["params"]
    params = jax.tree.map(lambda p: p + 0.05 * jax.random.normal(
        jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else p, params)
    assert params["p_0"]["h_0"]["kda"]["o_norm"]["weight"].shape == (128,)
    value_and_grads = lambda: jax.value_and_grad(lambda p: _loss(cfg, p, idx, targets))(params)
    with jax.default_matmul_precision("highest"):
        plain_loss, plain = value_and_grads()
        calls, real = [], kda_norm._kda_norm
        monkeypatch.setattr(kda_norm, "_kda_norm", lambda *a: (calls.append(a[0].shape), real(*a))[1])
        monkeypatch.setattr(kda_norm, "kda_norm", functools.partial(kda_norm.kda_norm,
                                                                    interpret=True))
        loss, grads = value_and_grads()
    assert calls and set(calls) == {(2, 96, 256)}  # the four KDA layers took the pair
    assert abs(float(loss) - float(plain_loss)) <= 1e-5 * float(plain_loss)
    flat, plain_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, plain))
    for path, g in flat.items():
        scale = float(jnp.abs(plain_flat[path]).max())
        np.testing.assert_allclose(g, plain_flat[path], rtol=0, atol=3e-4 * scale + 1e-30,
                                   err_msg=jax.tree_util.keystr(path))


def _changed_kda(change):
    """ops/kda.py's `kda` on operands changed first."""
    real = kda.kda
    return lambda q, k, v, g, beta, chunk: real(*change(q, k, v, g, beta), chunk)


DROPPED = {
    # one decay a head in place of one a channel
    "decay_by_head": lambda q, k, v, g, beta: (
        q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta),
    # no decay at all
    "no_decay": lambda q, k, v, g, beta: (q, k, v, jnp.zeros_like(g), beta),
    # beta taken as one
    "beta_one": lambda q, k, v, g, beta: (q, k, v, g, jnp.ones_like(beta)),
    # q not normed back (twice its length)
    "q_scale": lambda q, k, v, g, beta: (2 * q, k, v, g, beta),
}


@pytest.mark.parametrize("what", sorted(DROPPED) + ["k_norm", "carry", "solve", "conv",
                                                    "latent_rotary", "shared_expert", "scaling"])
def test_the_comparison_catches_what_is_dropped(seeded, what, monkeypatch):
    """A program whose delta rule decays by head or not at all, takes beta as
    one, takes q at twice its normed length (the operands `kda.kda` is handed
    are normed: off the TPU `kda_gated` norms before it) or k as the
    convolution left it, drops the carried state at a chunk's edge or the
    solve's off-diagonal, whose convolution is left out, whose
    latent layer turns its 64, or which leaves out the shared expert or the
    routed scaling is outside the loss's tolerance of the test above."""
    sizes, params, idx, targets, held, ref_loss, _ = seeded
    cfg = FAMILY.build(sizes, "float32")
    if what in DROPPED:
        monkeypatch.setattr(kda, "kda", _changed_kda(DROPPED[what]))
    elif what == "k_norm":  # q normed, k handed on as the convolution left it
        real = kda.kda_gated
        monkeypatch.setattr(kda, "kda_gated", lambda q, k, *rest, l2_eps: real(
            kda.l2norm(q, l2_eps), k, *rest))
    elif what == "carry":
        cfg = dataclasses.replace(cfg, kda_chunk=32)
        real = kda._chunk_fwd
        monkeypatch.setattr(kda, "_chunk_fwd", lambda q, k, v, G, beta, St, *a, **kw: real(
            q, k, v, G, beta, jnp.zeros_like(St), *a, **kw))
    elif what == "solve":  # the rows of a chunk taken as independent of one another
        monkeypatch.setattr(kda, "_inverse", lambda M, exact: jnp.eye(M.shape[0]))
    elif what == "conv":
        monkeypatch.setattr(kimi_linear, "causal_conv_within", lambda wide, w, bias, at, cuts: (
            None, *jnp.split(jax.nn.silu(wide), cuts, axis=-1), None))
    elif what == "latent_rotary":
        monkeypatch.setattr(kimi_linear, "LatentAttention",
                            lambda cfg, rotary, name: layers.LatentAttention(cfg, name=name))
    elif what == "shared_expert":
        params = jax.tree_util.tree_map_with_path(
            lambda path, p: jnp.zeros_like(p) if "shared" in jax.tree_util.keystr(path)
            and "down" in jax.tree_util.keystr(path) else p, params)
    else:
        cfg = dataclasses.replace(cfg, routed_scaling=1.0)
    with jax.default_matmul_precision("highest"):
        loss = _loss(cfg, params, idx, targets)
    assert abs(float(loss) - float(ref_loss)) > 1e-4 * float(ref_loss), what


@pytest.mark.parametrize("rotary", [True, False])
def test_the_latent_mixer_with_and_without_rotary(rotary):
    """`LatentAttention(rotary=False)` is the expanded form on q_pe and k_pe
    as they are, and the default turns them: each against the published form
    written out, and the two apart."""
    cfg = kanana.KananaConfig.tiny(dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, cfg.n_embd))
    layer = layers.LatentAttention(cfg, rotary=rotary)
    p = layer.init(jax.random.PRNGKey(1), x)["params"]
    assert sorted(p) == sorted(layers.LatentAttention(cfg).init(jax.random.PRNGKey(1), x)["params"])
    sizes = {"num_attention_heads": cfg.n_head, "qk_nope_head_dim": cfg.nope_dim,
             "qk_rope_head_dim": cfg.rope_dim, "kv_lora_rank": cfg.kv_latent,
             "v_head_dim": cfg.v_dim, "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta}
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": p}, x)
        plain = FAMILY._latent(x, p, sizes)
        turned = families.load("kanana")._attention(x, p, sizes)
    want, other = (turned, plain) if rotary else (plain, turned)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(got - other).max()) > 1e-2


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The shares of the experts (four here, thirty-two in the cell's
    deployment), each computed by the program as a chip's share of the layer
    (the router whole on every chip), summed, plus the shared expert counted
    once (every chip computes it alike): the uncut MLP of the reference, all
    experts held. Counted a chip it would be four times too much, which the
    last line sees."""
    sizes = _sizes(num_experts=8, first_expert_held=0)
    cfg = FAMILY.build(sizes, "float32")
    d, ff, k = sizes["hidden_size"], sizes["moe_intermediate_size"], sizes["num_experts_per_token"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, d))
    make = lambda first, held: ExpertShare(
        d, ff, 8, k, first, held, jnp.float32, router=SIGMOID, hand_up_choices=True,
        scaling=cfg.routed_scaling, gate_eps=cfg.gate_eps,
        headroom=kimi_linear.EXPERT_HEADROOM)
    p = make(0, None).init(jax.random.PRNGKey(7), x)["params"]
    p[SELECTION_BIAS] = 0.3 * jax.random.normal(jax.random.PRNGKey(8), (8,))
    shared = layers.SharedExpert(cfg)
    p_shared = shared.init(jax.random.PRNGKey(9), x)["params"]
    with jax.default_matmul_precision("highest"):
        routed, own = FAMILY._routed_mlp(x, p, sizes, None)
        want = routed + FAMILY._swiglu(x, p_shared)
        once = shared.apply({"params": p_shared}, x)
        total = 0.0
        for rank in range(4):
            at = slice(2 * rank, 2 * rank + 2)
            held = {**p, **{name: p[name][at] for name in ("gate", "up", "down")}}
            y, chosen = make(2 * rank, 2).apply({"params": held}, x)
            np.testing.assert_array_equal(np.sort(np.asarray(chosen)), np.sort(np.asarray(own)))
            # and the reference given the same share
            ref_share, _ = FAMILY._routed_mlp(
                x, held, {**sizes, "num_experts": 2, "first_expert_held": 2 * rank}, None)
            np.testing.assert_allclose(y, ref_share, rtol=1e-4, atol=1e-5)
            total = total + y
    np.testing.assert_allclose(total + once, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(once).max()) > 0.01 and float(jnp.abs(routed).max()) > 0.01
    assert float(jnp.abs(total + 4 * once - want).max()) > 0.05


def _lowered_tiny(cfg=None):
    ts = TrainStep(cfg or KimiLinearConfig.tiny(num_held=4),
                   make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    return ts._step.lower(state, {"idx": tok, "targets": tok})


def test_scopes_reach_the_ops_and_change_no_program(monkeypatch):
    with_scopes = _lowered_tiny()
    names = with_scopes.as_text(debug_info=True)
    for scope in ("kda.in_proj", "kda.conv", "kda.gate", "kda.scan", "kda.norm", "kda.out_proj",
                  "mla.q", "mla.kv_a", "mla.kv_norm", "mla.kv_b", "attn.core", "mla.o",
                  "moe.route", "moe.experts", "moe.combine", "moe.shared", "lm_head"):
        assert scope in names, scope
    assert "mla.rope" not in names  # nothing turns the 64
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    without = _lowered_tiny()
    assert "kda.scan" not in without.as_text(debug_info=True)
    assert with_scopes.as_text() == without.as_text()


def test_either_mixer_under_a_mesh_says_so():
    for kinds in ((KDA,), (MLA,)):
        cfg = KimiLinearConfig.tiny(layer_types=kinds, attn_fn=lambda q, k, v: q)
        with pytest.raises(NotImplementedError, match="one device"):
            KimiLinear(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_the_published_lists_give_the_kinds():
    lists = _sizes(rehearse=False)["linear_attn_config"]
    kinds = kimi_linear.layer_kinds(lists["kda_layers"], lists["full_attn_layers"], range(1, 28))
    assert kinds == KimiLinearConfig().layer_types and len(kinds) == 27
    assert kinds.count(KDA) == 20 and kinds.count(MLA) == 7
    assert [n + 1 for n, kind in enumerate(kinds) if kind == MLA] == lists["full_attn_layers"]
    with pytest.raises(ValueError, match="both or neither"):
        kimi_linear.layer_kinds(lists["kda_layers"], lists["full_attn_layers"], [0])


def test_parameters_of_the_cell():
    """The count of ISSUE 54 and PERF.md section 4 by the program's own shapes."""
    sizes = _sizes(rehearse=False)
    cfg = FAMILY.build(sizes, "bfloat16")
    shapes = jax.eval_shape(lambda: KimiLinear(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    blocks = shapes["p_0"]
    mixer = blocks["h_0"]["kda"]
    assert [count(mixer[w]) for w in ("qkv_proj", "o_proj", "f_a_proj", "f_b_proj", "b_proj")] == [
        3 * 2304 * 4096, 4096 * 2304, 2304 * 128, 128 * 4096, 2304 * 32]
    # and the filters, dt_bias, A_log and the head norm's weight
    assert count(mixer) == cfg.kda_params() + 4 * 3 * 4096 + 4096 + 32 + 128 == 39_514_272
    assert mixer["o_norm"]["weight"].shape == (128,) and list(mixer["o_norm"]) == ["weight"]
    assert count(blocks["h_3"]["attn"]) == cfg.latent_params() + 512 == 29_114_880
    assert count(blocks["h_0"]["mlp"]) == 3 * 2304 * 9216
    assert count(blocks["h_1"]["moe"]) == 2304 * 256 + 256 + 8 * 3 * 2304 * 1024
    assert count(blocks["h_1"]["shared"]) == 3 * 2304 * 1024
    # with each block's two norms
    assert [count(blocks[f"h_{i}"]) for i in range(5)] == [
        103_219_872, 103_809_952, 103_809_952, 93_410_560, 103_809_952]
    assert count(shapes["tok_emb"]) == count(shapes["lm_head"]) == 20_480 * 2304
    assert count(shapes) == 602_434_432
    assert 16 * count(shapes) / GIB == pytest.approx(8.98, abs=0.01)
    # 2.303 GFLOPs a token at T = 8,192: the KDA mixers 43% of it (their
    # delta rule by the recurrence: 18 x 32 x 128^2 a layer), the latent
    # layer 18.5%, of which its scores 11%
    assert cfg.matmul_params() == FAMILY.matmul_params(sizes) == 335_593_472
    flops = cfg.flops_per_token(8192)
    assert flops == FAMILY.flops_per_token(sizes, 8192) == (
        6 * 335_593_472 + 3 * 8192 * 32 * 320 + 4 * 18 * 32 * 128 * 128) == 2_302_967_808
    kda_flops = 4 * (6 * cfg.kda_params() + 18 * 32 * 128 * 128)
    assert kda_flops / flops == pytest.approx(0.428, abs=1e-3)
    assert 3 * 8192 * 32 * 320 / flops == pytest.approx(0.109, abs=1e-3)


def _cell_step(monkeypatch):
    """(cfg, the cell's step traced for a TPU on this box under a v5e's limit)."""
    for mod in (attention, short_conv):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    for mod in (kda, kda_norm):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat, "chip_limit", lambda stream: V5E_LIMIT)
    cfg = FAMILY.build(_sizes(rehearse=False), "bfloat16")
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 8192), jnp.int32)
    return cfg, ts._step.trace(state, {"idx": tok, "targets": tok})


def _results_under(jaxpr, scope, keep, outer=""):
    """What the equations of a jaxpr under the named scope `scope`, those of
    the jaxprs inside them too (a remat's, a custom rule's, a kernel's body),
    give that `keep(aval)` holds of: (primitive, shape, name stack)."""
    found = []
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        if scope in stack:
            found += [(eqn.primitive.name, v.aval.shape, stack) for v in eqn.outvars
                      if keep(v.aval)]
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found += _results_under(inner, scope, keep, stack)
    return found


def _float32_under(jaxpr, scope, size):
    """Those in float32 with `size` entries or more."""
    return _results_under(jaxpr, scope, lambda aval: getattr(aval, "dtype", None) == jnp.float32
                          and aval.size >= size)


# What a latent layer's projections wrote at the cells' shape while each
# wrote its parts side by side a head, q's 32 x 192 and k | v's 32 x 256, and
# what the backward padded and summed back into them.
FUSED_BY_A_LATENT_LAYER = ((2, 8192, 32, 192), (2, 8192, 6144), (2, 8192, 32, 256),
                           (2, 8192, 8192))


def latent_layers_are_cut_on_their_weights(traced, text, layers, elsewhere=()):
    """Holds a cell's traced step, and its text (`_traced_text`: lowered and
    as a jaxpr), to `layers.LatentAttention`'s cut (PR 63): under the layer's
    scopes no equation gives an array of a fused projection's shape, and the
    whole text names none in either notation (but those in `elsewhere`,
    which another layer of the cell makes); the forward's matmuls under
    `q_proj` write H x 128 and H x 64, those under `kv_b_proj` H x 128
    twice, each the array the latent call reads."""
    jaxpr = traced.jaxpr.jaxpr
    for shape in FUSED_BY_A_LATENT_LAYER:
        for scope in ("mla.", "attn.core"):
            assert not _results_under(
                jaxpr, scope, lambda aval: getattr(aval, "shape", None) == shape), (scope, shape)
        if shape not in elsewhere:
            assert "x".join(map(str, shape)) + "x" not in text, shape
            assert f"[{','.join(map(str, shape))}]" not in text, shape
    widths = lambda scope: sorted(
        shape[-1] for op, shape, stack in _results_under(jaxpr, scope, lambda aval: True)
        if op == "dot_general" and "transpose(" not in stack)
    assert widths("mla.q/q_proj") == layers * [2048] + layers * [4096]
    assert widths("mla.kv_b/kv_b_proj") == 2 * layers * [4096]
    assert widths("mla.kv_a/kv_a_proj") == layers * [64] + layers * [512]


def test_the_cell_s_latent_layer_cuts_its_projections_on_the_weights(monkeypatch):
    from tests.test_mellum import _traced_text

    _, traced = _cell_step(monkeypatch)
    latent_layers_are_cut_on_their_weights(traced, _traced_text(traced), layers=1)


# This family's own cell (B=2 x T=8192, one chip, a v5e's limit for the remat
# rule), as tests/test_mellum.py:_step_text gives it, taken on PR 55's own tree
# (the l2 norms of q and k inside kda_fwd and kda_bwd): the program the chip
# runs of PERF.md section 6 were made with; PR 60's since (the head norm and
# its gate a kernel pair on o as kda_fwd wrote it); PR 62's since, by design: the remat rule
# takes a rung by depth (models/remat.py), and the last three KDA layers of four save the
# delta rule's outputs, which no layer saved before: kda_fwd is called five times, not eight;
# PR 63's since, by design: the latent layer cuts its projections on their weights
# (models/layers.py:DenseParts), so each matmul writes what the latent pair reads;
# PR 65's since, by design: the rule is held to the chip's own limit to within 64 MiB
# (15.6875 GiB, not 15), the first KDA layer saves the delta rule's outputs too and
# kda_fwd is called four times, once a layer.
KIMI_LINEAR_STEP = "8ac39a8af90e0ffb9d9f25e39d8339bc3d4490643f510f70a4497031228eef89"


def test_the_cell_s_step_tallies_its_kernels_and_lowers_to_its_pinned_step(monkeypatch):
    """The cell's own step lowered for a TPU on this box: four KDA layers,
    each with kda_bwd once and kda_fwd as often as the remat plan runs it
    (once where a layer holds `kda_out` and `kda_states`, all four at this
    shape under a v5e's limit since PR 65, twice where not), the
    head norm's pair after it (forward twice: no plan holds its output), the
    convolution pair a KDA layer, the latent pair once in the one latent
    layer, megablox's calls in four routed layers. Between the convolution
    and the delta rule nothing is float32 at q's size, forward, second
    forward or backward: the heads' l2 norms are the kernels' (PR 55; the
    parent's step held 104 such results under `kda.conv`, 26 a layer), and
    since PR 60 nothing between the delta rule and W_o either: the head norm
    and its gate are `kda_norm_fwd` / `kda_norm_bwd` on o as `kda_fwd` wrote
    it, and no (B, T, H, 128) view of o, y or their cotangents is an equation
    under `kda.norm` (tests/test_tpu_compile_kimi_linear.py holds the compiled
    step's `kda.scan` to the same)."""
    from tests.test_mellum import _traced_text

    cfg, traced = _cell_step(monkeypatch)
    text = _traced_text(traced)
    calls = kernel_tally(text)
    assert calls.pop("kernel") and "@gmm" in text and "@tgmm" in text
    plan = remat.traced(cfg)
    assert plan.depth("kda_states") == 4
    assert calls == {"kda_fwd": 4 + (4 - 4), "kda_bwd": 4, "kda_norm_fwd": 4 * 2, "kda_norm_bwd": 4,
                     "causal_conv_fwd": 4 * 2,
                     "causal_conv_bwd": 4, "flash_mla_fwd": 1, "flash_mla_bwd_fused": 1,
                     "moe_token_sum": 4 * 2 * 2}, calls
    assert not set(KEPT_PRODUCTS) & set(plan.names)
    q_size = 2 * 8192 * cfg.kda_inner
    assert not _float32_under(traced.jaxpr.jaxpr, "kda.conv", q_size)
    assert not _float32_under(traced.jaxpr.jaxpr, "kda.norm", q_size)
    assert not _results_under(traced.jaxpr.jaxpr, "kda.norm",
                              lambda aval: getattr(aval, "shape", None) == (2, 8192, 32, 128))
    assert hashlib.sha256(text.encode()).hexdigest() == KIMI_LINEAR_STEP


def test_remat_plan_of_the_cell():
    """At the cell's shape under the v5e's limit the rule's choice; with no
    limit the first rung alone."""
    cfg = FAMILY.build(_sizes(rehearse=False), "bfloat16")
    shape = remat.StepShape(2, 8192)
    chosen = kimi_linear.remat_plan(cfg, shape, V5E_LIMIT)
    first = remat.FIRST_RUNG + ("moe_plan",)  # the routed layers' choices and plans with it
    # beside 8.98 GiB of state the delta rule's outputs (2.5 GiB over four layers) have room in
    # all four KDA layers (in none until PR 62, when a rung was every layer's or none's; in the
    # last three until PR 65, under a limit of 15 GiB where the chip says 15.75: 13.57 of 13.5)
    assert chosen.names == first + ("kda_out", "kda_states")
    assert not set(chosen.names) & set(KEPT_PRODUCTS)
    assert chosen.depths == ((("kda_out", "kda_states"), 4, 4),)
    assert cfg.layer_types == ("kda", "kda", "kda", "mla", "kda")
    assert chosen.saved_in("kda_states") == (True,) * 5  # the fourth makes none
    assert chosen.reckoned_bytes <= chosen.limit_bytes == V5E_ROOM
    # with the first rung alone 12.18, of which the chip's allocator read 12.044 GiB (my chip
    # run, PR 60, call 2; 12.436 before)
    assert chosen.reckoned_bytes / GIB == pytest.approx(13.57, abs=0.01)
    tight = kimi_linear.remat_plan(cfg, shape, 15 * GIB)
    assert tight.depth("kda_out") == 3
    assert tight.reckoned_bytes / GIB == pytest.approx(12.94, abs=0.01)
    assert tight.saved_in("kda_states") == (False, True, True, True, True)
    assert kimi_linear.remat_plan(cfg, shape, None).reckoned_bytes / GIB == pytest.approx(
        12.18, abs=0.01)
    tokens = 2 * 8192
    # eight bf16 arrays 4,096 wide with their gradients and the chunk states; the two float32
    # ones of the head norm and its gate went with PR 60 (196,608 before)
    assert chosen.block_bytes == tokens * (2 * 8 * 2 * 4096 + 4 * 4096 * 128 // 64) \
        == tokens * 163_840
    # the first rung: the latent layer's output and logsumexp, and in the four routed layers
    # the choices and the plan: five int32 and a bool an assignment; the rung: a KDA layer's
    # output and its chunk states (a float32 (128, 4096) a chunk of 64)
    routed = tokens * 8 * 21
    kda_layer = tokens * 4096 * 2 + 2 * 128 * 4096 * 128 * 4
    assert chosen.layer_bytes == (kda_layer, routed + kda_layer, routed + kda_layer,
                                  routed + tokens * 32 * 128 * 2 + tokens * 32 * 4,
                                  routed + kda_layer)
    roomy = kimi_linear.remat_plan(cfg, remat.StepShape(1, 4096), V5E_LIMIT)
    assert roomy.names == chosen.names and roomy.depth("kda_out") == 4  # as any shape with the room
    assert kimi_linear.remat_plan(cfg, shape, None).names == first
    assert kimi_linear.remat_plan(cfg, remat.StepShape(8, 8192), V5E_LIMIT).names == first


def test_step_reports_the_kda_gauges_through_the_telemetry():
    cfg = KimiLinearConfig.tiny(num_held=4)
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    try:
        state = ts.init(jax.random.PRNGKey(0))
        idx, targets = _batch(cfg.vocab_size)
        state, m = ts.step(state, ts.shard_batch({"idx": idx, "targets": targets}))
        jax.block_until_ready(m)
        assert 0.5 < float(m["kda_decay_mean"]) < 1.0 and 0.3 < float(m["kda_beta_mean"]) < 0.7
        assert float(m["kda_state_rms"]) > 0 and float(m["kda_path_pallas"]) == 0.0  # no TPU here
        assert float(m["kda_norm_path_pallas"]) == 0.0
        report = _telemetry.auto_report_metrics()
        for gauge in ("kda_decay_mean", "kda_beta_mean", "kda_state_rms", "kda_path_pallas",
                      "kda_norm_path_pallas", "moe_bias_abs_max", "moe_rows_held", "moe_held_share"):
            assert report[f"telemetry/{gauge}"] == float(m[gauge]), gauge
        plan = ts.telemetry.remat_plan
        assert plan.names == remat.FIRST_RUNG + ("moe_plan",) and plan.limit_bytes is None  # no chip
    finally:
        _telemetry.set_current_recorder(None)


def test_system_in_bf16_stays_near_the_reference(seeded):
    """bf16 operands, float32 sums: the loss within 2e-3 at this size with
    the choices the bf16 system made held."""
    sizes, params, idx, targets, _, _, _ = seeded
    cfg = FAMILY.build(sizes, "bfloat16")
    logits, sown = KimiLinear(cfg).apply({"params": params}, idx, mutable=["choices"])
    held, = jax.tree.leaves(sown["choices"]["p_0"])
    with jax.default_matmul_precision("highest"):
        ref = families.reference_loss(FAMILY, params, idx, targets, sizes, {"p_0": held})
    assert abs(float(loss_fn(logits, targets)) - float(ref)) <= 2e-3 * float(ref)
