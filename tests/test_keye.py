"""The indexed_attention layer of models/mellum.py (Keye-VL 2.0's language
model) on the CPU at tiny sizes, seeded weights: against the plain reference
of bench/families/keye.py in float32 (loss, every gradient, the selected key
sets), in bf16 (the experts' and the keys' agreement); the eight shares of a
layer against the uncut reference; the indexer's zero gradient; what the
cell's own step holds once a layer, and what its remat keeps.
"""

import collections
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families
from ray_tpu.models import mellum, remat
from ray_tpu.models.mellum import INDEXED, FULL, Indexer, Mellum, MellumBlock, MellumConfig, loss_fn
from ray_tpu.ops import attention, indexer
from ray_tpu.parallel.mesh import make_mesh
from ray_tpu.parallel.train_step import TrainStep
from ray_tpu.train import _telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = families.load("keye")
GIB = 1 << 30


def _sizes(rehearse=True, **changed):
    with open(os.path.join(ROOT, "bench", "configs", "keye_vl2_30b_l4_ep8.json")) as f:
        sizes = json.load(f)
    if rehearse:
        sizes.update(sizes["rehearsal"])
    sizes.update(changed)
    return sizes


def _batch(sizes, rows=2, t=128, seed=0):
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, sizes["vocab_size"], (rows, t + 1)), jnp.int32)
    return tokens[:, :-1], tokens[:, 1:]


def _flat(tree):
    return jnp.concatenate([x.reshape(-1).astype(jnp.float32) for x in jax.tree.leaves(tree)])


def _system_keys(cfg, params, name, x):
    """(B, T, T) bool: what the layer's own Indexer selects for the layer's
    input x (the residual stream before the layer)."""
    from ray_tpu.models.llama import RMSNorm

    h = RMSNorm(cfg.rms_eps).apply({"params": params[name]["attn_norm"]}, x.astype(cfg.dtype))
    mask, mask_t, _ = Indexer(cfg).apply({"params": params[name]["indexer"]}, h)
    seen = indexer.unpack(mask)
    assert (indexer.unpack(mask_t) == seen.swapaxes(1, 2)).all()
    return seen


@pytest.mark.parametrize("first_expert", [0, 4])
def test_system_agrees_with_the_reference_in_float32(first_expert):
    sizes = _sizes(first_expert_held=first_expert)
    cfg = FAMILY.build(sizes, "float32")
    model = Mellum(cfg)
    idx, targets = _batch(sizes)
    params = model.init(jax.random.PRNGKey(1), idx)["params"]

    def system(p):
        logits, sown = model.apply({"params": p}, idx, mutable=["choices"])
        return loss_fn(logits, targets), sown["choices"]

    (loss, sown), grads = jax.value_and_grad(system, has_aux=True)(params)
    held = {name: jax.tree.leaves(c)[0] for name, c in sown.items()}
    assert sorted(held) == FAMILY.layer_names(sizes)
    want, want_grads = jax.value_and_grad(
        lambda p: families.reference_loss(FAMILY, p, idx, targets, sizes, held))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    np.testing.assert_allclose(_flat(grads), _flat(want_grads), rtol=2e-4, atol=1e-6)
    # the reference's own choices are the system's, and so are its key sets
    names, outer = families.split_params(FAMILY, params, sizes)
    x = FAMILY.embed(outer, idx, sizes)
    for name in names:
        assert (FAMILY.choice(x, params[name], sizes) == held[name]).all()
        keys = FAMILY.selected_keys(x, params[name], sizes)
        assert (keys == _system_keys(cfg, params, name, x)).all()
        assert (np.asarray(keys.sum(-1)) == np.minimum(np.arange(128) + 1, 32)).all()
        x, _ = FAMILY.layer(x, params[name], sizes, held[name])


def test_indexer_takes_no_gradient():
    """A set of integers passes no gradient: the indexer's matrices get zero
    from the system and from the reference, and every other leaf does not."""
    sizes = _sizes()
    model = Mellum(FAMILY.build(sizes, "float32"))
    idx, targets = _batch(sizes, seed=3)
    params = model.init(jax.random.PRNGKey(2), idx)["params"]
    system = jax.grad(lambda p: loss_fn(model.apply({"params": p}, idx), targets))(params)
    reference = jax.grad(lambda p: families.reference_loss(FAMILY, p, idx, targets, sizes))(params)
    for grads in (system, reference):
        for name in FAMILY.layer_names(sizes):
            index = grads[name].pop("indexer")
            assert sorted(index) == ["k_norm", "wk", "wq", "ww"]
            assert all(float(jnp.abs(g).max()) == 0.0 for g in jax.tree.leaves(index))
        assert all(float(jnp.abs(g).max()) > 0.0 for g in jax.tree.leaves(grads))


def test_system_in_bf16_agrees_on_experts_and_keys():
    sizes = _sizes()
    cfg = FAMILY.build(sizes, "bfloat16")
    model = Mellum(cfg)
    idx, targets = _batch(sizes, rows=4, seed=5)
    params = model.init(jax.random.PRNGKey(4), idx)["params"]
    logits, sown = model.apply({"params": params}, idx, mutable=["choices"])
    held = {name: jax.tree.leaves(c)[0] for name, c in sown["choices"].items()}
    want = families.reference_loss(FAMILY, params, idx, targets, sizes, held)
    assert abs(float(loss_fn(logits, targets)) - float(want)) < 5e-3 * float(want)
    names, outer = families.split_params(FAMILY, params, sizes)
    x = FAMILY.embed(outer, idx, sizes)
    experts, keys = [], []
    for name in names:
        own = FAMILY.choice(x, params[name], sizes)
        experts.append(float((held[name][..., :, None] == own[..., None, :]).any(-1).mean()))
        theirs, ours = _system_keys(cfg, params, name, x), FAMILY.selected_keys(x, params[name], sizes)
        keys.append(float((theirs & ours).sum() / ours.sum()))
        x, _ = FAMILY.layer(x, params[name], sizes, held[name])
    assert min(experts) > 0.95 and min(keys) > 0.95, (experts, keys)
    assert max(keys) < 1.0  # bf16 scores do move a key at the threshold


def test_eight_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Each of 8 chips holds one expert of 8 and computes attention whole: the
    shares' outputs, attention counted once, are the uncut layer's."""
    sizes = _sizes(num_experts=8)
    whole = FAMILY.build(sizes, "float32")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128, sizes["hidden_size"]))
    block = lambda cfg: MellumBlock(cfg, INDEXED)
    params = block(whole).init(jax.random.PRNGKey(1), x)["params"]
    want, _ = FAMILY.layer(x, params, sizes)
    after_attention = FAMILY._attend(x, params, sizes)
    total = after_attention
    for e in range(8):
        share = dataclasses.replace(whole, first_expert=e, num_held=1)
        cut = {**params, "moe": {"router": params["moe"]["router"],
                                 **{k: params["moe"][k][e:e + 1] for k in ("gate", "up", "down")}}}
        total = total + block(share).apply({"params": cut}, x) - after_attention
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(block(whole).apply({"params": params}, x), want,
                               rtol=1e-4, atol=1e-5)


def test_a_short_sequence_is_plain_causal_attention():
    """T <= top_k: every key before a query is selected, the indexer's scores
    are not computed, and the layer is a full_attention layer's."""
    cfg = MellumConfig.tiny(layer_types=(INDEXED,), qk_norm=True, index_heads=4, index_dim=16,
                            index_top_k=64, yarn=None, num_held=4, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, cfg.n_embd))
    params = MellumBlock(cfg, INDEXED).init(jax.random.PRNGKey(1), x)["params"]
    assert "indexer" in params  # made at any length
    plain = {k: v for k, v in params.items() if k != "indexer"}
    np.testing.assert_array_equal(MellumBlock(cfg, INDEXED).apply({"params": params}, x),
                                  MellumBlock(cfg, FULL).apply({"params": plain}, x))
    assert Indexer(cfg).apply({"params": params["indexer"]}, x) is None


def test_flops_per_token_at_the_cell_s_size():
    sizes = _sizes(rehearse=False)
    layer = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128 + 8 * 16 / 128 * 3 * 2048 * 768
    assert FAMILY.matmul_params(sizes) == 4 * layer + 18992 * 2048
    index = 2048 * (16 * 64 + 64 + 16)
    by_hand = int(6 * (4 * layer + 18992 * 2048)
                  + 4 * (12 * 4096 * (2048 - 2048 ** 2 / (2 * 16384)) + 2 * index + 16 * 64 * 16384))
    assert FAMILY.flops_per_token(sizes, 16384) == by_hand
    assert by_hand == pytest.approx(1.268e9, rel=1e-3)
    cfg = FAMILY.build(sizes, "bfloat16")
    assert cfg.flops_per_token(16384) == by_hand and cfg.index_params() == index
    # at or under top_k keys the layer is causal attention, the indexer's matrices still run
    assert FAMILY.flops_per_token(sizes, 2048) == cfg.flops_per_token(2048) == int(
        6 * (4 * layer + 18992 * 2048) + 4 * (12 * 4096 * 1024 + 2 * index + 16 * 64 * 2048))


def test_remat_plan_of_the_cell_keeps_the_selection():
    cfg = FAMILY.build(_sizes(rehearse=False), "bfloat16")
    plan = mellum.remat_plan(cfg, remat.StepShape(1, 16384), 15 * GIB)
    assert plan.names == ("attn_out", "attn_lse", "attn_sel", "attn_q", "attn_k", "attn_v")
    assert plan.sel_bytes == 4 * 2 * 16384 * 512 * 4  # the mask and its transpose, 4 layers
    assert plan.saved_bytes == 4 * plan.layer_bytes and plan.reckoned_bytes < plan.limit_bytes
    # with no limit the first rung alone, the selection in it
    assert mellum.remat_plan(cfg, remat.StepShape(1, 16384), None).names == \
        ("attn_out", "attn_lse", "attn_sel")
    # a sequence of top_k keys or fewer selects nothing and holds no mask
    assert "attn_sel" not in mellum.remat_plan(cfg, remat.StepShape(8, 2048), 15 * GIB).names
    # the other family of this file is as it was
    old = mellum.remat_plan(MellumConfig(num_held=16, vocab_size=24576),
                            remat.StepShape(2, 8192), 15 * GIB)
    assert old.names == remat.FIRST_RUNG + ("attn_q", "attn_k", "attn_v") and old.sel_bytes == 0


def test_the_cell_s_step_selects_once_a_layer(monkeypatch):
    """The cell's own step lowered for a TPU on this box: four layers, and in
    each the indexer's scores, the selection and the forward kernel once (the
    blocks' remat keeps `attn_sel`, `attn_out`, `attn_lse`), one of each
    backward kernel, every call under the name that says k; no causal call."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat, "chip_limit", lambda stream: 15 * GIB)
    cfg = FAMILY.build(_sizes(rehearse=False), "bfloat16")
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((1, 16384), jnp.int32)
    text = ts._step.trace(state, {"idx": tok, "targets": tok}).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "attn_sel" in remat.traced(ts.model.config).names
    calls = collections.Counter(re.findall(r'kernel_name = "(\w+)"', text))
    ours = {k: n for k, n in calls.items() if k.startswith(("flash_", "index_"))}
    assert ours == {"index_scores": 4, "index_select": 4, "flash_sel2048_fwd": 4,
                    "flash_sel2048_bwd_dq": 4, "flash_sel2048_bwd_dkv": 4}, calls


def test_step_reports_the_keys_a_query_kept_through_the_telemetry():
    cfg = MellumConfig.tiny(layer_types=(INDEXED, INDEXED), qk_norm=True, index_heads=4,
                            index_dim=16, index_top_k=32, yarn=None, num_held=4, block_size=256)
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    try:
        state = ts.init(jax.random.PRNGKey(0))
        idx, targets = _batch({"vocab_size": cfg.vocab_size})
        state, m = ts.step(state, ts.shard_batch({"idx": idx, "targets": targets}))
        # mean of min(32, t + 1) over 128 positions
        assert float(m["attn_keys_selected_mean"]) == (sum(range(1, 33)) + 96 * 32) / 128
        jax.block_until_ready(m)
        assert _telemetry.auto_report_metrics()["telemetry/attn_keys_selected_mean"] == 28.125
        # at a length the selection says nothing the step reports none
        short = ts.shard_batch({"idx": idx[:, :32], "targets": targets[:, :32]})
        assert "attn_keys_selected_mean" not in ts.step(state, short)[1]
    finally:
        _telemetry.set_current_recorder(None)


def test_the_fourth_old_cell_lowers_to_the_parent_s_step(monkeypatch):
    """tests/test_mellum.py pins three of the four old cells' steps; this is
    the fourth, `gpt2_small` at B=128 x T=256, taken on the parent of PR 34
    (08dc464) and on this tree alike: the new layer kind, the fields it
    added to shared modules and the selected kernels leave it as it was."""
    import hashlib

    from tests.test_mellum import _step_text

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat, "chip_limit", lambda stream: 15 * GIB)
    with open(os.path.join(ROOT, "bench", "configs", "gpt2_small.json")) as f:
        sizes = json.load(f)
    cfg = families.load(sizes["family"]).build(sizes, "bfloat16")
    ts = TrainStep(cfg, make_mesh(sizes["mesh"], devices=jax.devices()[:1]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((128, 256), jnp.int32)
    text = _step_text(ts, state, {"idx": tok, "targets": tok})
    assert remat.traced(ts.model.config).names == remat.FIRST_RUNG + (
        "attn_q", "attn_k", "attn_v", "mlp_up")
    assert hashlib.sha256(text.encode()).hexdigest() == \
        "acf912c95fd5fdea137f3b76050c9c34df21e61736d4c44e6445d332d2476446"
