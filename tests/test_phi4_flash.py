"""models/phi4_flash.py against the plain reference of
bench/families/phi4_flash.py on seeded weights with two cross-decoder periods
(loss and every gradient, the memory's and the shared K/V's summed over both
their readers), what the comparison catches when a part is dropped, the
parameters of the cell and of the uncut model, the remat rule's plan, the
cell's lowered step (its kernels tallied, its hash pinned) and the gauges
through the telemetry."""

import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families
from ray_tpu.models import phi4_flash, remat
from ray_tpu.models.loss import loss_fn
from ray_tpu.models.phi4_flash import CROSS, FULL, GMU, MAMBA, WINDOW, Phi4Flash, Phi4FlashConfig
from ray_tpu.ops import attention, selective_scan, short_conv
from ray_tpu.parallel.mesh import kernel_tally, make_mesh
from ray_tpu.parallel.train_step import TrainStep
from ray_tpu.train import _telemetry
from tests.test_lfm2 import _batch
from tests._tpu_compile import V5E_LIMIT, V5E_ROOM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = families.load("phi4_flash")
GIB = remat.GIB
CELL = "phi4_mini_flash_l5"


def _sizes(rehearse=True, **changed):
    with open(os.path.join(ROOT, "bench", "configs", f"{CELL}.json")) as f:
        sizes = json.load(f)
    if rehearse:
        sizes.update(sizes["rehearsal"])
    sizes.update(changed)
    return sizes


def _loss(cfg, params, idx, targets):
    return loss_fn(Phi4Flash(cfg).apply({"params": params}, idx), targets)


@pytest.fixture(scope="module")
def seeded():
    sizes = _sizes()
    cfg = FAMILY.build(sizes, "float32")
    # the rehearsal keeps two periods of the cross-decoder: two readers each
    assert cfg.layer_types == (WINDOW, MAMBA, FULL, GMU, CROSS, GMU, CROSS) == tuple(
        FAMILY.kinds(sizes))
    idx, targets = _batch(sizes["vocab_size"], t=128)  # four windows, two query blocks
    params = Phi4Flash(cfg).init(jax.random.PRNGKey(1), idx)["params"]
    # the vectors (norms, biases, taps' bias, D, lambda's) off their initial
    # values, so that each one's gradient is a test of its own
    params = jax.tree.map(lambda p: p + 0.05 * jax.random.normal(
        jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else p, params)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.value_and_grad(lambda p: families.reference_loss(
            FAMILY, p, idx, targets, sizes))(params)
    return sizes, params, idx, targets, ref_loss, ref_grads


def test_system_agrees_with_the_reference_in_float32(seeded):
    """Loss and every gradient. Both sides are float32; what differs is the
    algebra (the maps as heads of twice the width beside zeros with sqrt(2)
    on W_q against the pairs as written, the scan's chunks against blocks of
    steps, the mixer whole against a group of channels at a time): 1e-5 of
    the loss, 3e-4 of each gradient's largest entry."""
    sizes, params, idx, targets, ref_loss, ref_grads = seeded
    cfg = FAMILY.build(sizes, "float32")
    assert sorted(params) == ["final_norm", "p_0", "tok_emb"] == sorted(
        FAMILY.layer_names(sizes) + ["final_norm", "tok_emb"])
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: _loss(cfg, p, idx, targets))(params)
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * float(ref_loss)
    flat, ref_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, ref_grads))
    # a self-attention mixer's nine leaves, a cross one's nine, Mamba's nine, the
    # unit's two; a block's two norms of two and its MLP's two; embedding, final norm
    assert len(flat) == len(ref_flat) == 2 * 9 + 2 * 9 + 9 + 2 * 2 + 7 * 6 + 3
    for path, g in flat.items():
        scale = float(jnp.abs(ref_flat[path]).max())
        assert scale > 0, path
        np.testing.assert_allclose(g, ref_flat[path], rtol=0, atol=3e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_shared_tensors_gradients_are_sums_over_both_readers(seeded):
    """The cell has one reader of m and one of K, V and cannot see this: with
    two cross-decoder periods the gradient into the full layer's key and
    value columns and into the memory layer's scan (A_log, D, W_x, W_dt) is
    the reference's, and neither reader's alone: without the second period's
    readers it is another by more than the tolerance."""
    sizes, params, idx, targets, _, ref_grads = seeded
    cfg = FAMILY.build(sizes, "float32")
    d, kv = cfg.n_embd, 2 * cfg.kv_dim
    shared = lambda g: [g["p_0"]["h_2"]["attn"]["qkv"]["kernel"][:, d:d + kv]] + [
        g["p_0"]["h_1"]["mamba"][name] for name in ("A_log", "D")] + [
        g["p_0"]["h_1"]["mamba"][name]["kernel"] for name in ("x_proj", "dt_proj")]
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda p: _loss(cfg, p, idx, targets))(params)
        one = dict(sizes, layers_kept=sizes["layers_kept"][:5], num_hidden_layers=5)
        fewer = {**params, "p_0": {k: v for k, v in params["p_0"].items() if k < "h_5"}}
        alone = jax.grad(lambda p: _loss(FAMILY.build(one, "float32"), p, idx, targets))(fewer)
    for both, ref, single in zip(shared(grads), shared(ref_grads), shared(alone)):
        scale = float(jnp.abs(ref).max())
        np.testing.assert_allclose(both, ref, rtol=0, atol=3e-4 * scale)
        assert float(jnp.abs(single - ref).max()) > 30e-4 * scale


DROPPED = {
    "carry": ("RESET_EVERY", 32),
    "memory_after_gate": ("MEMORY_AFTER_GATE", True),
    "second_lambda": ("NO_SECOND_LAMBDA", True),
    "diff_norm": ("NO_DIFF_NORM", True),
}


@pytest.mark.parametrize("what", sorted(DROPPED))
def test_the_comparison_catches_what_is_dropped(seeded, what, monkeypatch):
    """Each of the control's faults (bench/tests/phi4_flash_control.py) put
    into the reference moves its loss from the sound reference's by more than
    the float32 comparison allows, at this size: the comparison above would
    see a program that made any of them."""
    sizes, params, idx, targets, ref_loss, _ = seeded
    name, value = DROPPED[what]
    monkeypatch.setattr(FAMILY, name, value)
    with jax.default_matmul_precision("highest"):
        loss = families.reference_loss(FAMILY, params, idx, targets, sizes)
    assert abs(float(loss) - float(ref_loss)) > 1e-5 * float(ref_loss), what


def test_bf16_decays_move_the_scan_and_not_the_loss(monkeypatch):
    """The control's fifth fault, a step's decay rounded to bf16 (a decay
    nearer 1 than 2^-9 becomes 1), moves the reference's own recurrence by
    0.46% over 256 steps of operands as the model's initialisation gives
    them, a hundred times the kernels' distance from it; the loss
    at this size moves by 5e-7 (granite's finding, PERF.md section 7): the
    scan is held by tests/test_selective_scan.py and chip_smoke.py's
    `sscan_vs_recurrence`, not by a scalar loss."""
    from tests.test_selective_scan import _operands, _rel

    (u, delta, A, B, C, _), _ = _operands(1, 256, 128, 16, jnp.float32)
    sound = FAMILY._recurrence(u, delta, A, B, C)
    monkeypatch.setattr(FAMILY, "DECAY", lambda a: a.astype(jnp.bfloat16).astype(jnp.float32))
    assert _rel(FAMILY._recurrence(u, delta, A, B, C), sound) > 2e-3


def test_a_reader_before_its_source_is_refused():
    assert Phi4FlashConfig().layer_types[14:20] == (MAMBA, WINDOW, MAMBA, FULL, GMU, CROSS)
    assert Phi4FlashConfig().layer_types.count(MAMBA) == 9
    with pytest.raises(ValueError, match="layer 16"):
        Phi4FlashConfig.tiny(layers_kept=(17, 18, 19))
    with pytest.raises(ValueError, match="layer 17"):
        Phi4FlashConfig.tiny(layers_kept=(16, 18, 19))
    Phi4FlashConfig.tiny(layers_kept=(14, 15))  # the self-decoder alone reads nothing


def _count(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def test_parameters_of_the_cell_and_of_the_uncut_model():
    """The count of ISSUE 57 and PERF.md section 4 by the program's own
    shapes, 577.2 M, and by the same rule the published model's 3.85 B."""
    sizes = _sizes(rehearse=False)
    cfg = FAMILY.build(sizes, "bfloat16")
    assert (cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.intermediate, cfg.window,
            cfg.ssm_inner, cfg.ssm_state, cfg.ssm_rank, cfg.ssm_conv) == (
        2560, 40, 20, 64, 10240, 512, 5120, 16, 160, 4)
    shapes = jax.eval_shape(lambda: Phi4Flash(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    blocks = shapes["p_0"]
    assert _count(blocks["h_0"]["mlp"]) == 78_643_200
    assert _count(blocks["h_0"]["attn"]) == _count(blocks["h_2"]["attn"]) == 19_668_864
    assert _count(blocks["h_1"]["mamba"]) == 41_241_600
    assert _count(blocks["h_3"]["gmu"]) == 26_214_400
    assert _count(blocks["h_4"]["cross"]) == 13_112_704
    assert [_count(blocks[f"h_{i}"]) for i in range(5)] == [
        98_322_304, 119_895_040, 98_322_304, 104_867_840, 91_766_144]
    assert _count(shapes["tok_emb"]) == 25_008 * 2560
    assert _count(shapes) == cfg.params() == 577_199_232
    assert _count(shapes) == FAMILY.matmul_params(sizes) + FAMILY.vector_params(sizes)
    assert 16 * _count(shapes) / GIB == pytest.approx(8.60, abs=0.01)
    # 4.24 GFLOPs a token at T = 16,384: a full or a cross layer's maps 377 M
    # each, 18% together with the window's 23 M
    assert cfg.matmul_params() == FAMILY.matmul_params(sizes)
    flops = cfg.flops_per_token(16384)
    assert flops == FAMILY.flops_per_token(sizes, 16384)
    maps = 18 * 2560 * (2 * 8192 + 512 - 8)
    assert flops == 6 * cfg.matmul_params() + maps + 18 * 5120 * 16
    assert flops / 1e9 == pytest.approx(4.24, abs=0.01) and maps / flops == pytest.approx(
        0.18, abs=0.005)
    whole = Phi4FlashConfig()
    uncut = jax.eval_shape(lambda: Phi4Flash(whole).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert _count(uncut) == whole.params()
    assert _count(uncut) / 1e9 == pytest.approx(3.853, abs=0.001)


def _cell_step(monkeypatch):
    """(cfg, the cell's step traced for a TPU on this box under a v5e's limit)."""
    for mod in (attention, selective_scan, short_conv):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat, "chip_limit", lambda stream: V5E_LIMIT)
    cfg = FAMILY.build(_sizes(rehearse=False), "bfloat16")
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((1, 16384), jnp.int32)
    return cfg, ts._step.trace(state, {"idx": tok, "targets": tok})


# This family's own cell (B=1 x T=16384, one chip, a v5e's limit for the remat
# rule), as tests/test_mellum.py:_step_text gives it, taken on PR 57's own
# tree: the program the chip runs of PERF.md section 6 were made with; PR 62's
# since, by design: the remat rule takes a rung by depth (models/remat.py), and
# the last three layers of five save their MLP's product, which no layer saved before;
# PR 65's since, by design: the rule is held to the chip's own limit to within 64 MiB
# (15.6875 GiB, not 15), and the last four layers save it.
PHI4_FLASH_STEP = "dde8ac4bc93f85a6aa5840d8b1d9c04ccd8d3593f6be152ea495615de4bee655"


def test_the_cell_s_step_tallies_its_kernels_and_lowers_to_its_pinned_step(monkeypatch):
    """The cell's own step lowered for a TPU on this box: the Mamba layer
    runs sscan_bwd once and sscan_fwd as often as the remat plan runs it
    (once where it holds `sscan_y` and `sscan_states`), the convolution's
    pair beside it; the window layer the windowed flash pair once, the full
    and the cross layer the causal pair once each: three flash layers, every
    forward once (the first rung holds their outputs). The MLPs' `gate_up`
    matmul, (1, 16384, 20480) out, runs once in the four layers that save its
    product (three until PR 65) and twice in the one that does not: 10 - 4 times."""
    from tests.test_mellum import _traced_text

    cfg, traced = _cell_step(monkeypatch)
    text = _traced_text(traced)
    calls = kernel_tally(text)
    calls.pop("kernel", None)
    assert "sscan_states" in remat.traced(cfg).names  # else sscan_fwd would run twice
    assert calls == {"sscan_fwd": 1, "sscan_bwd": 1, "causal_conv_fwd": 2,
                     "causal_conv_bwd": 1, "flash_fwd": 2,
                     "flash_bwd_fused" + attention.LEGACY_NAMES: 2,
                     "flash_win512_fwd": 1, "flash_win512_bwd_fused": 1}, calls
    assert remat.traced(cfg).depth("mlp_up") == 4
    assert len(re.findall(r"dot_general.*-> tensor<1x16384x20480xbf16>", text)) == 10 - 4
    assert hashlib.sha256(text.encode()).hexdigest() == PHI4_FLASH_STEP


def test_remat_plan_of_the_cell():
    """At the cell's shape under the v5e's limit the rule's choice; with no
    limit the first rung alone; m and K, V are booked with the layers'
    inputs."""
    cfg = FAMILY.build(_sizes(rehearse=False), "bfloat16")
    shape = remat.StepShape(1, 16384)
    chosen = phi4_flash.remat_plan(cfg, shape, V5E_LIMIT)
    # beside 8.60 GiB of state the scan's output and states (0.2 GiB) have
    # room, and of the MLPs' products (3.1 GiB over five layers) the last
    # four layers' (none until PR 62, when a rung was every layer's or none's; three until
    # PR 65, under a limit of 15 GiB where the chip says 15.75: 13.92 of 13.5)
    assert chosen.names == remat.FIRST_RUNG + ("sscan_y", "sscan_states", "mlp_up")
    assert chosen.depths == ((("sscan_y", "sscan_states"), 1, 1), (("mlp_up",), 4, 5))
    assert chosen.saved_in("mlp_up") == (False, True, True, True, True)
    assert chosen.reckoned_bytes <= chosen.limit_bytes == V5E_ROOM
    assert chosen.reckoned_bytes / GIB == pytest.approx(13.92, abs=0.01)
    tight = phi4_flash.remat_plan(cfg, shape, 15 * GIB)
    assert tight.depth("mlp_up") == 3
    assert tight.reckoned_bytes / GIB == pytest.approx(13.30, abs=0.01)
    tokens = 16384
    assert phi4_flash.carried_bytes(cfg, tokens, 2) == tokens * 2 * (5120 + 2 * 1280) \
        == 251_658_240  # the issue's 252 MB
    # an attention layer's output (40 heads of 128) and logsumexp, the Mamba
    # layer's y and chunk states, the gated memory unit's nothing; the last
    # four layers' MLP's product (gate and up, 8,192 wide each)
    attn, product = tokens * 5120 * 2 + tokens * 40 * 4, 2 * tokens * 10240 * 2
    assert cfg.layer_types == ("window", "mamba", "full", "gmu", "cross")
    assert chosen.layer_bytes == (attn, tokens * 5120 * 2 + 128 * 5120 * 16 * 4 + product,
                                  attn + product, product, attn + product)
    roomy = phi4_flash.remat_plan(cfg, remat.StepShape(1, 4096), V5E_LIMIT)
    assert roomy.names == chosen.names and roomy.depth("mlp_up") == 5  # where a shape has the room
    assert phi4_flash.remat_plan(cfg, shape, None).names == remat.FIRST_RUNG
    self_decoder = Phi4FlashConfig.tiny(layers_kept=(14, 15))
    assert phi4_flash.carried_bytes(self_decoder, tokens, 2) == 0


def test_step_reports_the_gauges_through_the_telemetry():
    cfg = Phi4FlashConfig.tiny(layers_kept=(15, 16, 17, 18, 19, 20, 21))
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    try:
        state = ts.init(jax.random.PRNGKey(0))
        idx, targets = _batch(cfg.vocab_size, t=256)  # two chunks: a state is carried
        state, m = ts.step(state, ts.shard_batch({"idx": idx, "targets": targets}))
        jax.block_until_ready(m)
        assert float(m["ssm_chunk_log_decay_min"]) < -1 and float(m["ssm_state_abs_max"]) > 0
        inits = [phi4_flash.lambda_init(i) for i in (15, 17, 19, 21)]
        assert min(inits) - 0.1 < float(m["attn_lambda_min"]) <= float(m["attn_lambda_max"]) \
            < max(inits) + 0.1
        assert float(m["carried_bytes"]) == 2 * 256 * 2 * (128 + 2 * 32)
        report = _telemetry.auto_report_metrics()
        for gauge in ("ssm_chunk_log_decay_min", "ssm_state_abs_max", "attn_lambda_min",
                      "attn_lambda_max", "carried_bytes"):
            assert report[f"telemetry/{gauge}"] == float(m[gauge]), gauge
        plan = ts.telemetry.remat_plan
        assert plan.names == remat.FIRST_RUNG and plan.limit_bytes is None  # no chip
    finally:
        _telemetry.set_current_recorder(None)


def test_system_in_bf16_stays_near_the_reference(seeded):
    """bf16 operands, float32 sums: the loss within 2e-3 at this size."""
    sizes, params, idx, targets, ref_loss, _ = seeded
    cfg = FAMILY.build(sizes, "bfloat16")
    loss = _loss(cfg, params, idx, targets)
    assert abs(float(loss) - float(ref_loss)) <= 2e-3 * float(ref_loss)
