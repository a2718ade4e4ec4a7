"""The device profile (ray_tpu/train/_device_profile.py): from an op_name to
a scope, from a compiled step to a table, from a trace's events to ms a
step, and from a window the program armed to a file, a GCS record, the
recorder's summary and nothing at all while no window is armed. All on the
CPU: the reduction is pure, and a CPU trace names XLA's thunks by their
instructions, so the whole path runs here (its times are no device's)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.parallel.mesh import make_mesh
from ray_tpu.parallel.train_step import TrainStep
from ray_tpu.train import _device_profile as dp

# ------------------------------------------------- (a) op_name -> scope, pass

T = "jit(train_step)/transpose(jvp(GPT2))/jvp(GPT2)/checkpoint/"
OP_NAMES = {
    "forward": ("jit(train_step)/jvp(GPT2)/h_3/mlp/c_fc/dot_general",
                "h/mlp/c_fc", "fwd", "mlp"),
    "backward": (T + "h_3/attn/c_attn/dot_general", "h/attn/c_attn", "bwd", "attn.proj"),
    "remat": (T + "rematted_computation/h_3/mlp/c_fc/dot_general",
              "h/mlp/c_fc", "remat", "mlp"),
    "backward_outside_a_block": ("jit(train_step)/transpose(jvp(GPT2))/ln_f/mul",
                                 "ln_f", "bwd", "norm"),
    "loss": ("jit(train_step)/jvp(loss)/reduce_max", "loss", "fwd", "loss"),
    "loss_backward": ("jit(train_step)/transpose(jvp(loss))/div", "loss", "bwd", "loss"),
    "a_family_s_own_loss": ("jit(train_step)/jvp(loss.diffusion)/reduce_sum",
                            "loss.diffusion", "fwd", "loss"),
    "scalar_delta_rule": (T + "rematted_computation/p_0/h_2/gdn/gdn.rule/pallas_call",
                          "p/h/gdn/gdn.rule", "remat", "gdn"),
    "optimizer": ("jit(train_step)/optimizer/jit(_where)/select_n",
                  "optimizer", "update", "optimizer"),
    "take_under_wte": ("jit(train_step)/jvp(GPT2)/wte/jit(_take)/gather", "wte", "fwd", "embed"),
    "scatter_add_under_wte": ("jit(train_step)/transpose(jvp(GPT2))/wte/jit(_take)/scatter-add",
                              "wte", "bwd", "embed"),
    "tied_head": ("jit(train_step)/jvp(GPT2)/wte.attend/dot_general",
                  "wte.attend", "fwd", "head"),
    "lm_head": ("jit(train_step)/jvp(Llama)/lm_head/dot_general", "lm_head", "fwd", "head"),
    "bare_reduce_sum": ("jit(train_step)/reduce_sum", "", "fwd", "unscoped"),
    "model_level_op": ("jit(train_step)/jvp(GPT2)/iota", "", "fwd", "unscoped"),
    "parameter": ("state['params']['h_0']['mlp']['c_fc']['kernel']", "", "fwd", "unscoped"),
    "residual_add": ("jit(train_step)/jvp(GPT2)/h_0/add", "h", "fwd", "norm"),
    "attention_einsum": ("jit(train_step)/jvp(GPT2)/h_0/attn/bhtd,bhsd->bhts/dot_general",
                         "h/attn/bhtd,bhsd->bhts", "fwd", "attn.core"),
    "flash_kernel": ("jit(train_step)/jvp(GPT2)/h_0/attn/flash_fwd/pallas_call",
                     "h/attn/flash_fwd", "fwd", "attn.core"),
    "attention_out_projection": (T + "h_0/attn/c_proj/dot_general",
                                 "h/attn/c_proj", "bwd", "attn.proj"),
    "mlp_out_projection": (T + "h_0/mlp/c_proj/dot_general", "h/mlp/c_proj", "bwd", "mlp"),
    "llama_projection": ("jit(train_step)/jvp(Llama)/h_1/attn/wq/dot_general",
                         "h/attn/wq", "fwd", "attn.proj"),
    "qk_norm": ("jit(train_step)/jvp(Mellum)/h_1/attn/q_norm/mul",
                "h/attn/q_norm", "fwd", "attn.core"),
    "indexer": ("jit(train_step)/jvp(Mellum)/h_1/attn/indexer/attn.index/wq/dot_general",
                "h/attn/indexer/attn.index/wq", "fwd", "attn.core"),
    "selected_attention": (
        "jit(train_step)/transpose(jvp(Mellum))/jvp(Mellum)/checkpoint/h_2/attn/attn.selected/"
        "flash_sel2048_bwd_fused/pallas_call",
        "h/attn/attn.selected/flash_sel2048_bwd_fused", "bwd", "attn.core"),
    "routed_scope": ("jit(train_step)/jvp(Mellum)/h_0/moe/moe.route/router/dot_general",
                     "h/moe/moe.route/router", "fwd", "moe"),
    "routed_experts_remat": (
        "jit(train_step)/transpose(jvp(Mellum))/jvp(Mellum)/checkpoint/rematted_computation/"
        "h_0/moe/moe.experts/gmm/pallas_call", "h/moe/moe.experts/gmm", "remat", "moe"),
    "state_space_scan": ("jit(train_step)/jvp(Granite)/p_0/h_3/mamba/ssm.scan/ssd_fwd/pallas_call",
                         "p/h/mamba/ssm.scan/ssd_fwd", "fwd", "ssm"),
    "state_space_norm": ("jit(train_step)/jvp(Granite)/p_0/h_3/mamba/ssm.gate/norm/mul",
                         "p/h/mamba/ssm.gate/norm", "fwd", "ssm"),
    "gated_convolution": (
        "jit(train_step)/jvp(Lfm2)/p_0/h_2/conv/conv.mix/gated_conv_fwd/pallas_call",
        "p/h/conv/conv.mix/gated_conv_fwd", "fwd", "conv"),
    "gated_convolution_projection": (
        "jit(train_step)/transpose(jvp(Lfm2))/p_0/jvp(Lfm2)/p_0/checkpoint/h_0/conv/conv.in_proj/"
        "in_proj/dot_general", "p/h/conv/conv.in_proj/in_proj", "bwd", "conv"),
    "operator_norm": ("jit(train_step)/jvp(Lfm2)/p_0/h_1/operator_norm/rsqrt",
                      "p/h/operator_norm", "fwd", "norm"),
    "latent_projection": (
        "jit(train_step)/jvp(Kanana)/p_0/h_1/attn/mla.kv_b/kv_b_proj/dot_general",
        "p/h/attn/mla.kv_b/kv_b_proj", "fwd", "mla"),
    "latent_rotary_backward": (
        "jit(train_step)/transpose(jvp(Kanana))/p_0/jvp(Kanana)/p_0/checkpoint/h_0/attn/mla.rope/mul",
        "p/h/attn/mla.rope", "bwd", "mla"),
    "latent_kernel": (
        "jit(train_step)/jvp(Kanana)/p_0/h_3/attn/attn.core/flash_mla_fwd/pallas_call",
        "p/h/attn/attn.core/flash_mla_fwd", "fwd", "attn.core"),
    "shared_expert": (
        "jit(train_step)/jvp(Kanana)/p_0/h_2/moe.shared/shared/up/dot_general",
        "p/h/moe.shared/shared/up", "fwd", "moe.shared"),
    "routed_beside_the_shared_expert": (
        "jit(train_step)/jvp(Kanana)/p_0/h_2/moe/moe.route/router/dot_general",
        "p/h/moe/moe.route/router", "fwd", "moe"),
    "mixer_only_scan": (
        "jit(train_step)/jvp(NemotronH)/p_0/h_0/mamba/ssm.scan/ssd_fwd/pallas_call",
        "p/h/mamba/ssm.scan/ssd_fwd", "fwd", "ssm"),
    "mixer_only_attention": (
        "jit(train_step)/jvp(NemotronH)/p_0/h_5/attn/wq/dot_general", "p/h/attn/wq", "fwd",
        "attn.proj"),
    "mixer_only_experts_backward": (
        "jit(train_step)/transpose(jvp(NemotronH))/p_0/jvp(NemotronH)/p_0/checkpoint/h_1/moe/"
        "moe.experts/tgmm/pallas_call", "p/h/moe/moe.experts/tgmm", "bwd", "moe"),
    "mixer_only_shared_expert": (
        "jit(train_step)/jvp(NemotronH)/p_0/h_1/moe.shared/shared/down/dot_general",
        "p/h/moe.shared/shared/down", "fwd", "moe.shared"),
    "mixer_only_block_norm": ("jit(train_step)/jvp(NemotronH)/p_0/h_8/norm/rsqrt",
                              "p/h/norm", "fwd", "norm"),
    "period_norm": ("jit(train_step)/jvp(Granite)/p_0/h_5/mixer_norm/rsqrt",
                    "p/h/mixer_norm", "fwd", "norm"),
    "backward_in_a_period": (
        "jit(train_step)/transpose(jvp(Granite))/p_0/jvp(Granite)/p_0/checkpoint/h_9/mamba/"
        "ssm.in_proj/in_proj/dot_general", "p/h/mamba/ssm.in_proj/in_proj", "bwd", "ssm"),
    "remat_in_a_period": (
        "jit(train_step)/transpose(jvp(Granite))/p_0/jvp(Granite)/p_0/checkpoint/"
        "rematted_computation/h_9/mlp/up/dot_general", "p/h/mlp/up", "remat", "mlp"),
    "cond_backward": (
        "jit(train_step)/transpose(jvp(Mellum))/jvp(Mellum)/checkpoint/h_1/moe/cond/branch_0_fun/"
        "transpose(jvp(moe.experts))/jit(tgmm)/pallas_call", "h/moe/moe.experts", "bwd", "moe"),
    "cond_forward_again_in_the_backward": (
        "jit(train_step)/transpose(jvp(Mellum))/jvp(Mellum)/checkpoint/h_1/moe/cond/branch_0_fun/"
        "jvp(moe.experts)/jit(gmm)/pallas_call", "h/moe/moe.experts", "remat", "moe"),
    "cond_forward": ("jit(train_step)/jvp(Mellum)/h_1/moe/cond/branch_1_fun/moe.experts/jit(gmm)/"
                     "jit(_take)/gather", "h/moe/moe.experts", "fwd", "moe"),
    "cond_branch": ("jit(train_step)/jvp(Mellum)/h_0/moe/branch_1_fun/moe.combine/gather",
                    "h/moe/moe.combine", "fwd", "moe"),
    "memory_unit_gate": (
        "jit(train_step)/jvp(Phi4Flash)/p_0/h_3/gmu/gmu.gate/mul", "p/h/gmu/gmu.gate", "fwd", "gmu"),
    "memory_unit_backward": (
        "jit(train_step)/transpose(jvp(Phi4Flash))/p_0/jvp(Phi4Flash)/p_0/checkpoint/h_3/gmu/"
        "gmu.out_proj/out_proj/dot_general", "p/h/gmu/gmu.out_proj/out_proj", "bwd", "gmu"),
    "cross_attention_kernel": (
        "jit(train_step)/jvp(Phi4Flash)/p_0/h_4/cross/attn.cross/flash_fwd/pallas_call",
        "p/h/cross/attn.cross/flash_fwd", "fwd", "attn.cross"),
    "cross_attention_query": (
        "jit(train_step)/jvp(Phi4Flash)/p_0/h_4/cross/wq/dot_general", "p/h/cross/wq", "fwd",
        "attn.cross"),
    "self_attention_fused_projection": (
        "jit(train_step)/jvp(Phi4Flash)/p_0/h_2/attn/qkv/dot_general", "p/h/attn/qkv", "fwd",
        "attn.proj"),
    "self_attention_difference": (
        "jit(train_step)/transpose(jvp(Phi4Flash))/p_0/jvp(Phi4Flash)/p_0/checkpoint/"
        "rematted_computation/h_0/attn/attn.diff/rsqrt", "p/h/attn/attn.diff", "remat", "attn.core"),
    "selective_scan_kernel": (
        "jit(train_step)/jvp(Phi4Flash)/p_0/h_1/mamba/ssm.scan/sscan_fwd/pallas_call",
        "p/h/mamba/ssm.scan/sscan_fwd", "fwd", "ssm"),
    "loop_body": ("jit(train_step)/jvp(Mellum)/h_0/attn/indexer/attn.select/while/body/add",
                  "h/attn/indexer/attn.select", "fwd", "attn.core"),
}


@pytest.mark.parametrize("form", sorted(OP_NAMES))
def test_an_op_name_says_scope_pass_and_group(form):
    op_name, scope, which, group = OP_NAMES[form]
    assert dp.scope_of(op_name) == (scope, which)
    assert dp.group_of(scope) == group
    assert group in dp.GROUPS and which in dp.PASSES


def test_a_collective_is_its_own_group_whatever_its_scope():
    assert dp.group_of("h/mlp/w1", "collective") == "collective"
    assert dp.group_of("", "collective") == "collective"


# ------------------------------------------- (b) a compiled step -> a table


def _tiny(family):
    if family == "gpt2":
        from ray_tpu.models.gpt2 import GPT2Config

        return GPT2Config.tiny(), True
    if family == "llama":
        from ray_tpu.models.llama import LlamaConfig

        return LlamaConfig.tiny(), True
    if family == "gpt2_moe":
        from ray_tpu.models.gpt2_moe import GPT2MoEConfig

        return GPT2MoEConfig.tiny_moe(), False  # its blocks are not under nn.remat
    if family in ("mellum", "mellum_indexed"):
        from ray_tpu.models.mellum import INDEXED, MellumConfig

        if family == "mellum":
            return MellumConfig.tiny(num_held=4), True
        return MellumConfig.tiny(num_held=4, layer_types=(INDEXED,) * 2, qk_norm=True,
                                 index_top_k=32, index_heads=4, index_dim=16, yarn=None,
                                 block_size=256), True
    if family == "lfm2":
        from ray_tpu.models.lfm2 import Lfm2Config

        return Lfm2Config.tiny(num_held=4), True
    if family == "kanana":
        from ray_tpu.models.kanana import KananaConfig

        return KananaConfig.tiny(num_held=4), True
    if family == "nemotron_h":
        from ray_tpu.models.nemotron_h import NemotronHConfig

        return NemotronHConfig.tiny(num_held=4), True
    if family == "afmoe":
        from ray_tpu.models.afmoe import AfmoeConfig

        return AfmoeConfig.tiny(num_held=4), True
    if family == "kimi_linear":
        from ray_tpu.models.kimi_linear import KimiLinearConfig

        return KimiLinearConfig.tiny(num_held=4), True
    if family == "phi4_flash":
        from ray_tpu.models.phi4_flash import Phi4FlashConfig

        return Phi4FlashConfig.tiny(), True
    if family == "sdar":
        from ray_tpu.models.sdar import SDARConfig

        return SDARConfig.tiny(num_held=4), True
    if family == "qwen3_next":
        from ray_tpu.models.qwen3_next import Qwen3NextConfig

        return Qwen3NextConfig.tiny(num_held=4), True
    from ray_tpu.models.granite import GraniteConfig

    return GraniteConfig.tiny(), True


def _compiled_text(cfg, t=128):  # an indexed layer packs its mask 128 keys to a word row
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, t), jnp.int32)
    return ts._step.lower(state, {"idx": tok, "targets": tok}).compile().as_text()


@pytest.mark.parametrize("family", ["gpt2", "llama", "gpt2_moe", "mellum", "mellum_indexed",
                                    "granite", "lfm2", "kanana", "nemotron_h", "afmoe",
                                    "kimi_linear", "phi4_flash", "sdar", "qwen3_next"])
def test_every_instruction_of_a_family_s_step_is_in_a_group(family):
    """The tiny configuration's step, compiled here: every scheduled
    instruction has a group of the one vocabulary and a pass, few are
    unscoped, both directions of the model and the update are there, and a
    remat pass exactly where the blocks are under nn.remat."""
    cfg, rematted = _tiny(family)
    table = dp.scope_table(_compiled_text(cfg))
    assert table["module"] == "jit_train_step"
    rows = table["rows"]
    assert len(rows) > 100
    for name, (scope, which, cls, group, kind) in rows.items():
        assert group in dp.GROUPS and which in dp.PASSES and cls in dp.CLASSES, (name, group)
    unscoped = [n for n, r in rows.items() if r[3] == "unscoped"]
    assert len(unscoped) < 0.05 * len(rows), (len(unscoped), len(rows), unscoped[:20])
    passes = {r[1] for r in rows.values()}
    assert {"fwd", "bwd", "update"} <= passes
    assert ("remat" in passes) == rematted
    groups = {r[3] for r in rows.values()}
    # a latent attention's projections are `mla`: what stands round its kernels
    proj = "mla" if family in ("kanana", "kimi_linear") else "attn.proj"
    assert {"embed", proj, "attn.core", "norm", "head", "loss", "optimizer"} <= groups
    want = {"gpt2": "mlp", "llama": "mlp", "gpt2_moe": "moe", "mellum": "moe",
            "mellum_indexed": "moe", "granite": "ssm", "lfm2": "conv",
            "kanana": "moe.shared", "nemotron_h": "moe.shared", "afmoe": "moe.shared",
            "kimi_linear": "kda", "phi4_flash": "gmu", "sdar": "moe",
            "qwen3_next": "gdn"}[family]
    assert want in groups
    if family == "sdar":  # the objective is the family's own: its noise, its mask, its loss
        scopes = {part for r in rows.values() for part in r[0].split("/")}
        assert {"sdar.noise", "attn.flash_bd", "loss.diffusion"} <= scopes
    if family == "phi4_flash":  # five kinds of block: each kind's scopes, the cross layer apart
        scopes = {r[0] for r in rows.values()}
        for scope in ("ssm.in_proj", "ssm.conv", "ssm.x_proj", "ssm.dt", "ssm.scan", "ssm.gate",
                      "ssm.out_proj", "gmu.in_proj", "gmu.gate", "gmu.out_proj", "attn.window",
                      "attn.full", "attn.cross", "attn.diff"):
            assert any(scope in s.split("/") for s in scopes), scope
        assert {"ssm", "gmu", "attn.cross", "mlp"} <= groups
        assert {dp.group_of(s) for s in scopes if "attn.cross" in s.split("/")} == {"attn.cross"}
        assert {dp.group_of(s) for s in scopes if "attn.full" in s.split("/")} == {"attn.core"}
        assert not [n for n in unscoped if rows[n][0].startswith("p")], unscoped  # none a block's
    if family == "kimi_linear":  # the delta-rule mixer by its six scopes, beside a latent layer
        scopes = {r[0] for r in rows.values()}
        for scope in ("kda.in_proj", "kda.conv", "kda.gate", "kda.scan", "kda.norm",
                      "kda.out_proj", "mla.q", "attn.core", "moe.shared"):
            assert any(scope in s.split("/") for s in scopes), scope
        assert {dp.group_of(s) for s in scopes if "kda.scan" in s.split("/")} == {"kda"}
        assert not [n for n in unscoped if rows[n][0].startswith("p")], unscoped  # none a block's
    if family == "qwen3_next":  # the scalar delta-rule mixer by its five scopes, beside a gated attention
        scopes = {r[0] for r in rows.values()}
        for scope in ("gdn.in_proj", "gdn.conv", "gdn.rule", "gdn.norm", "gdn.out_proj",
                      "attn.gate", "attn.qk_norm", "attn.rope", "moe.shared", "moe.shared_gate"):
            assert any(scope in s.split("/") for s in scopes), scope
        assert {dp.group_of(s) for s in scopes if "gdn.rule" in s.split("/")} == {"gdn"}
        assert {dp.group_of(s) for s in scopes if "moe.shared_gate" in s.split("/")} == {
            "moe.shared"}
        assert {"moe", "moe.shared"} <= groups
        assert not [n for n in unscoped if rows[n][0].startswith("p")], unscoped  # none a block's
    if family in ("lfm2", "kanana", "afmoe", "kimi_linear"):  # dense and routed MLPs in one model
        assert {"mlp", "moe"} <= groups
    if family == "nemotron_h":  # blocks that are a mixer alone: no block has an `mlp` scope
        assert {"ssm", "moe", "moe.shared"} <= groups and "mlp" not in groups
        scopes = {r[0] for r in rows.values()}
        for scope in ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate", "ssm.out_proj",
                      "moe.route", "moe.experts", "moe.combine", "moe.shared", "attn"):
            assert any(scope in s.split("/") for s in scopes), scope
        assert not [n for n in unscoped if rows[n][0].startswith("p")], unscoped  # none a block's
    if family == "afmoe":  # what its attention adds is laid to the kernels' group, its four norms to `norm`
        scopes = {r[0] for r in rows.values()}
        for scope in ("attn.gate", "attn.qk_norm", "attn.rope", "norm.post"):
            assert any(scope in s.split("/") for s in scopes), scope
        assert {dp.group_of(s) for s in scopes if "attn.gate" in s.split("/")} == {"attn.core"}
        assert not [n for n in unscoped if rows[n][0].startswith("p")], unscoped  # none a block's
    assert any(r[2] == "matmul" and r[3] == "head" for r in rows.values())


HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (p0: bf16[8,16], p1: bf16[16,32]) -> (f32[8], bf16[8,32]) {
  %p0 = bf16[8,16]{1,0} parameter(0)
  %p1 = bf16[16,32]{1,0} parameter(1)
  %convolution.1 = bf16[8,32]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(train_step)/transpose(jvp(GPT2))/jvp(GPT2)/checkpoint/h_0/mlp/c_fc/dot_general"}
  %convert.1 = f32[8,32]{1,0} convert(%convolution.1)
  %reduce.1 = f32[8]{0} reduce(%convert.1, %p0), dimensions={1}, to_apply=%add.region, metadata={op_name="jit(train_step)/transpose(jvp(GPT2))/jvp(GPT2)/checkpoint/h_0/ln_2/reduce_sum"}
  ROOT %tuple.1 = (f32[8]{0}, bf16[8,32]{1,0}) tuple(%reduce.1, %convolution.1)
}

%fused_computation.2 (p0: bf16[8,4,2,16]) -> bf16[8,2,4,16] {
  %p0 = bf16[8,4,2,16]{3,2,1,0} parameter(0)
  ROOT %transpose.1 = bf16[8,2,4,16]{3,2,1,0} transpose(%p0), dimensions={0,2,1,3}, metadata={op_name="jit(train_step)/jvp(GPT2)/h_0/attn/transpose"}
}

%add.region (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

%body (s: (s32[], f32[8])) -> (s32[], f32[8]) {
  %s = (s32[], f32[8]{0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%s), index=0
  %gte.1 = f32[8]{0} get-tuple-element(%s), index=1
  %exp.7 = f32[8]{0} exponential(%gte.1), metadata={op_name="jit(train_step)/jvp(Mellum)/h_0/attn/indexer/attn.select/while/body/exp"}
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%gte.0, %exp.7)
}

%cond (s: (s32[], f32[8])) -> pred[] {
  %s = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main.1 (a: bf16[8,16], b: bf16[16,32], q: bf16[8,4,2,16]) -> f32[8] {
  %a = bf16[8,16]{1,0} parameter(0), metadata={op_name="batch['idx']"}
  %b = bf16[16,32]{1,0} parameter(1)
  %q = bf16[8,4,2,16]{3,2,1,0} parameter(2)
  %multiply_reduce_fusion.3 = (f32[8]{0:T(256)}, bf16[8,32]{1,0:T(8,128)(2,1)}) fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(GPT2))/jvp(GPT2)/checkpoint/h_0/ln_2/reduce_sum"}, backend_config={"convolution_algorithm_config":{"emitter":"x"}}
  %copy_fusion.4 = bf16[8,2,4,16]{3,2,1,0} fusion(%q), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(train_step)/jvp(GPT2)/h_0/attn/transpose"}
  %flash_fwd.12 = (bf16[8,4,16]{2,1,0}, f32[8,1,4]{2,1,0}) custom-call(%copy_fusion.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(GPT2)/h_0/attn/flash_fwd/pallas_call"}
  %gte.5 = f32[8]{0} get-tuple-element(%multiply_reduce_fusion.3), index=0
  %reduce_sum.6 = f32[8]{0} negate(%gte.5)
  %scaled.7 = f32[8]{0} multiply(%reduce_sum.6, %reduce_sum.6), metadata={op_name="jit(train_step)/optimizer/mul"}
  %all-reduce.8 = f32[8]{0} all-reduce(%scaled.7), to_apply=%add.region, metadata={op_name="jit(train_step)/optimizer/mul"}
  %orphan.9 = f32[8]{0} negate(%all-reduce.8)
  %init = (s32[], f32[8]{0}) tuple(%gte.5, %orphan.9)
  %while.10 = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(train_step)/jvp(Mellum)/h_0/attn/indexer/attn.select/while"}
  %slice-start.11 = ((f32[8]{0}), f32[4]{0}, s32[]) slice-start(%orphan.9), slice={[0:4]}, metadata={op_name="jit(train_step)/jvp(GPT2)/h_0/mlp/c_fc/dot_general"}
  ROOT %last = f32[8]{0} get-tuple-element(%while.10), index=1
}
"""


def test_the_table_of_a_hand_written_program():
    """A fusion is its matmul's, not its epilogue's (the name XLA gives it);
    a body that only moves data is a copy; a Pallas call is a kernel under
    its own name; an instruction with no op_name inherits from what it
    feeds, else is unscoped; a loop's body is scheduled, a reduce's
    computation is not."""
    table = dp.scope_table(HLO)
    rows = table["rows"]
    assert table["module"] == "jit_train_step"
    assert rows["multiply_reduce_fusion.3"][:4] == ["h/mlp/c_fc", "bwd", "matmul", "mlp"]
    assert rows["multiply_reduce_fusion.3"][4] == \
        "multiply_reduce_fusion fusion -> (f32[8], bf16[8,32])"
    assert rows["copy_fusion.4"][:4] == ["h/attn", "fwd", "copy", "attn.core"]
    assert rows["flash_fwd.12"][:4] == ["h/attn/flash_fwd", "fwd", "kernel", "attn.core"]
    assert rows["reduce_sum.6"][:4] == ["optimizer", "update", "elementwise", "optimizer"]
    assert rows["all-reduce.8"][:4] == ["optimizer", "update", "collective", "collective"]
    # feeds the loop's tuple first, which itself inherits the loop's scope
    assert rows["init"][0] == rows["orphan.9"][0] == "h/attn/indexer/attn.select"
    assert rows["slice-start.11"][2] == "copy"
    assert rows["exp.7"][:4] == ["h/attn/indexer/attn.select", "fwd", "elementwise", "attn.core"]
    assert "add.9" not in rows and "convolution.1" not in rows
    assert rows["b"][:4] == ["h/mlp/c_fc", "bwd", "elementwise", "mlp"]  # inherits; never runs


def test_result_bytes_reads_tuples_and_layouts():
    assert dp.result_bytes("bf16[128,256,768]{2,1,0:T(8,128)(2,1)}") == 128 * 256 * 768 * 2
    assert dp.result_bytes("(f32[768]{0:T(1024)S(1)}, pred[4], s32[])") == 768 * 4 + 4 + 4


# ------------------------------------------------- (d) events -> ms a step

MS = 1_000_000
STEP = "jit_train_step(77)"
MATMUL = "%multiply_reduce_fusion.3 = (f32[8]{0:T(256)}, bf16[8,32]{1,0}) fusion(bf16[8,16]{1,0} %a, bf16[16,32]{1,0} %b), kind=kOutput, calls=%fused_computation.1"
COPY = "%copy_fusion.4 = bf16[8,2,4,16]{3,2,1,0} fusion(bf16[8,4,2,16]{3,2,1,0} %q), kind=kLoop, calls=%fused_computation.2"
KERNEL = "%flash_fwd.12 = (bf16[8,4,16]{2,1,0}, f32[8,1,4]{2,1,0}) custom-call(bf16[8,2,4,16]{3,2,1,0} %copy_fusion.4), custom_call_target=\"tpu_custom_call\""
WHILE = "%while.10 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %init), condition=%cond, body=%body"
EXP = "%exp.7 = f32[8]{0} exponential(f32[8]{0} %gte.1)"
UPDATE = "%scaled.7 = f32[8]{0} multiply(f32[8]{0} %reduce_sum.6, f32[8]{0} %reduce_sum.6)"
STRANGER = "%fusion.999 = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop, calls=%fused_computation.9"


def _step_events(t0):
    """One step program of 100 ms at t0: a matmul of 40, a copy of 10, a
    kernel of 20, a loop of 20 that holds two passes of 8, an update of 5,
    5 idle."""
    return [[MATMUL, t0, 40 * MS], [COPY, t0 + 40 * MS, 10 * MS], [KERNEL, t0 + 50 * MS, 20 * MS],
            [WHILE, t0 + 70 * MS, 20 * MS], [EXP, t0 + 71 * MS, 8 * MS], [EXP, t0 + 80 * MS, 8 * MS],
            [UPDATE, t0 + 90 * MS, 5 * MS]]


def _hand_trace(step_name=STEP, devices=2):
    first = 1000 * MS
    devs = []
    for d in range(devices):
        skew = d * MS  # the second device runs a millisecond behind
        lines, modules = [], []
        # the trace began while a step ran: 30 ms of it are left, with one op
        modules.append([step_name, first + skew - 30 * MS, 30 * MS])
        lines += [[UPDATE, first + skew - 10 * MS, 5 * MS]]
        for k in range(3):
            t0 = first + skew + k * 100 * MS
            modules.append([step_name, t0, 100 * MS])
            lines += _step_events(t0)
        # another program between the first two steps' ends is not the step's
        modules.append(["jit_convert_element_type(3)", first + skew + 300 * MS, MS])
        lines.append([STRANGER, first + skew + 300 * MS, MS])
        devs.append({"name": f"/device:TPU:{d}", "modules": modules, "lines": [lines]})
    spans = [["ray_tpu.train_step.wait", first + k * 100 * MS + 2 * MS, 100 * MS, 7 + k]
             for k in range(3)]  # each ends 2 ms after the first device's program, 1 after the last's
    spans.append(["ray_tpu.train_step.dispatch", first, 3 * MS, 8])
    return {"platform": "tpu", "devices": devs, "spans": spans}


def test_the_reduction_of_a_hand_written_window():
    profile = dp.reduce(_hand_trace(), {STEP: dp.scope_table(HLO)})
    assert profile["program"] == {
        "module": "jit_train_step", "fingerprint": "77", "table": "matched",
        "joined_share": 1.0, "instructions": len(dp.scope_table(HLO)["rows"])}
    assert profile["steps"] == 3 and profile["devices"] == 2  # the cut first step is left out
    assert profile["busy_ms"] == pytest.approx(95.0)
    assert profile["window_ms"] == pytest.approx(100.0)
    assert profile["idle_share"] == pytest.approx(0.05)
    groups = {(r["group"], r["pass"]): r for r in profile["groups"]}
    assert groups[("mlp", "bwd")]["ms"] == pytest.approx(40.0)
    assert groups[("mlp", "bwd")]["calls"] == pytest.approx(1.0)
    # the copy and the kernel, and the loop's own 4 ms with its body's 16
    assert groups[("attn.core", "fwd")]["ms"] == pytest.approx(10 + 20 + 4 + 16)
    assert groups[("optimizer", "update")]["ms"] == pytest.approx(5.0)
    assert sum(r["ms"] for r in profile["groups"]) == pytest.approx(profile["busy_ms"])
    assert sum(r["ms"] for r in profile["scopes"]) == pytest.approx(profile["busy_ms"])
    assert sum(r["share"] for r in profile["groups"]) == pytest.approx(1.0)
    scopes = {(r["scope"], r["pass"], r["class"]): r["ms"] for r in profile["scopes"]}
    assert scopes[("h/attn/indexer/attn.select", "fwd", "elementwise")] == pytest.approx(20.0)
    assert scopes[("h/attn/flash_fwd", "fwd", "kernel")] == pytest.approx(20.0)
    assert profile["kernels"] == [
        {"name": "flash_fwd", "ms": pytest.approx(20.0), "share": pytest.approx(20 / 95),
         "calls": pytest.approx(1.0)}]
    assert profile["shares"] == {
        "remat_share": 0.0, "optimizer_share": pytest.approx(5 / 95), "head_loss_share": 0.0,
        "copy_share": pytest.approx(10 / 95), "unscoped_share": 0.0}
    top = profile["kinds"][0]
    assert top["kind"] == "multiply_reduce_fusion fusion -> (f32[8], bf16[8,32])"
    assert top["ms"] == pytest.approx(40.0)
    assert top["where"] == [{"scope": "h/mlp/c_fc", "pass": "bwd", "class": "matmul",
                             "ms": pytest.approx(40.0)}]
    loop = next(r for r in profile["kinds"] if r["kind"].startswith("exp "))
    assert loop["ms"] == pytest.approx(16.0) and loop["calls"] == pytest.approx(2.0)
    assert not any(r["kind"].startswith("fusion ") for r in profile["kinds"])  # the stranger's
    # seen complete 1 ms after the later device's program ended, each step
    lag = profile["completion_lag_ms"]
    assert lag["per_step"] == pytest.approx([1.0, 1.0, 1.0]) and lag["steps"] == [7, 8, 9]
    assert lag["median"] == pytest.approx(1.0)
    json.dumps(profile)  # plain data


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_the_listing_says_whose_time_a_profile_is(platform):
    """What rides the report and the GCS record carries the platform and the
    devices read, and the listing says where the times are no device's (a
    trace with no TPU plane is read from XLA's host thunks)."""
    from ray_tpu._private import profiling

    trace = dict(_hand_trace(), platform=platform)
    brief = dp.brief(dp.reduce(trace, {STEP: dp.scope_table(HLO)}))
    assert brief["platform"] == platform and brief["devices"] == 2
    assert len(brief["top"]) == 3 and brief["top"][0][:2] == ["attn.core", "fwd"]
    lines = profiling.describe_device_trace({"path": "/t", "steps": 3, "host": "h", "profile": brief})
    assert f"95.000 ms busy a step over 3 steps on 2 x {platform}" in lines[1]
    assert ("no device's time" in lines[1]) == (platform == "cpu")
    assert ("no device's time" in dp.render(dp.reduce(trace, {}))) == (platform == "cpu")


def test_a_child_that_outlasts_its_bound_is_ended(monkeypatch, tmp_path):
    """The reduction's child is waited for CHILD_WAIT_S and no longer."""
    import subprocess
    import sys

    monkeypatch.setattr(dp, "CHILD_WAIT_S", 0.2)
    child = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"],
                             stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True)
    with pytest.raises(RuntimeError, match="ended after 0 s"):
        dp.profile_from_child(child, str(tmp_path))
    assert child.poll() is not None


def test_a_program_the_trace_stored_no_hlo_for_gives_raw_ops_and_says_so():
    """The table of another fingerprint is not joined: no scope row, the
    kinds of op as they are, and the reason."""
    profile = dp.reduce(_hand_trace(), {"jit_train_step(78)": dp.scope_table(HLO)})
    assert profile["program"]["table"] == "the trace stored no HLO under this program's name"
    assert profile["program"]["fingerprint"] == "77"
    assert profile["groups"] == [] and profile["scopes"] == [] and profile["shares"] == {}
    assert profile["busy_ms"] == pytest.approx(95.0)
    assert profile["kinds"][0]["ms"] == pytest.approx(40.0) and profile["kinds"][0]["where"] == []
    assert dp.brief(profile)["top"] == []


def test_instructions_that_are_not_the_program_s_are_not_joined():
    """The same names with other results: another program's instructions."""
    other = HLO.replace("bf16[8,32]", "bf16[8,64]")
    profile = dp.reduce(_hand_trace(), {STEP: dp.scope_table(other)})
    assert profile["program"]["table"] == "the stored HLO's instructions are not the trace's"
    assert profile["program"]["joined_share"] == pytest.approx(55 / 95)
    assert profile["groups"] == []


def test_a_trace_with_no_step_program():
    profile = dp.reduce({"platform": "tpu", "devices": [], "spans": []}, {})
    assert profile["steps"] == 0 and profile["busy_ms"] == 0.0 and profile["groups"] == []
    assert profile["program"]["table"] == "no step program in the trace"


# ------------------------------------ (e) a window the program itself armed


def _tiny_gpt2_step():
    from ray_tpu.models.gpt2 import GPT2Config

    cfg = GPT2Config.tiny(use_flash_attention=False, dtype=jnp.float32)
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    state = ts.init(jax.random.PRNGKey(0))
    idx = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    return ts, state, {"idx": idx, "targets": np.roll(idx, -1, 1)}


@pytest.mark.usefixtures("compiled_afresh")  # the profile joins the trace to the HLO the compile stored
def test_a_window_ends_in_a_profile(monkeypatch, tmp_path, shutdown_only):
    """request_device_trace round three steps of a tiny TrainStep (forced on
    the CPU): device_profile.json beside the trace, the GCS record with its
    path and largest rows, the summary's device_profile, one flight-recorder
    event, and the listing's lines."""
    import ray_tpu
    from ray_tpu._private import flight_recorder, profiling
    from ray_tpu._private.worker import get_global_worker
    from ray_tpu.train import _telemetry

    monkeypatch.setenv("RTPU_device_trace_force", "1")
    ray_tpu.init(num_cpus=2)
    ts, state, batch = _tiny_gpt2_step()
    try:
        state, _ = ts.step(state, ts.shard_batch(batch))  # compiles
        trace_dir = str(tmp_path / "window")
        assert "device_profile" not in ts.telemetry.summary()
        assert _telemetry.request_device_trace(3, trace_dir)
        for _ in range(4):
            state, m = ts.step(state, ts.shard_batch(batch))
            jax.block_until_ready(m)
            ts.telemetry.settle(30.0)
        brief = ts.telemetry.device_trace.wait_profile(120.0)
        assert ts.telemetry.device_trace._reducer.daemon  # it never holds the interpreter's exit
        summary = ts.telemetry.summary()
    finally:
        _telemetry.set_current_recorder(None)
    path = os.path.join(trace_dir, dp.PROFILE_FILE)
    assert brief is not None and brief["path"] == path and os.path.isfile(path)
    with open(path) as f:
        profile = json.load(f)
    assert profile["platform"] == "cpu" and profile["steps"] == 3
    assert brief["platform"] == "cpu" and brief["devices"] == 1
    assert profile["program"]["module"] == "jit_train_step"
    assert profile["program"]["table"] == "matched", profile["program"]
    assert sum(r["ms"] for r in profile["groups"]) == pytest.approx(profile["busy_ms"])
    groups = {r["group"] for r in profile["groups"]}
    assert {"mlp", "attn.proj", "attn.core", "head", "optimizer", "loss"} <= groups
    assert {r["pass"] for r in profile["groups"]} == set(dp.PASSES)
    assert profile["shares"]["remat_share"] > 0 and profile["shares"]["unscoped_share"] < 0.05
    assert len(profile["completion_lag_ms"]["per_step"]) >= 2
    assert summary["device_profile"] == brief and len(brief["top"]) == 5
    assert brief["top"][0][2] >= brief["top"][1][2] > 0
    regs = [r for r in profiling.list_registered(get_global_worker().gcs, "device_trace")
            if r["path"] == trace_dir]
    assert len(regs) == 1 and regs[0]["profile"] == json.loads(json.dumps(brief)), regs
    lines = profiling.describe_device_trace(regs[0])
    assert path in lines[1] and brief["top"][0][0] in lines[2] and "remat" in lines[-1]
    assert "1 x cpu (XLA's host thunks: no device's time)" in lines[1]
    events = [e for e in flight_recorder.dump() if e.get("event") == "train.device_profile"]
    assert len(events) == 1


def test_no_profile_work_and_no_file_while_no_window_is_armed(monkeypatch, tmp_path):
    """Steps with nothing armed: the table's builder, the reader and the
    reducer are never called and nothing is written; the controller's two
    hooks return at their first test."""
    from ray_tpu.train import _telemetry

    called = []
    import subprocess

    for name in ("scope_table", "read_trace", "stored_hlo", "reduce", "profile_window",
                 "start_child", "profile_from_child"):
        monkeypatch.setattr(dp, name, lambda *a, _n=name, **k: called.append(_n))
    monkeypatch.setattr(subprocess, "Popen", lambda *a, **k: called.append("subprocess.Popen"))
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: called.append("start_trace"))
    monkeypatch.setenv("RTPU_device_trace_force", "1")
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    ts, state, batch = _tiny_gpt2_step()
    try:
        for _ in range(4):
            state, m = ts.step(state, ts.shard_batch(batch))
        jax.block_until_ready(m)
        ctl = ts.telemetry.device_trace
        assert ctl.wait_profile(1.0) is None and ctl._reducer is None
        assert "device_profile" not in ts.telemetry.summary()
    finally:
        _telemetry.set_current_recorder(None)
    assert called == []
    assert not any(dp.PROFILE_FILE in files for _, _, files in os.walk(tmp_path))


@pytest.mark.usefixtures("compiled_afresh")  # the profile joins the trace to the HLO the compile stored
def test_the_tool_reduces_a_trace_taken_by_anyone(tmp_path, capsys):
    """A trace started by the caller (as the benchmark's --trace 1 run
    does), reduced by `python -m ray_tpu.train._device_profile <dir>`."""
    from ray_tpu.train import _telemetry

    ts, state, batch = _tiny_gpt2_step()
    try:
        state, m = ts.step(state, ts.shard_batch(batch))
        jax.block_until_ready(m)
        jax.profiler.start_trace(str(tmp_path))
        for _ in range(2):
            state, m = ts.step(state, ts.shard_batch(batch))
        jax.block_until_ready(m)
        jax.profiler.stop_trace()
    finally:
        _telemetry.set_current_recorder(None)
    out = str(tmp_path / "profile.json")
    assert dp.main([str(tmp_path), "--json", out]) == 0
    text = capsys.readouterr().out
    assert "table matched" in text and "optimizer" in text and "remat_share" in text
    with open(out) as f:
        assert json.load(f)["steps"] == 2
    assert dp.main([str(tmp_path / "nothing_here")]) == 1


# ------------------------------------------- (f) a scope moves no instruction


def test_the_optimizer_s_scope_holds_the_global_norm_and_moves_no_instruction():
    """`optax.global_norm(grads)` is under the `optimizer` scope (it was the
    one unscoped reduction of the step); a named scope is metadata alone: the
    step lowered with names stripped is the step of the same function
    without the scope."""
    import optax

    from ray_tpu.models.gpt2 import GPT2Config

    cfg = GPT2Config.tiny()
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    batch = {"idx": tok, "targets": tok}
    scoped = ts._step.lower(state, batch)
    with_names = scoped.as_text(debug_info=True)
    assert '"jit(train_step)/optimizer/reduce_sum"' in with_names
    assert '"jit(train_step)/reduce_sum"' not in with_names

    real = jax.named_scope
    import contextlib

    jax.named_scope = lambda name: contextlib.nullcontext()  # the step as it was written before
    try:
        plain = jax.jit(ts._step_fn, out_shardings=(ts.state_shardings, None),
                        donate_argnums=(0,)).lower(state, batch)
    finally:
        jax.named_scope = real
    assert scoped.as_text() == plain.as_text()
    # and the table finds no bare reduction left
    rows = dp.scope_table(ts._step.lower(state, batch).compile().as_text())["rows"]
    bare = [n for n, r in rows.items() if r[3] == "unscoped" and "reduce" in r[4]]
    assert not bare, bare
