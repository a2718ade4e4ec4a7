"""Compiles for a described (not attached) TPU v5e: what the chip's compiler
would refuse — a Mosaic kernel it cannot partition, a mis-tiled slice, a
program that does not fit 16 GB — fails here, at no chip time.

Only one process may hold the TPU library, so the topology is described
inside a fixture (tests/conftest.py:topo, never at import) and every compile
runs in the test's own process. A compile that passes is not a chip run:
nothing here is a time or a result. This file holds the causal flash calls
and the two dense families; a family's own kernels and its cell's step are in
a file of its own (tests/test_tpu_compile_<family>.py, helpers in
tests/_tpu_compile.py), so that a new family adds a file and edits none and
the files spread over the workers.
"""

import collections
import math

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.gpt2 import GPT2Config
from ray_tpu.ops import attention
from ray_tpu.parallel.mesh import collective_tally
from ray_tpu.parallel.train_step import TrainStep, attn_for_mesh
from tests._tpu_compile import (GIB, V5E_LIMIT, KERNELS, _entry_results, _kernel_calls, _live_bytes,
                                _loss, _qkv, _step_args)


def test_flash_forward_compiles(one_chip):
    c = jax.jit(attention.flash_causal_attention).lower(
        *_qkv((16, 1024, 12, 64), one_chip)).compile()
    assert c.as_text().count("tpu_custom_call") == 1


def test_flash_backward_compiles(one_chip):
    grad = jax.grad(_loss(attention.flash_causal_attention), argnums=(0, 1, 2))
    c = jax.jit(grad).lower(*_qkv((16, 1024, 12, 64), one_chip)).compile()
    # forward (for the residuals) + the one backward call
    assert c.as_text().count("tpu_custom_call") == 2
    assert _kernel_calls(c.as_text()) == dict.fromkeys(KERNELS, 1)


def test_flash_long_wide_heads_compile(one_chip):
    fn = jax.value_and_grad(_loss(attention.flash_causal_attention), argnums=(0, 1, 2))
    c = jax.jit(fn).lower(*_qkv((2, 4096, 16, 128), one_chip)).compile()
    assert c.as_text().count("tpu_custom_call") == 2


@pytest.mark.parametrize("shape", [
    (128, 256, 12, 64),    # gpt2_small.t256: two pairs of heads a grid step
    (32, 1024, 12, 64),    # gpt2_small.t1024: a pair, one vreg of lanes
    (1, 8192, 32, 128),    # mistral_7b_l8.fsdp4_t8192, one chip's share
    (1, 4096, 32, 64),     # granite4_h_micro_l10.t4096's attention layer
    (2, 8192, 32, 64),     # lfm2_8b_a1b_l5_ep4.t8192's
    (1, 256, 7, 64),       # a prime h: the whole row, which ends inside a vreg
    (2, 256, 8, 32),       # four heads a vreg
    (2, 256, 2, 32),       # a row narrower than a vreg
], ids=lambda s: "x".join(map(str, s)))
def test_flash_compiles_at_the_cells_shapes(one_chip, shape):
    """Forward and backward with the tiles `flash_tiles` picks for the
    benchmark's per-chip calls: a VMEM overflow or a mis-tiled slice of the
    larger tiles fails here."""
    fn = jax.value_and_grad(_loss(attention.flash_causal_attention), argnums=(0, 1, 2))
    c = jax.jit(fn).lower(*_qkv(shape, one_chip)).compile()
    assert c.as_text().count("tpu_custom_call") == 2
    assert _kernel_calls(c.as_text()) == dict.fromkeys(KERNELS, 1)


def test_causal_attention_under_mesh_keeps_kernel(mesh_2x2, monkeypatch):
    """A bare pallas_call in a dp/tp-sharded jit is refused ("Mosaic kernels
    cannot be automatically partitioned"); attn_for_mesh shard_maps it."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    sharded = NamedSharding(mesh_2x2, P("dp", None, "tp", None))  # (B, T, H, D)
    args = _qkv((16, 1024, 12, 64), sharded)
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(attention.causal_attention).lower(*args).compile()
    grad = jax.grad(_loss(attn_for_mesh(mesh_2x2)), argnums=(0, 1, 2))
    c = jax.jit(grad).lower(*args).compile()
    assert c.as_text().count("tpu_custom_call") == 2
    assert _kernel_calls(c.as_text()) == dict.fromkeys(KERNELS, 1)


def test_gpt2_124m_step_fits_one_chip(topo, monkeypatch):
    """The whole step at published widths fits a chip, runs each kernel once
    a layer, and writes the logits once, in bf16: the loss is logsumexp less
    the target's logit (models/loss.py), so no float32 array of the logits'
    size is written (log_softmax's result was one, 14.8 ms of the `t256`
    cell's step, and its backward another pass over it). The trap this
    holds: a loss that gathers the target's logit from
    `logits.astype(float32)` makes XLA write the float32 logits as a second
    result of the head's matmul, 6 GiB at the cell's shape."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices[:1]), ("dp",))
    ts = TrainStep(GPT2Config.gpt2_124m(), mesh, telemetry=False)
    c = ts._step.lower(*_step_args(ts)).compile()
    assert _live_bytes(c) < 16 * GIB, c.memory_analysis()
    # one forward and one backward kernel in each of 12 remat'd layers:
    # the forward's output and logsumexp are saved across the remat
    # (models/remat.py), so it is not run again
    text = c.as_text()
    assert text.count("tpu_custom_call") == 24
    assert _kernel_calls(text) == dict.fromkeys(KERNELS, 12)
    assert text.startswith("HloModule jit_train_step")
    written = _entry_results(text)
    assert written["bf16[16,1024,50257]"] and not written["f32[16,1024,50257]"], written


def test_gpt2_step_on_dp_tp_mesh_keeps_kernel(mesh_2x2, monkeypatch):
    """Published widths on a dp=2, tp=2 mesh, depth cut to 2 for compile
    time: V=50257 does not divide by tp (the embedding stays replicated over
    it), the kernel survives the mesh, and the collectives are there."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    ts = TrainStep(GPT2Config.gpt2_124m(n_layer=2), mesh_2x2, telemetry=False)
    assert ts.state_specs["params"]["wte"]["embedding"] == P(None, None)
    assert ts.state_specs["params"]["h_0"]["attn"]["c_attn"]["kernel"] == P(None, "tp")
    text = ts._step.lower(*_step_args(ts)).compile().as_text()
    # the policy reaches the kernel through the shard_map over heads
    assert text.count("tpu_custom_call") == 4
    assert _kernel_calls(text) == dict.fromkeys(KERNELS, 2)
    assert "all-reduce(" in text


def test_mistral_step_under_fsdp_gathers_weights_not_activations(topo, monkeypatch):
    """mistral_7b_l8.fsdp4_t8192's step at the cell's widths and batch, depth
    cut to 2 for compile time: with the residual stream pinned to the
    batch's split (parallel/mesh.py:stream_sharding) the partitioner gathers
    each kernel whole, in bf16, and moves no activation. Left to choose it
    keeps the weights where they are and all-reduces `bf16[4,8192,14336]`
    five times a layer (PERF.md section 6, PR 30)."""
    from ray_tpu.models.llama import LlamaConfig

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    layers, batch, seq_len, d, ff, kv, vocab = 2, 4, 8192, 4096, 14336, 1024, 32768
    cfg = LlamaConfig(vocab_size=vocab, block_size=32768, n_layer=layers, n_head=32,
                      n_kv_head=8, n_embd=d, intermediate=ff, rope_theta=1e6)
    ts = TrainStep(cfg, Mesh(np.array(topo.devices), ("fsdp",)), telemetry=False)
    c = ts._step.lower(*_step_args(ts, (batch, seq_len))).compile()
    text = c.as_text()
    tally = collective_tally(text)

    def rows(c):  # of a [..., width] array
        return math.prod(c.shape[:-1]) if len(c.shape) > 1 else 1

    # no sum and no gather leaves the batch's tokens on a chip
    moved = {str(c): n for c, n in tally.items()
             if c.kind in ("all-reduce", "all-gather") and c.dtype != "s32"
             and rows(c) >= batch * seq_len}
    assert not moved, moved
    # what is exchanged is the embedding's look-up, made on each chip's
    # quarter of the hidden dimension: one chip's share of the stream,
    # forward and backward
    exchanged = [c for c in tally.elements() if c.kind == "all-to-all"]
    share = batch * seq_len * d * 2 // 4
    assert len(exchanged) <= 2 and all(c.nbytes <= share for c in exchanged), exchanged
    # every kernel is gathered whole, as the matmul's bf16 operand
    gathered = {c.shape for c in tally if c.kind == "all-gather" and c.dtype == "bf16"}
    assert {(d, ff), (ff, d), (d, d), (d, kv), (d, vocab)} <= gathered, gathered
    # and each block's weight gradients are summed over the chips
    summed = {c.shape for c in tally if c.kind == "all-reduce"}
    assert {(d, ff), (ff, d), (d, vocab)} <= summed, summed
    assert _kernel_calls(text) == dict.fromkeys(KERNELS, layers)
    # 5.25 GiB; the parent's program, by the same code, holds 7.19
    assert _live_bytes(c) < 5.5 * GIB, c.memory_analysis()


@pytest.mark.slow  # 100 and 85 s: whole steps of eight layers on four chips and of ten on one
@pytest.mark.timeout(900)
@pytest.mark.parametrize("config,axis,chips,batch,depths,read_gib", [
    ("mistral_7b_l8", "fsdp", 4, (4, 8192), (7, 8), 13.464),
    ("granite4_h_micro_l10", "dp", 1, (1, 4096), (9, 10), 11.252)])
def test_a_whole_cell_under_the_chip_s_own_limit_holds_what_the_compile_read(
        topo, monkeypatch, config, axis, chips, batch, depths, read_gib):
    """The two cells whose plan PR 65 moved and whose whole step no family's
    compile file holds: each cell's step at its shape and mesh, compiled for
    the described v5e under the limit the rule makes of that chip's reading.
    mistral's eight blocks save the operands and the last seven `mlp_up`
    (14.07 GiB a chip reckoned, 13.464 by the compiler's count); granite's
    nine Mamba layers save the scan's outputs and all ten layers `mlp_up`
    (13.68 reckoned, 11.252: its 16 bytes a parameter, tests/test_granite.py).
    Neither stands over its reckoning, and both under the room."""
    from ray_tpu.models import remat
    from ray_tpu.ops import short_conv, ssd
    from tests._tpu_compile import V5E_ROOM, cell_config

    for mod in (attention, ssd, short_conv):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat, "chip_limit", lambda stream: V5E_LIMIT)
    cfg = cell_config(config)
    ts = TrainStep(cfg, Mesh(np.array(topo.devices[:chips]), (axis,)), telemetry=False)
    c = ts._step.lower(*_step_args(ts, batch)).compile()
    plan = remat.traced(ts.model.config)
    assert tuple(k for _, k, _ in plan.depths) == depths
    live = _live_bytes(c)
    assert abs(live / GIB - read_gib) <= 0.05, (plan, c.memory_analysis())
    assert live <= plan.reckoned_bytes <= V5E_ROOM


def test_the_plan_at_a_shape_no_chip_ran_fits_the_chip(topo, monkeypatch):
    """models/remat.py's rule at a shape the chip runs of PR 33 never saw,
    GPT-2 small's widths 24 layers deep at half its cell's rows, given a
    v5e's limit: it takes every name, and the step compiled with them holds
    less than the 14 GiB the cells are held to, and not more than the
    reckoning said by more than the error it showed against the chip's own
    readings (tests/test_remat.py). (At the cell's own rows the rule takes
    no further rung, reckoning 14.9 GiB with the operands: that step
    compiled to 14.7, and to 11.7 without them. The routed cell's share
    with 8 experts held and the operands saved compiled to 11.10 GiB where
    the rule reckons 10.93.)"""
    from ray_tpu.models import remat

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat, "chip_limit", lambda stream: V5E_LIMIT)
    ts = TrainStep(GPT2Config.gpt2_124m(n_layer=24), Mesh(np.array(topo.devices[:1]), ("dp",)),
                   telemetry=False)
    c = ts._step.lower(*_step_args(ts, (64, 256))).compile()
    plan = remat.traced(ts.model.config)
    assert plan.names == remat.FIRST_RUNG + ("attn_q", "attn_k", "attn_v", "mlp_up")
    assert _live_bytes(c) < 14 * GIB, c.memory_analysis()
    assert _live_bytes(c) - plan.reckoned_bytes <= 0.85 * GIB, (plan, c.memory_analysis())



def test_the_scope_table_names_the_ledger_s_ops_of_gpt2_small_t256(topo, monkeypatch):
    """`gpt2_small.t256`'s step compiled for the described v5e at the cell's
    shapes, through train/_device_profile.py's table: the two entries the
    ledger's `device_ops` prints first are named by where the program wrote
    them. `multiply_reduce_fusion -> (f32[768], f32[128,256], f32[128,256],
    f32[768], bf16[128,256,768])` is 24 input-gradient matmuls (`c_fc`,
    `c_attn`, the head) whose name is their epilogue's, a LayerNorm's
    backward sums; `copy -> bf16[128,12,256,64]`, until PR 42 the layout
    copies round the flash calls, 96 a step, is gone with every other op on
    an array of that shape or of (128, 256, 12, 64): the calls read
    (128, 256, 768); the Pallas calls are kernels under their own names."""
    from ray_tpu.models import remat
    from ray_tpu.train import _device_profile as dp

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat, "chip_limit", lambda stream: V5E_LIMIT)  # the cell's own plan
    mesh = Mesh(np.array(topo.devices[:1]), ("dp",))
    ts = TrainStep(GPT2Config.gpt2_124m(), mesh, telemetry=False)
    text = ts._step.lower(*_step_args(ts, (128, 256))).compile().as_text()
    table = dp.scope_table(text)
    assert table["module"] == "jit_train_step"
    rows = table["rows"].values()
    named = "-> (f32[768], f32[128,256], f32[128,256], f32[768], bf16[128,256,768])"
    fused = collections.Counter((r[0], r[1], r[2], r[3]) for r in rows if r[4].endswith(named))
    assert fused == {("h/mlp/c_fc", "bwd", "matmul", "mlp"): 12,
                     ("h/attn/c_attn", "bwd", "matmul", "attn.proj"): 11,
                     ("wte.attend", "bwd", "matmul", "head"): 1}, fused
    by_heads = [r[4] for r in rows if "[128,12,256,64]" in r[4] or "[128,256,12,64]" in r[4]]
    assert not by_heads, by_heads
    kernels = collections.Counter((r[0], r[1], r[3]) for r in rows if r[2] == "kernel")
    assert kernels == {("h/attn/flash_fwd", "fwd", "attn.core"): 12,
                       (f"h/attn/{KERNELS[1]}", "bwd", "attn.core"): 12}, kernels
    # every matmul of the dense layers and the head is a matmul whatever its
    # fusion is called, in all three passes, and nothing scheduled is unscoped
    # but a handful of XLA's own
    matmuls = collections.Counter(r[1] for r in rows if r[2] == "matmul")
    assert matmuls == {"fwd": 4 * 12 + 1, "bwd": 8 * 12 + 2, "remat": 12}, matmuls
    assert sum(r[3] == "unscoped" for r in rows) < 20
