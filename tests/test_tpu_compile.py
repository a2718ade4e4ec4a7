"""Compiles for a described (not attached) TPU v5e: what the chip's compiler
would refuse — a Mosaic kernel it cannot partition, a mis-tiled slice, a
program that does not fit 16 GB — fails here, at no chip time.

The only file that describes a chip. Only one process may hold the TPU
library, so the topology is described inside a fixture (never at import) and
every compile runs in the test's own process. A compile that passes is not a
chip run: nothing here is a time or a result.
"""

import collections
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from ray_tpu.models.gpt2 import GPT2Config
from ray_tpu.ops import attention
from ray_tpu.parallel.mesh import collective_tally
from ray_tpu.parallel.train_step import TrainStep, attn_for_mesh

GIB = 1 << 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Such a compile is written to the persistent cache but cannot be read
    # back without a chip: keep the cache off round these tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))


def _qkv(shape, sharding):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)
    return x, x, x


def _loss(attn):
    return lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum()


def _step_args(ts, batch=(16, 1024)):
    """(state, batch) of a TrainStep as shapes with its own shardings."""
    shapes = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, ts.state_shardings)
    tokens = jax.ShapeDtypeStruct(batch, jnp.int32, sharding=ts.batch_sharding)
    return state, {"idx": tokens, "targets": tokens}


def _live_bytes(compiled):
    """What the program holds on a device: arguments, results that alias
    none of them, and temporaries."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
_CUSTOM_CALL = re.compile(
    r"^\s*(?:ROOT )?%?([\w\-.]+) = .*custom_call_target=\"tpu_custom_call\"", re.M)


def _kernel_calls(compiled_text):
    """How many tpu_custom_calls of the compiled program carry each kernel's
    name in their instruction name (the names are what the benchmark's
    per-kernel metrics and a reader of the trace find them by). A call
    without one of the three names fails."""
    counts = collections.Counter()
    for name in _CUSTOM_CALL.findall(compiled_text):
        named = [k for k in KERNELS if k in name]
        assert len(named) == 1, f"tpu_custom_call {name!r} names no one kernel"
        counts[named[0]] += 1
    assert sum(counts.values()) == compiled_text.count("tpu_custom_call")
    return dict(counts)


def test_flash_forward_compiles(one_chip):
    c = jax.jit(attention.flash_causal_attention).lower(
        *_qkv((16, 12, 1024, 64), one_chip)).compile()
    assert c.as_text().count("tpu_custom_call") == 1


def test_flash_backward_compiles(one_chip):
    grad = jax.grad(_loss(attention.flash_causal_attention), argnums=(0, 1, 2))
    c = jax.jit(grad).lower(*_qkv((16, 12, 1024, 64), one_chip)).compile()
    # forward (for the residuals) + the dq and dkv kernels
    assert c.as_text().count("tpu_custom_call") == 3
    assert _kernel_calls(c.as_text()) == dict.fromkeys(KERNELS, 1)


def test_flash_long_wide_heads_compile(one_chip):
    fn = jax.value_and_grad(_loss(attention.flash_causal_attention), argnums=(0, 1, 2))
    c = jax.jit(fn).lower(*_qkv((2, 16, 4096, 128), one_chip)).compile()
    assert c.as_text().count("tpu_custom_call") == 3


@pytest.mark.parametrize("shape", [
    (128, 12, 256, 64),    # gpt2_small.t256: several heads a grid step
    (32, 12, 1024, 64),    # gpt2_small.t1024
    (1, 32, 8192, 128),    # mistral_7b_l8.fsdp4_t8192, one chip's share
    (1, 7, 256, 64),       # a prime bh: the last grid step's heads run past the end
], ids=lambda s: "x".join(map(str, s)))
def test_flash_compiles_at_the_cells_shapes(one_chip, shape):
    """Forward and backward with the tiles `flash_tiles` picks for the
    benchmark's per-chip calls: a VMEM overflow or a mis-tiled slice of the
    larger tiles fails here."""
    fn = jax.value_and_grad(_loss(attention.flash_causal_attention), argnums=(0, 1, 2))
    c = jax.jit(fn).lower(*_qkv(shape, one_chip)).compile()
    assert c.as_text().count("tpu_custom_call") == 3
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
    assert c.as_text().count("tpu_custom_call") == 3
    assert _kernel_calls(c.as_text()) == dict.fromkeys(KERNELS, 1)


@pytest.mark.timeout(300)
def test_gpt2_124m_step_fits_one_chip(topo, monkeypatch):
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices[:1]), ("dp",))
    ts = TrainStep(GPT2Config.gpt2_124m(), mesh, telemetry=False)
    c = ts._step.lower(*_step_args(ts)).compile()
    assert _live_bytes(c) < 16 * GIB, c.memory_analysis()
    # one forward and two backward kernels in each of 12 remat'd layers:
    # the forward's output and logsumexp are saved across the remat
    # (models/remat.py), so it is not run again
    assert c.as_text().count("tpu_custom_call") == 36
    assert _kernel_calls(c.as_text()) == dict.fromkeys(KERNELS, 12)
    assert c.as_text().startswith("HloModule jit_train_step")


@pytest.mark.timeout(300)
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
    assert text.count("tpu_custom_call") == 6
    assert _kernel_calls(text) == dict.fromkeys(KERNELS, 2)
    assert "all-reduce(" in text


@pytest.mark.timeout(600)
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


@pytest.mark.timeout(300)
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
    monkeypatch.setattr(remat, "chip_limit", lambda stream: 15 * GIB)
    ts = TrainStep(GPT2Config.gpt2_124m(n_layer=24), Mesh(np.array(topo.devices[:1]), ("dp",)),
                   telemetry=False)
    c = ts._step.lower(*_step_args(ts, (64, 256))).compile()
    plan = remat.traced(ts.model.config)
    assert plan.names == remat.FIRST_RUNG + ("attn_q", "attn_k", "attn_v", "mlp_up")
    assert _live_bytes(c) < 14 * GIB, c.memory_analysis()
    assert _live_bytes(c) - plan.reckoned_bytes <= 0.85 * GIB, (plan, c.memory_analysis())


def test_windowed_flash_compiles_at_the_cell_s_shape(one_chip):
    """mellum2_12b_l4_ep4.t8192's window layers: (2, 32, 8192, 128) under a
    window of 1,024, forward and backward with the tiles `flash_tiles`
    picks, each call under the name that says its window."""
    windowed = lambda q, k, v: attention.flash_causal_attention(q, k, v, window=1024)
    fn = jax.value_and_grad(_loss(windowed), argnums=(0, 1, 2))
    text = jax.jit(fn).lower(*_qkv((2, 32, 8192, 128), one_chip)).compile().as_text()
    names = _CUSTOM_CALL.findall(text)
    assert len(names) == text.count("tpu_custom_call") == 3
    for kernel in ("flash_win1024_fwd", "flash_win1024_bwd_dq", "flash_win1024_bwd_dkv"):
        assert sum(kernel in n for n in names) == 1, names
    assert not any(k in n for k in KERNELS for n in names)


def test_expert_share_compiles_at_the_cell_s_size(one_chip, monkeypatch):
    """16 held experts of 64, top-8, on 16,384 tokens of width 2,304: the
    three grouped matmuls and their six gradients are megablox's kernels
    under the names the compiler gives them (gmm, tgmm: what the
    benchmark's moe_gmm metrics look for), once for the buffer with headroom
    and once for the buffer of every row, and the plan's gathers bring no
    scatter of rows."""
    from ray_tpu.ops.moe import ExpertShare

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    layer = ExpertShare(2304, 896, 64, 8, 0, 16)
    x = jax.ShapeDtypeStruct((2, 8192, 2304), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, 2304), jnp.bfloat16)))["params"])
    loss = lambda p, x: layer.apply({"params": p}, x).astype(jnp.float32).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile().as_text()
    names = _CUSTOM_CALL.findall(text)
    kinds = collections.Counter(re.sub(r"[.\d]+$", "", n) for n in names)
    assert kinds == {"gmm": 2 * 6, "tgmm": 2 * 3}, kinds
    # megablox's group metadata is made with scatters of a few hundred
    # elements; none is as long as the tokens
    scattered = re.findall(r"= \w+\[([\d,]*)\]\S* scatter\(", text)
    assert all(math.prod(map(int, s.split(","))) < 1024 for s in scattered), scattered
    # the buffer with headroom: 1.5 x 131,072 x 16/64 rows
    assert "bf16[49152,2304]" in text and "bf16[131072,2304]" in text


def test_selected_flash_compiles_at_the_cell_s_shape(one_chip):
    """keye_vl2_30b_l4_ep8.t16384's layers: (1, 32, 16384, 128) over 2,048
    keys a query named by a packed mask, forward and backward with the tiles
    `flash_tiles` picks, each call under the name that says k."""
    mask = jax.ShapeDtypeStruct((1, 16384, 512), jnp.int32, sharding=one_chip)
    selected = lambda q, k, v, m, mt: attention.flash_selected_attention(
        q, k, v, m, mt, 2048).astype(jnp.float32).sum()
    fn = jax.value_and_grad(selected, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(*_qkv((1, 32, 16384, 128), one_chip), mask, mask).compile().as_text()
    names = _CUSTOM_CALL.findall(text)
    assert len(names) == text.count("tpu_custom_call") == 3
    for kernel in ("flash_sel2048_fwd", "flash_sel2048_bwd_dq", "flash_sel2048_bwd_dkv"):
        assert sum(kernel in n for n in names) == 1, names
    assert not any(k in n for k in KERNELS for n in names)


def test_indexer_compiles_at_the_cell_s_shape(one_chip):
    """The indexer's scores (16 heads of 64 against one key head over 16,384
    positions) and the exact top-2,048 of each row, as pallas calls under
    their names, and the mask's transpose beside them without a (T, T) array
    of words."""
    from ray_tpu.ops import indexer

    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    def select(q, k, w):
        mask = indexer._pallas_select(indexer._pallas_scores(q, k, w, False), 2048, False)
        return mask, indexer.transpose_packed(mask)

    c = jax.jit(select).lower(shape((1, 16384, 16, 64), jnp.bfloat16),
                              shape((1, 16384, 64), jnp.bfloat16),
                              shape((1, 16384, 16), jnp.bfloat16)).compile()
    names = _CUSTOM_CALL.findall(c.as_text())
    assert sorted(re.sub(r"[.\d]+$", "", n) for n in names) == ["index_scores", "index_select"]
    # the scores, 1 GiB of float32, are the only array of that size
    assert GIB <= c.memory_analysis().temp_size_in_bytes < 1.25 * GIB
