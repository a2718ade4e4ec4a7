"""models/mellum.py's two cells compiled for a described TPU v5e (as
tests/test_tpu_compile.py, no chip): `mellum2_12b_l4_ep4.t8192`'s whole step
and its windowed flash calls, `keye_vl2_30b_l4_ep8.t16384`'s selected flash
calls and its indexer."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from ray_tpu.ops import attention
from ray_tpu.parallel.train_step import TrainStep
from tests._tpu_compile import (GIB, V5E_LIMIT, V5E_ROOM, KERNELS, _CUSTOM_CALL, _kinds, _live_bytes,
                                _loss, _qkv, _step_args, cell_config)


@pytest.mark.slow  # 120 and 100 s: the lowered step's hash is tests/test_mellum.py's PINNED_STEPS["mellum2_12b_l4_ep4"], its bytes tests/test_remat.py's, fast
@pytest.mark.timeout(600)
@pytest.mark.parametrize("rows,kept,grouped", [(2, ("moe_gate", "moe_up", "moe_out"), 60 + 1),
                                               (4, (), 4 * 18)])
def test_mellum_step_holds_what_the_rule_s_block_term_books(topo, monkeypatch, rows, kept, grouped):
    """mellum2_12b_l4_ep4.t8192's whole step compiled for the described v5e
    at the cell's rows and at twice them, the `block` term as PR 45 fitted
    it again (6.5 buffers of a row an assignment). At the cell's rows the rule
    keeps the kernel's operands, the expert layer's down and gate products
    whole and its up product in the last three layers of four (since PR 65,
    under the chip's own limit to within 64 MiB; the down product in three
    layers and the gate's in two under 15 GiB, since PR 62 took a rung by
    depth; the gate and up products whole before): the program holds less
    than the 14.12 GiB the rule is held to (14.044 by my compile of PR 65,
    where it reckons 14.085) and stands within the error the
    reckoning has shown of what it reckoned. At
    twice the rows no further rung fits, the first rung is taken whatever it
    costs, and the reckoning stands over the program (16.7 against 14.7: a
    term linear in the rows books more than XLA then holds), never under.
    The compiler keeps every grouped matmul the lowered step has and adds
    none: 15 a layer and one more for each product a layer does not keep,
    both branches of every `cond` counted."""
    from ray_tpu.models import remat

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat, "chip_limit", lambda stream: V5E_LIMIT)
    cfg = cell_config("mellum2_12b_l4_ep4")
    ts = TrainStep(cfg, Mesh(np.array(topo.devices[:1]), ("dp",)), telemetry=False)
    c = ts._step.lower(*_step_args(ts, (rows, 8192))).compile()
    plan = remat.traced(cfg)
    assert tuple(n for n in plan.names if n.startswith("moe_")) == ("moe_plan",) + kept
    live = _live_bytes(c)
    if kept:
        assert live < V5E_ROOM, c.memory_analysis()
        assert -0.85 * GIB <= live - plan.reckoned_bytes <= 0.35 * GIB, (plan, c.memory_analysis())
    else:
        assert plan.names == remat.FIRST_RUNG + ("moe_plan",)
        assert live <= plan.reckoned_bytes, (plan, c.memory_analysis())
    kinds = _kinds(c.as_text())
    assert (kinds["gmm"], kinds["tgmm"], kinds["moe_token_sum"]) == (grouped, 4 * 6, 4 * 4), kinds


def test_windowed_flash_compiles_at_the_cell_s_shape(one_chip):
    """mellum2_12b_l4_ep4.t8192's window layers: (2, 8192, 32, 128) under a
    window of 1,024, forward and backward with the tiles `flash_tiles`
    picks, each call under the name that says its window."""
    windowed = lambda q, k, v: attention.flash_causal_attention(q, k, v, window=1024)
    fn = jax.value_and_grad(_loss(windowed), argnums=(0, 1, 2))
    text = jax.jit(fn).lower(*_qkv((2, 8192, 32, 128), one_chip)).compile().as_text()
    names = _CUSTOM_CALL.findall(text)
    assert len(names) == text.count("tpu_custom_call") == 2
    for kernel in ("flash_win1024_fwd", "flash_win1024_bwd_fused"):
        assert sum(kernel in n for n in names) == 1, names
    assert not any(k in n for k in KERNELS + ("bwd_dq", "bwd_dkv") for n in names)


def test_selected_flash_compiles_at_the_cell_s_shape(one_chip):
    """keye_vl2_30b_l4_ep8.t16384's layers: (1, 16384, 32, 128) over 2,048
    keys a query named by a packed mask, forward and backward with the tiles
    `flash_tiles` picks, each call under the name that says k."""
    mask = jax.ShapeDtypeStruct((1, 16384, 512), jnp.int32, sharding=one_chip)
    selected = lambda q, k, v, m, mt: attention.flash_selected_attention(
        q, k, v, m, mt, 2048).astype(jnp.float32).sum()
    fn = jax.value_and_grad(selected, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(*_qkv((1, 16384, 32, 128), one_chip), mask, mask).compile().as_text()
    names = _CUSTOM_CALL.findall(text)
    assert len(names) == text.count("tpu_custom_call") == 2
    for kernel in ("flash_sel2048_fwd", "flash_sel2048_bwd_fused"):
        assert sum(kernel in n for n in names) == 1, names
    assert not any(k in n for k in KERNELS + ("bwd_dq", "bwd_dkv") for n in names)


def test_indexer_compiles_at_the_cell_s_shape(one_chip):
    """The indexer's scores (16 heads of 64 against one key head over 16,384
    positions) and the exact top-2,048 of each row, as pallas calls under
    their names, and the mask's transpose beside them without a (T, T) array
    of words. The selection's loops end on what it counts (a `while` on a
    scalar reduced from the rows' counts, trip counts from the block's first
    row): Mosaic takes them at this shape. Its mask stays the call's first
    result, so the benchmark's shape function reads the bytes it read."""
    from bench import shapes, trace
    from ray_tpu.ops import indexer

    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    def select(q, k, w):
        mask, passes = indexer._pallas_select(indexer._pallas_scores(q, k, w, False), 2048, False)
        return mask, indexer.transpose_packed(mask), passes

    c = jax.jit(select).lower(shape((1, 16384, 16, 64), jnp.bfloat16),
                              shape((1, 16384, 64), jnp.bfloat16),
                              shape((1, 16384, 16), jnp.bfloat16)).compile()
    names = _CUSTOM_CALL.findall(c.as_text())
    assert sorted(re.sub(r"[.\d]+$", "", n) for n in names) == ["index_scores", "index_select"]
    # the scores, 1 GiB of float32, are the only array of that size
    assert GIB <= c.memory_analysis().temp_size_in_bytes < 1.25 * GIB
    call, = (line.strip() for line in c.as_text().splitlines()
             if re.match(r"\s*%index_select[.\d]* = ", line))
    kind = trace.kind(call)  # what the benchmark's trace reader makes of the event
    assert kind.startswith("index_select custom-call -> (s32[1,16384,512], s32["), kind
    assert shapes.load("index_select")(kind, "f32[1,16384,16384]") == (0, 536_870_912 + 33_554_432)
