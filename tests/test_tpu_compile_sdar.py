"""models/sdar.py's cell compiled for a described TPU v5e (as
tests/test_tpu_compile.py, no chip): `sdar_30b_a3b_l5_ep8.t8192`'s
block-diffusion flash pair at the cell's shape, and its whole step."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from ray_tpu.ops import attention
from ray_tpu.parallel.train_step import TrainStep
from tests._tpu_compile import (GIB, V5E_LIMIT, V5E_ROOM, KERNELS, _CUSTOM_CALL, _kinds,
                                _live_bytes, _loss, _qkv, _step_args, cell_config)


def test_block_diffusion_flash_compiles_at_the_cell_s_shape(one_chip):
    """The cell's layers: a doubled stream (1, 16384, 32, 128) in blocks of 4,
    forward and backward with the tiles `flash_tiles` picks (1,024 square,
    cut into sub-tiles of 128), each call under the name that says L."""
    tiles = attention.flash_tiles(32, 16384, 128, "bfloat16", blocks=4)
    assert tiles == attention.FlashTiles(1024, 1024, 1, None, None, 128, 128, 4)
    blockwise = lambda q, k, v: attention.flash_causal_attention(q, k, v, blocks=4)
    fn = jax.value_and_grad(_loss(blockwise), argnums=(0, 1, 2))
    text = jax.jit(fn).lower(*_qkv((1, 16384, 32, 128), one_chip)).compile().as_text()
    names = _CUSTOM_CALL.findall(text)
    assert len(names) == text.count("tpu_custom_call") == 2
    for kernel in ("flash_bd4_fwd", "flash_bd4_bwd_fused"):
        assert sum(kernel in n for n in names) == 1, names
    assert not any(k in n for k in KERNELS + ("bwd_dq", "bwd_dkv") for n in names)


@pytest.mark.parametrize("shape,length", [((2, 1024, 4, 64), 1), ((1, 512, 2, 128), 16),
                                          ((1, 8192, 8, 128), 4)])
def test_block_diffusion_flash_compiles_at_the_smoke_s_shapes(one_chip, shape, length):
    """Tiles too small for a pass to cut (the forward's at 512, both at 256)
    take their masked tiles whole, by `_Blocks.whole`: Mosaic has to take
    that path too."""
    blockwise = lambda q, k, v: attention.flash_causal_attention(q, k, v, blocks=length)
    fn = jax.value_and_grad(_loss(blockwise), argnums=(0, 1, 2))
    text = jax.jit(fn).lower(*_qkv(shape, one_chip)).compile().as_text()
    names = _CUSTOM_CALL.findall(text)
    assert sorted(f"flash_bd{length}_" in n for n in names) == [True, True], names


@pytest.mark.slow  # a whole step of a routed cell at published widths
@pytest.mark.timeout(900)
def test_sdar_step_holds_what_the_rule_books(topo, monkeypatch):
    """sdar_30b_a3b_l5_ep8.t8192's whole step compiled for the described v5e:
    the program stands under the 14.12 GiB a step is held to (13.5 until PR 65) and within the
    error the reckoning has shown of what it reckoned; one flash pair, q's
    and k's prep pair and the expert layer's calls a layer."""
    from ray_tpu.models import remat

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat, "chip_limit", lambda stream: V5E_LIMIT)
    cfg = cell_config("sdar_30b_a3b_l5_ep8")
    ts = TrainStep(cfg, Mesh(np.array(topo.devices[:1]), ("dp",)), telemetry=False)
    c = ts._step.lower(*_step_args(ts, (1, 8192))).compile()
    plan = remat.traced(cfg)
    live = _live_bytes(c)
    print("plan", plan, "live GiB", live / GIB, c.memory_analysis())
    assert live < V5E_ROOM, c.memory_analysis()
    assert -0.85 * GIB <= live - plan.reckoned_bytes <= 0.35 * GIB, (plan, c.memory_analysis())
    kinds = _kinds(c.as_text())
    print(dict(kinds))
    assert (kinds["flash_bd4_fwd"], kinds["flash_bd4_bwd_fused"]) == (5, 5), kinds
