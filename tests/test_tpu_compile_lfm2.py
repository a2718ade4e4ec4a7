"""models/lfm2.py's cell compiled for a described TPU v5e, as
tests/test_tpu_compile.py and with no chip: the gated convolution's two
kernels at `lfm2_8b_a1b_l5_ep4.t8192`'s shape, and the cell's whole step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from ray_tpu.ops import attention
from ray_tpu.parallel.train_step import TrainStep
from tests._tpu_compile import (GIB, V5E_LIMIT, V5E_ROOM, _CUSTOM_CALL, _kinds, _live_bytes,
                                _step_args, cell_config)


def test_gated_conv_kernels_compile_at_the_cell_s_shape(one_chip):
    """lfm2_8b_a1b_l5_ep4.t8192's conv layers: three streams of 2,048 side by
    side over (2, 8192) tokens and 3 taps, forward and backward, each a pallas
    call under its name; nothing is left for the backward but the operands,
    and no temporary is as large as a stream."""
    from ray_tpu.ops import short_conv

    bcu = jax.ShapeDtypeStruct((2, 8192, 3 * 2048), jnp.bfloat16, sharding=one_chip)
    taps = jax.ShapeDtypeStruct((3, 2048), jnp.float32, sharding=one_chip)

    def loss(bcu, taps):
        y = short_conv.gated_short_conv(bcu, taps, interpret=False)
        return y.astype(jnp.float32).sum(), y  # y kept: the forward call is not dead code

    c = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).lower(bcu, taps).compile()
    names = _CUSTOM_CALL.findall(c.as_text())
    # wrapped by the transformations it went through, as the trace shows it
    assert len(names) == 2 and sum("gated_conv_fwd" in n for n in names) == 1 \
        and sum("gated_conv_bwd" in n for n in names) == 1, names
    assert c.memory_analysis().temp_size_in_bytes < 2 * 8192 * 2048 * 2


@pytest.mark.slow  # 100 s: the lowered step's hash is tests/test_kanana.py's LFM2_STEP, fast
@pytest.mark.timeout(600)
def test_lfm2_step_fits_the_chip_under_the_rule_s_limit(topo, monkeypatch):
    """lfm2_8b_a1b_l5_ep4.t8192's whole step compiled for the described v5e:
    the rule takes every rung at this shape, the program holds less than the
    14.12 GiB the rule is held to (13.5 until PR 65) and within the error the reckoning has shown
    of what it reckoned (tests/test_remat.py: 0.35 GiB under to 0.85 over),
    four conv layers run each kernel once, and the bias's update is part of
    the one program."""
    from ray_tpu.models import remat
    from ray_tpu.ops import short_conv

    for mod in (attention, short_conv):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat, "chip_limit", lambda stream: V5E_LIMIT)
    cfg = cell_config("lfm2_8b_a1b_l5_ep4")
    ts = TrainStep(cfg, Mesh(np.array(topo.devices[:1]), ("dp",)), telemetry=False)
    c = ts._step.lower(*_step_args(ts, (2, 8192))).compile()
    plan = remat.traced(cfg)
    assert set(plan.names) == set(remat.FIRST_RUNG) | {
        "conv_bcu", "conv_y", "mlp_up", "attn_q", "attn_k", "attn_v",
        "moe_plan", "moe_gate", "moe_up", "moe_out"}  # the expert layer's, since PR 45
    live = _live_bytes(c)
    assert live < V5E_ROOM, c.memory_analysis()
    assert -0.85 * GIB <= live - plan.reckoned_bytes <= 0.35 * GIB, (plan, c.memory_analysis())
    kinds = _kinds(c.as_text())
    conv = {k: n for k, n in kinds.items() if "gated_conv" in k}
    assert sorted(conv.values()) == [4, 4] and len(conv) == 2, kinds
    assert kinds["gmm"] and kinds["tgmm"]
    assert sum(n for k, n in kinds.items() if "flash" in k) == 2, kinds
