"""models/kimi_linear.py's cell compiled for a described TPU v5e, as
tests/test_tpu_compile.py and with no chip: the delta rule's two kernels at
`kimi_linear_l5_ep32.t8192`'s shape, the head norm's pair there, the
cell's whole step, and a smaller step whose plan saves the delta rule's
outputs in some KDA layers and not in all."""

import jax
import jax.numpy as jnp
import pytest

from tests._tpu_compile import V5E_LIMIT, V5E_ROOM, _CUSTOM_CALL


def test_delta_rule_kernels_compile_at_the_cell_s_shape(one_chip):
    """kimi_linear_l5_ep32.t8192's KDA layers: 32 heads of 128 over (2, 8192)
    positions in chunks of 64, forward and backward, each a pallas call under
    its name, the gate and the heads' l2 norms made inside as the model asks;
    what the forward leaves for the backward is the chunk states, 537 MB."""
    from ray_tpu.ops import kda

    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
    head = shape((2, 8192, 32, 128), jnp.bfloat16)
    a_log, dt_bias = shape((32,), jnp.float32), shape((32, 128), jnp.float32)
    beta = shape((2, 8192, 32), jnp.float32)

    def loss(*ops):
        return kda.kda_gated(*ops, l2_eps=1e-6, interpret=False)[0].astype(jnp.float32).sum()

    c = jax.jit(jax.grad(loss, argnums=range(7))).lower(
        head, head, head, head, a_log, dt_bias, beta).compile()
    names = _CUSTOM_CALL.findall(c.as_text())
    assert len(names) == 2 and sum("kda_fwd" in n for n in names) == 1 \
        and sum("kda_bwd" in n for n in names) == 1, names
    states = 2 * 128 * 32 * 128 * 128 * 4
    assert states < c.memory_analysis().temp_size_in_bytes < 4 * states


def test_head_norm_s_pair_compiles_at_the_cell_s_shape(one_chip):
    """ops/kda_norm.py's two calls on o and z (2, 8192, 32 x 128) bf16, each a
    pallas call under its name, nothing kept between them but o and z, and
    no array of o's size beside the operands and results (the weight's
    gradient is (2, 8, 128) float32 partial sums)."""
    from ray_tpu.ops import kda_norm

    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
    wide, weight = shape((2, 8192, 4096), jnp.bfloat16), shape((128,), jnp.float32)

    def loss(o, z, w):
        return kda_norm.kda_norm(o, z, w, 1e-5, interpret=False).astype(jnp.float32).sum()

    c = jax.jit(jax.grad(loss, argnums=range(3))).lower(wide, wide, weight).compile()
    names = _CUSTOM_CALL.findall(c.as_text())
    assert sorted(n.split(".")[0] for n in names) == ["kda_norm_bwd"], names  # y is not asked for
    c = jax.jit(jax.value_and_grad(loss, argnums=range(3))).lower(wide, wide, weight).compile()
    names = _CUSTOM_CALL.findall(c.as_text())
    assert sorted(n.split(".")[0] for n in names) == ["kda_norm_bwd", "kda_norm_fwd"], names
    assert "[2,8192,32,128]" not in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 2 * 2 * 8192 * 4096 * 2  # y, and its cotangent


@pytest.mark.slow  # 60 s: the lowered step's tally and hash are tests/test_kimi_linear.py's, fast
@pytest.mark.timeout(600)
@pytest.mark.parametrize("layers,read_gib", [("all", 13.934), ("last", 13.647), ("first", 13.94)])
def test_kimi_linear_step_fits_the_chip_under_the_rule_s_limit(topo, monkeypatch, layers, read_gib):
    """kimi_linear_l5_ep32.t8192's whole step compiled for the described v5e:
    the rule takes the first rung and the delta rule's outputs in all four
    KDA layers at this shape (`all`: since PR 65, under the chip's own limit
    to within 64 MiB), and the program holds what my compile of PR 65 read,
    13.934 GiB: 0.37 over the reckoning (13.567). Under a limit of 15 GiB, the
    v5e's until then, it took the last three layers' (`last`: all four's did
    not fit beside 8.98 GiB of state under 13.5), and the program held what
    my compile of PR 63 read, 13.647
    GiB (PR 62's read 13.575: since PR 63 the latent layer cuts its
    projections on their weights, and the step compiled holds 0.07 GiB
    more): 0.705 over the reckoning, where every case before the rule took a
    rung by depth stood within 0.35 (this one's band is its own, stated
    below; the chip's allocator read 13.616 of PR 62's step). With the first
    three KDA layers saving in their place (`first`: no rule takes those) the
    same step holds 0.3 GiB more, which is why the rule takes the last: their
    backward runs first and lets go of them before most gradients exist. Four
    KDA layers run kda_bwd once, the
    ones that save kda_fwd once and the other twice, the head
    norm's pair after them (kda_norm_bwd once, kda_norm_fwd twice), the
    convolution's pair beside them, one layer the latent pair, the bias's
    update is part of the one program, and under `kda.conv` the compiled
    step has no float32 array of q's size in either layout (PR 55: the
    heads' l2 norms are the kernels'; what stays there beside the
    convolution's calls is the bf16 split into q, k and v and the three
    gradients put side by side), and since PR 60 under `kda.norm` and
    `kda.scan` no result shaped (2, 8192, 32, 128) in bf16 or float32: o goes
    from kda_fwd through the head norm's pair to W_o, and its cotangent back
    into kda_bwd, as (2, 8192, 4096)."""
    import builtins

    import numpy as np
    from jax.sharding import Mesh

    from ray_tpu.models import remat
    from ray_tpu.ops import attention, kda, kda_norm, short_conv
    from ray_tpu.parallel.train_step import TrainStep
    from ray_tpu.train._device_profile import scope_table
    from tests._tpu_compile import GIB, _kinds, _live_bytes, _step_args, cell_config

    for mod in (attention, kda, kda_norm, short_conv):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    limit = V5E_LIMIT if layers == "all" else 15 * GIB
    monkeypatch.setattr(remat, "chip_limit", lambda stream: limit)
    if layers == "first":  # the rule's layers taken from the other end: `plan` sorts them, the last first
        monkeypatch.setattr(remat, "sorted", lambda of, key=None, reverse=False: builtins.sorted(
            of, key=key), raising=False)
    cfg = cell_config("kimi_linear_l5_ep32")
    ts = TrainStep(cfg, Mesh(np.array(topo.devices[:1]), ("dp",)), telemetry=False)
    c = ts._step.lower(*_step_args(ts, (2, 8192))).compile()
    plan = remat.traced(cfg)
    assert plan.names == remat.FIRST_RUNG + ("moe_plan", "kda_out", "kda_states")
    depth = 4 if layers == "all" else 3
    assert plan.depth("kda_states") == depth
    # layers kda, kda, kda, mla, kda: the fourth makes none, and what a layer does not make it may save
    assert plan.saved_in("kda_states") == {"all": (True,) * 5,
                                           "last": (False, True, True, True, True),
                                           "first": (True, True, True, True, False)}[layers]
    live = _live_bytes(c)
    assert live < V5E_ROOM, c.memory_analysis()
    assert plan.reckoned_bytes <= plan.limit_bytes == (V5E_ROOM if layers == "all" else 13.5 * GIB)
    # 13.647 GiB where the rule reckons 12.94: with the outputs saved in the last 0, 1, 2 and 3
    # layers the compiler counted 12.30, 12.33, 12.95 and 13.575, the rule 12.18, 12.18, 12.32
    # and 12.94 (my compiles, PR 62; PR 63's step holds 0.07 more at depth 3, 0.06 with `first`):
    # past the first layer's the compiled step holds every saved
    # byte beside its fullest moment, where `Held.total` lets the gradients' room take them
    # (PERF.md section 7). The case is held to its reading, not to a wider band for all.
    # With all four layers' (PR 65) the compiler counted 13.934 where the rule reckons 13.567:
    # the first layer's 0.625 GiB cost the compiled step 0.29, so 0.37 over.
    assert abs(live / GIB - read_gib) <= 0.05, (plan, c.memory_analysis())
    assert (live - plan.reckoned_bytes <= 0.75 * GIB) == (layers != "first")
    assert (live - plan.reckoned_bytes <= 0.40 * GIB) == (layers == "all")
    kinds = _kinds(c.as_text())
    assert {k: n for k, n in kinds.items() if "kda" in k or "conv" in k or "flash" in k} == {
        "kda_fwd": 8 - depth, "kda_bwd": 4, "kda_norm_fwd": 8, "kda_norm_bwd": 4,
        "causal_conv_fwd": 8, "causal_conv_bwd": 4,
        "flash_mla_fwd": 1, "flash_mla_bwd_fused": 1}, kinds
    assert kinds["gmm"] and kinds["tgmm"]
    rows = scope_table(c.as_text())["rows"].values()
    conv = [kind for scope, _, _, _, kind in rows if "kda.conv" in scope]
    assert len(conv) > 12 and not [kind for kind in conv if any(
        shape in kind for shape in ("f32[2,8192,4096]", "f32[2,8192,32,128]",
                                    "f32[2048,8,32,128]"))], conv
    after = [kind for scope, _, _, _, kind in rows if "kda.norm" in scope or "kda.scan" in scope]
    assert len(after) > 100 and not [kind for kind in after if any(
        shape in kind for shape in ("f32[2,8192,32,128]", "bf16[2,8192,32,128]",
                                    "f32[2,8192,4096]"))], after


def test_a_step_at_a_depth_compiles_with_the_second_kda_fwd_where_nothing_is_saved(
        topo, monkeypatch):
    """Three KDA layers at the cell's widths on (1, 2048) tokens (40 s where
    the cell's five layers take 60 and are `slow`), under a limit with room
    for the delta rule's outputs in the last two layers and not in the first
    (models/remat.py's depths): the step compiled for the described v5e
    calls kda_fwd 3 + (3 - 2) times and kda_bwd three, and holds no more
    than the rule reckoned."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh

    from ray_tpu.models import kimi_linear, remat
    from ray_tpu.ops import attention, kda, kda_norm, short_conv
    from ray_tpu.parallel.train_step import TrainStep
    from tests._tpu_compile import GIB, _kinds, _live_bytes, _step_args, cell_config

    for mod in (attention, kda, kda_norm, short_conv):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(cell_config("kimi_linear_l5_ep32"), layer_types=("kda",) * 3)
    shape = remat.step_shape((1, 2048), {})
    whole = kimi_linear.remat_plan(cfg, shape, 64 * GIB)
    assert whole.depth("kda_states") == 3
    limit = next(limit for limit in range(whole.reckoned_bytes * 10 // 9, 0, -(1 << 24))
                 if kimi_linear.remat_plan(cfg, shape, limit).depth("kda_states") == 2)
    monkeypatch.setattr(remat, "chip_limit", lambda stream: limit)
    ts = TrainStep(cfg, Mesh(np.array(topo.devices[:1]), ("dp",)), telemetry=False)
    c = ts._step.lower(*_step_args(ts, (1, 2048))).compile()
    plan = remat.traced(cfg)
    assert plan.depth("kda_states") == 2 and plan.saved_in("kda_states") == (False, True, True)
    kinds = _kinds(c.as_text())
    assert {k: n for k, n in kinds.items() if k.startswith("kda")} == {
        "kda_fwd": 3 + (3 - 2), "kda_bwd": 3, "kda_norm_fwd": 6, "kda_norm_bwd": 3}, kinds
    assert -0.85 * GIB <= _live_bytes(c) - plan.reckoned_bytes <= 0.35 * GIB, (
        plan, c.memory_analysis())
