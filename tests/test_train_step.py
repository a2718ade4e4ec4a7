"""Sharded train-step tests on the virtual 8-device CPU mesh.

Checks every parallelism axis combination gives the same loss trajectory as
the single-device step (the shardings must be semantics-preserving — XLA only
changes where the FLOPs run)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from ray_tpu.models.gpt2 import GPT2Config
from ray_tpu.ops import attention
from ray_tpu.parallel.mesh import (
    Collective, batch_sharding, collective_tally, filter_spec_for_mesh, make_mesh,
    single_axis_mesh, stream_sharding)
from ray_tpu.parallel.train_step import TrainStep, attn_for_mesh

CFG = GPT2Config.tiny(use_flash_attention=False, dtype=jnp.float32)


def _batch(rng, B=8, T=64):
    idx = rng.integers(0, CFG.vocab_size, size=(B, T)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1)
    return {"idx": jnp.asarray(idx), "targets": jnp.asarray(tgt)}


def _run(mesh, steps=3):
    ts = TrainStep(CFG, mesh, learning_rate=1e-3)
    state = ts.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(steps):
        batch = ts.shard_batch(_batch(rng))
        state, m = ts.step(state, batch)
        losses.append(float(m["loss"]))
    return losses, state


@pytest.fixture(scope="module")
def baseline():
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    return _run(mesh)


@pytest.mark.parametrize(
    "axes",
    [
        {"dp": 8},
        {"fsdp": 8},
        {"dp": 2, "fsdp": 4},
        {"tp": 8},
        {"dp": 2, "tp": 4},
        {"sp": 8},
        {"dp": 2, "sp": 4},
        {"dp": 2, "fsdp": 2, "tp": 2},
        {"dp": 2, "sp": 2, "tp": 2},
    ],
)
def test_parallel_matches_single_device(axes, baseline):
    base_losses, _ = baseline
    losses, _ = _run(make_mesh(axes))
    np.testing.assert_allclose(losses, base_losses, rtol=2e-3, atol=2e-3)
    assert losses[-1] < losses[0]  # it actually learns


def test_flash_config_under_mesh_matches_single_device(baseline):
    """use_flash_attention=True on a multi-device mesh goes through
    attn_for_mesh (shard_map over batch and heads); same trajectory."""
    import dataclasses

    cfg = dataclasses.replace(CFG, use_flash_attention=True)
    ts = TrainStep(cfg, make_mesh({"dp": 2, "fsdp": 2, "tp": 2}), learning_rate=1e-3)
    assert ts.model.config.attn_fn is not None
    assert TrainStep(cfg, single_axis_mesh("dp", jax.devices()[:1])
                     ).model.config.attn_fn is None
    state = ts.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(3):
        state, m = ts.step(state, ts.shard_batch(_batch(rng)))
        losses.append(float(m["loss"]))
    np.testing.assert_allclose(losses, baseline[0], rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("axes", [{"dp": 2, "tp": 4}, {"fsdp": 4, "tp": 2}, {"dp": 8}])
def test_attn_for_mesh_matches_reference(axes):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((8, 64, 4, 16)), jnp.float32)
               for _ in range(3))
    got = jax.jit(attn_for_mesh(make_mesh(axes)))(q, k, v)
    np.testing.assert_allclose(got, attention.causal_attention(q, k, v), atol=2e-5)


def test_attention_path_is_decided_from_backend_and_shape(monkeypatch):
    assert attention.attention_path(1024) == "xla"  # these tests run on CPU
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert [attention.attention_path(t) for t in (128, 256, 1000, 1024)] == [
        "xla", "flash", "xla", "flash"]


def test_on_tpu_does_not_swallow_a_backend_failure(monkeypatch):
    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        attention.attention_path(1024)


@pytest.mark.parametrize("spec, shape, want", [
    (P("tp", "fsdp"), (50257, 768), P(None, "fsdp")),   # GPT-2's V on tp=2
    (P("tp", "fsdp"), (50304, 768), P("tp", "fsdp")),
    (P(("dp", "fsdp"), None), (6, 8), P(("dp",), None)),  # 6 % (2*2) != 0
    (P("fsdp", "tp"), None, P("fsdp", "tp")),             # no shape: mesh only
    (P("sp", "ep"), (8, 8), P(None, None)),               # axes the mesh lacks
])
def test_filter_spec_drops_axes_that_do_not_divide(spec, shape, want):
    mesh = make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    assert filter_spec_for_mesh(spec, mesh, shape) == want


def test_state_is_sharded():
    mesh = make_mesh({"fsdp": 4, "tp": 2})
    ts = TrainStep(CFG, mesh)
    state = ts.init(jax.random.PRNGKey(0))
    kernel = state["params"]["h_0"]["attn"]["c_attn"]["kernel"]
    # column-parallel qkv kernel: sharded fsdp x tp
    assert len(kernel.sharding.device_set) == 8
    # adam mu follows the same sharding as the param
    mu = state["opt_state"][1][0].mu["h_0"]["attn"]["c_attn"]["kernel"]
    assert mu.sharding == kernel.sharding


def test_donation_and_step_counter():
    mesh = single_axis_mesh("dp")
    ts = TrainStep(CFG, mesh)
    state = ts.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    state, _ = ts.step(state, ts.shard_batch(_batch(rng)))
    state, _ = ts.step(state, ts.shard_batch(_batch(rng)))
    assert int(state["step"]) == 2


def test_multi_step_matches_repeated_step():
    """One lax.scan dispatch of k steps must match k single-step calls
    (the dispatch-amortized path used on TPU)."""
    mesh = single_axis_mesh("dp")
    rng = np.random.default_rng(3)
    batch_np = _batch(rng)

    ts1 = TrainStep(CFG, mesh, learning_rate=1e-3)
    s1 = ts1.init(jax.random.PRNGKey(0))
    b1 = ts1.shard_batch(batch_np)
    for _ in range(4):
        s1, m1 = ts1.step(s1, b1)

    ts2 = TrainStep(CFG, mesh, learning_rate=1e-3)
    s2 = ts2.init(jax.random.PRNGKey(0))
    b2 = ts2.shard_batch(batch_np)
    s2, m2 = ts2.multi_step(s2, b2, 4)

    assert m2["loss"].shape == (4,)  # stacked per-step metrics
    np.testing.assert_allclose(float(m2["loss"][-1]), float(m1["loss"]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(s1["params"]["wte"]["embedding"]),
        np.asarray(s2["params"]["wte"]["embedding"]),
        rtol=1e-5, atol=1e-5,
    )
    assert int(s2["step"]) == 4
    # second call reuses the compiled scan (cached dispatch path)
    s2, m2 = ts2.multi_step(s2, b2, 4)
    assert int(s2["step"]) == 8


# ---- the residual stream's sharding (parallel/mesh.py:stream_sharding)


@pytest.mark.parametrize("axes, want", [
    ({"fsdp": 4}, P(("fsdp",), None, None)),
    ({"dp": 2, "tp": 2}, P(("dp",), None, None)),            # hidden whole: Megatron's stream
    ({"dp": 2, "fsdp": 2, "sp": 2}, P(("dp", "fsdp"), "sp", None)),
    ({"tp": 4}, P(None, None, None)),
    ({"dp": 1, "fsdp": 1}, None),                            # one device: nothing to say
])
def test_stream_sharding_is_the_batch_s_split_with_the_hidden_dimension_whole(axes, want):
    n = int(np.prod(list(axes.values())))
    mesh = make_mesh(axes, devices=jax.devices()[:n])
    got = stream_sharding(mesh)
    if want is None:
        assert got is None
        return
    assert got.spec == want and got.mesh is mesh
    assert tuple(got.spec)[:2] == tuple(batch_sharding(mesh).spec)


def _family(name):
    from ray_tpu.models.gpt2_moe import GPT2MoEConfig
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.models.mellum import MellumConfig

    kw = dict(use_flash_attention=False)
    return {"gpt2": lambda: GPT2Config.tiny(**kw),
            "gpt2_moe": lambda: GPT2MoEConfig.tiny_moe(**kw),
            "llama": lambda: LlamaConfig.tiny(**kw),
            "mellum": lambda: MellumConfig.tiny(num_held=4, **kw)}[name]()


FAMILIES = ("gpt2", "gpt2_moe", "llama", "mellum")


def _lowered_step(cfg, mesh, B=8, T=64):
    ts = TrainStep(cfg, mesh, telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((B, T), jnp.int32)
    return ts._step.lower(state, {"idx": tok, "targets": tok}).as_text()


@pytest.mark.parametrize("family", FAMILIES)
def test_one_device_step_lowers_as_it_does_without_the_stream_s_pin(family, monkeypatch):
    """On a one-device mesh the rule is its own empty case: the step lowers
    to the same text as with `pin` taken out of the model, so the one-chip
    cells run the program they ran. A mesh of more devices does say where
    the stream lives."""
    import importlib

    cfg = _family(family)
    one = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    with_hook = _lowered_step(cfg, one)
    assert "stablehlo" in with_hook and "sharding_constraint" not in with_hook
    assert "sharding_constraint" in _lowered_step(cfg, make_mesh({"dp": 2}, devices=jax.devices()[:2]))
    for module in ("gpt2", "gpt2_moe", "llama", "mellum"):
        monkeypatch.setattr(
            importlib.import_module(f"ray_tpu.models.{module}"), "pin", lambda x, sharding: x)
    assert _lowered_step(cfg, one) == with_hook
    assert "sharding_constraint" not in _lowered_step(
        cfg, make_mesh({"dp": 2}, devices=jax.devices()[:2]))  # the hook was what said it


@pytest.mark.parametrize("family", ("gpt2", "llama"))
def test_fsdp_step_gathers_weights_and_leaves_the_activations(family):
    """Four CPU devices, {"fsdp": 4}: the compiled step gathers kernels and
    sums their gradients; no sum or gather leaves an array with the whole
    batch's (B, T) in front on a device. Unpinned, the partitioner
    all-reduces the MLP's intermediates and gathers the stream (the same
    choice as on the TPU at the benchmark's widths: tests/test_tpu_compile.py)."""
    B, T = 8, 64
    cfg = _family(family)
    ts = TrainStep(cfg, make_mesh({"fsdp": 4}, devices=jax.devices()[:4]), telemetry=False)
    state = ts.init(jax.random.PRNGKey(0))
    idx = np.zeros((B, T), np.int32)
    batch = ts.shard_batch({"idx": idx, "targets": idx})
    tally = collective_tally(ts._step.lower(state, batch).compile().as_text())
    moved = {str(c): n for c, n in tally.items()
             if c.kind in ("all-reduce", "all-gather") and c.shape[:2] == (B, T)
             and c.dtype.startswith(("f", "bf"))}
    assert not moved, moved
    d = cfg.n_embd
    gathered = {c.shape for c in tally if c.kind == "all-gather"}
    assert {(d, d), (d, 3 * d) if family == "gpt2" else (d, cfg.mlp_dim)} <= gathered, gathered


def test_collective_tally_reads_a_compiled_program_s_text():
    """By kind and result shape; one channel once (the TPU compiler writes an
    overlapped collective into every fusion that carries a stage of it); a
    `-start` is its `-done`'s; a collective of several arrays once for each."""
    text = """
  %all-gather.1 = bf16[4096,14336]{1,0:T(8,128)(2,1)} all-gather(%p.1), channel_id=15, replica_groups=[1,4]<=[4], dimensions={0}
  %all-gather.2 = bf16[4096,14336]{1,0:T(8,128)(2,1)S(1)} all-gather(%p.2), channel_id=15, replica_groups=[1,4]<=[4], dimensions={0}
  %all-gather.3 = bf16[4096,14336]{1,0} all-gather(%p.3), channel_id=17, dimensions={0}
  %ar = (f32[]{:T(128)}, f32[4096]{0}) all-reduce(%a, %b), channel_id=3, to_apply=%add
  %cps = (bf16[96,1024]{1,0}, bf16[96,1024]{1,0}, u32[], u32[]) collective-permute-start(%x), channel_id=9
  %cpd = bf16[96,1024]{1,0} collective-permute-done(%cps)
  %a2a = bf16[4,1,8192,1024]{3,2,1,0} all-to-all(%y), channel_id=21, dimensions={0}
  %fusion.7 = bf16[8192,14336]{1,0} fusion(%all-gather.3), kind=kOutput, calls=%fused
"""
    tally = collective_tally(text)
    assert {str(c): n for c, n in tally.items()} == {
        "all-gather bf16[4096,14336]": 2, "all-reduce f32[]": 1, "all-reduce f32[4096]": 1,
        "collective-permute bf16[96,1024]": 1, "all-to-all bf16[4,1,8192,1024]": 1}
    gather = Collective("all-gather", "bf16", (4096, 14336))
    assert gather.nbytes == 4096 * 14336 * 2 and Collective("all-gather", "pred", (4, 8)).nbytes == 32
