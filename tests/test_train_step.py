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
from ray_tpu.parallel.mesh import filter_spec_for_mesh, make_mesh, single_axis_mesh
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
