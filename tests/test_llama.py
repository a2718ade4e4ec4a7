"""Llama model family tests on the virtual 8-device CPU mesh.

Same semantics-preservation contract as test_train_step.py: every parallelism
axis combination must give the single-device loss trajectory, because the
shardings only move FLOPs. Plus unit checks for RoPE and GQA math."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.layers import apply_rope, rope_angles
from ray_tpu.models.llama import LlamaConfig, forward, init_params, num_params
from ray_tpu.parallel.mesh import make_mesh
from ray_tpu.parallel.train_step import TrainStep

CFG = LlamaConfig.tiny(use_flash_attention=False, dtype=jnp.float32)


def _batch(rng, B=8, T=64):
    idx = rng.integers(0, CFG.vocab_size, size=(B, T)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1)
    return {"idx": jnp.asarray(idx), "targets": jnp.asarray(tgt)}


def _run(mesh, steps=4):
    ts = TrainStep(CFG, mesh, learning_rate=5e-3)
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
        {"tp": 4, "dp": 2},
        {"sp": 4, "dp": 2},
        {"dp": 2, "fsdp": 2, "tp": 2},
    ],
)
def test_parallel_matches_single_device(axes, baseline):
    base_losses, _ = baseline
    losses, _ = _run(make_mesh(axes))
    np.testing.assert_allclose(losses, base_losses, rtol=2e-3, atol=2e-3)
    assert losses[-1] < losses[0]


def test_rope_rotation_properties():
    # rotating by position p then querying against position p+k depends only
    # on k (relative-position property of RoPE)
    D = 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 4, 1, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 4, 1, D)), jnp.float32)
    ang0 = rope_angles(D, 10000.0, jnp.arange(4))
    ang5 = rope_angles(D, 10000.0, jnp.arange(4) + 5)
    dots0 = jnp.einsum("bthd,bshd->ts", apply_rope(q, ang0), apply_rope(k, ang0))
    dots5 = jnp.einsum("bthd,bshd->ts", apply_rope(q, ang5), apply_rope(k, ang5))
    np.testing.assert_allclose(dots0, dots5, rtol=1e-4, atol=1e-4)
    # norm preservation
    np.testing.assert_allclose(
        jnp.linalg.norm(apply_rope(q, ang0)), jnp.linalg.norm(q), rtol=1e-5
    )


def test_pos_offset_matches_full_sequence():
    # forward of the second half with pos_offset equals the second half of the
    # full forward when attention is bidirectionally blocked... for a causal
    # model the first half context differs, so check the embedding-free path:
    # RoPE angles themselves.
    D = 8
    full = rope_angles(D, 1e4, jnp.arange(16))
    shifted = rope_angles(D, 1e4, jnp.arange(8) + 8)
    np.testing.assert_allclose(full[8:], shifted, rtol=0, atol=0)


def test_gqa_matches_mha_when_kv_repeated():
    # a GQA model with n_kv_head == n_head is plain MHA; with fewer kv heads
    # the output must still be finite and the param count smaller
    cfg_mha = LlamaConfig.tiny(n_kv_head=4, use_flash_attention=False,
                               dtype=jnp.float32)
    cfg_gqa = LlamaConfig.tiny(n_kv_head=2, use_flash_attention=False,
                               dtype=jnp.float32)
    p_mha = init_params(cfg_mha)
    p_gqa = init_params(cfg_gqa)
    assert num_params(p_gqa) < num_params(p_mha)
    idx = jnp.zeros((2, 16), jnp.int32)
    out = forward(cfg_gqa, p_gqa, idx)
    assert out.shape == (2, 16, cfg_gqa.vocab_size)
    assert bool(jnp.isfinite(out).all())


def test_state_is_sharded():
    mesh = make_mesh({"fsdp": 4, "tp": 2})
    ts = TrainStep(CFG, mesh)
    state = ts.init(jax.random.PRNGKey(0))
    kernel = state["params"]["h_0"]["attn"]["wq"]["kernel"]
    assert len(kernel.sharding.device_set) == 8
    mu = state["opt_state"][1][0].mu["h_0"]["attn"]["wq"]["kernel"]
    assert mu.sharding == kernel.sharding


@pytest.mark.parametrize("saved", ["first_rung", "every_name"])
def test_residuals_saved_across_remat_change_no_loss_and_no_gradient(saved, monkeypatch):
    """A step whose blocks save named residuals across their remat
    (models/remat.py) and the same step with `policy=None`, the kernel in
    interpret mode in both, in float32: the loss and every gradient are the
    same in every bit, since a saved value and a recomputed one are the same
    value. (In bf16 XLA rounds a value where its fusions end, and two
    programs that fuse differently differ in the last bits.) `every_name`:
    a chip with room for all of REMAT_RUNGS."""
    from ray_tpu.models import llama, remat
    from ray_tpu.ops.attention import flash_causal_attention

    attn = functools.partial(flash_causal_attention, interpret=True)  # (B, T, H, D)

    cfg = LlamaConfig.tiny(attn_fn=attn, block_size=256, dtype=jnp.float32)
    batch = _batch(np.random.default_rng(3), B=2, T=256)
    if saved == "every_name":
        monkeypatch.setattr(remat, "chip_limit", lambda stream: 16 * remat.GIB)
    model = llama.Llama(cfg)
    want_names = remat.FIRST_RUNG + (
        tuple(n for names, _ in llama.REMAT_RUNGS for n in names) if saved == "every_name" else ())
    params = model.init(jax.random.PRNGKey(0), batch["idx"])["params"]

    def loss_and_grads():
        return jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(
            model.apply({"params": p}, batch["idx"]), batch["targets"])))(params)

    loss, grads = loss_and_grads()
    assert remat.traced(cfg).names == want_names
    monkeypatch.setattr(remat, "block_policy", lambda *a: (None,) * cfg.n_layer)
    plain_loss, plain_grads = loss_and_grads()
    assert np.isfinite(float(loss)) and float(loss) == float(plain_loss)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(plain_grads)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
