"""The one next-token loss (models/loss.py) against the form it replaced,
kept here: `log_softmax` over the whole row, then the target's element."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.loss import loss_fn


def _old_loss(logits, targets):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -ll.mean()


def _random(rng):
    return rng.normal(size=(4, 16, 1000)) * 3, rng.integers(0, 1000, size=(4, 16))


def _target_holds_the_mass(rng):
    logits, targets = _random(rng)
    logits[0, 0, targets[0, 0]] = 40.0  # that row's loss is 0 in float32
    logits[1, 2, targets[1, 2]] = 12.0
    return logits, targets


def _plus_minus_80(rng):
    return rng.choice([-80.0, 80.0], size=(4, 16, 1000)), rng.integers(0, 1000, size=(4, 16))


def _whole_vocabulary(rng):  # gpt2's 50,257: no multiple of 128
    targets = rng.integers(0, 50257, size=(2, 8))
    targets[0, :2] = (0, 50256)
    return rng.normal(size=(2, 8, 50257)) * 2, targets


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", [_random, _target_holds_the_mass, _plus_minus_80, _whole_vocabulary],
                         ids=lambda f: f.__name__.strip("_"))
def test_loss_is_the_log_softmax_form_s_value_and_gradient(case, dtype):
    logits, targets = case(np.random.default_rng(7))
    logits, targets = jnp.asarray(logits, dtype), jnp.asarray(targets, jnp.int32)
    got, got_g = jax.jit(jax.value_and_grad(loss_fn))(logits, targets)
    want, want_g = jax.jit(jax.value_and_grad(_old_loss))(logits, targets)
    assert got.dtype == jnp.float32 and got_g.dtype == dtype
    assert np.isfinite(float(got)) and np.isfinite(np.asarray(got_g, np.float32)).all()
    assert abs(float(got) - float(want)) <= 1e-6 * abs(float(want))
    got_g, want_g = np.asarray(got_g, np.float32), np.asarray(want_g, np.float32)
    # a bf16 gradient is rounded where it leaves the loss, once in each form
    tol = 2e-3 if dtype == jnp.bfloat16 else 1e-6
    assert np.abs(got_g - want_g).max() <= tol * np.abs(want_g).max()


def test_every_family_s_step_runs_the_one_loss():
    """The next-token objective (`Family.objective` of every configuration's
    step but the diffusion family's), the pipeline schedule and the routed
    GPT-2 call the same function object, and `gpt2` and `llama` hand out that
    one under its old name (bench/tests/ asks them for it)."""
    from ray_tpu import models
    from ray_tpu.models import gpt2, gpt2_moe, llama, sdar
    from ray_tpu.parallel import pipeline

    for module in (gpt2, gpt2_moe, llama, pipeline, models):
        assert module.loss_fn is loss_fn, module.__name__
    for family in (gpt2, gpt2_moe, llama):
        config = next(v for v in vars(family).values() if hasattr(v, "family"))
        assert config.family.objective is models.next_token_objective
    assert sdar.SDARConfig.family.objective is sdar.objective
