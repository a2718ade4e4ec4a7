"""The plain references against models/ at a tiny size, float32 on the CPU:
the same parameters and batch give the same loss and the same gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families

TINY = {
    "gpt2": {"vocab_size": 257, "n_positions": 64, "n_embd": 64, "n_layer": 2, "n_head": 4,
             "layer_norm_epsilon": 1e-6},
    "llama": {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 160,
              "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "max_position_embeddings": 64, "rms_norm_eps": 1e-5,
              "rope_theta": 1e6, "sliding_window": None, "tie_word_embeddings": False},
}


def _model(family):
    if family == "gpt2":
        from ray_tpu.models import gpt2 as m
        return m.GPT2, m.loss_fn
    from ray_tpu.models import llama as m
    return m.Llama, m.loss_fn


@pytest.mark.parametrize("family", sorted(TINY))
def test_reference_agrees_with_models(family):
    sizes = TINY[family]
    fam = families.load(family)
    cfg = fam.build(sizes, "float32")
    model_cls, loss_fn = _model(family)
    model = model_cls(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, sizes["vocab_size"], (3, 33)), jnp.int32)
    idx, targets = tokens[:, :-1], tokens[:, 1:]
    params = model.init(jax.random.PRNGKey(1), idx)["params"]

    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(
            lambda p: loss_fn(model.apply({"params": p}, idx), targets))(params)
    got, got_g = jax.value_and_grad(
        lambda p: families.reference_loss(fam, p, idx, targets, sizes))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    flat_w, flat_g = jax.tree.leaves(want_g), jax.tree.leaves(got_g)
    scale = max(float(jnp.abs(a).max()) for a in flat_w)
    for a, b in zip(flat_w, flat_g):
        assert float(jnp.abs(a - b).max()) <= 2e-5 * scale


def test_query_blocks_change_nothing():
    """Attention in query blocks (long sequences) equals attention in one."""
    from bench.families import _plain

    q, k, v = (jax.random.normal(key, shape) for key, shape in zip(
        jax.random.split(jax.random.PRNGKey(0), 3),
        [(2, 64, 4, 8), (2, 64, 2, 8), (2, 64, 2, 8)]))
    whole = _plain.causal_attention(q, k, v)
    old = _plain.QUERY_BLOCK
    _plain.QUERY_BLOCK = 16
    try:
        blocks = _plain.causal_attention(q, k, v)
    finally:
        _plain.QUERY_BLOCK = old
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole), rtol=1e-6, atol=1e-6)
