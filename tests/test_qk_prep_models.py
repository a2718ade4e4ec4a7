"""Three tiny models at heads of 128 whose loss and gradients through
ops/qk_prep.py's pair and the flash calls on rows (both in interpret mode) are
those of the same model on `LlamaAttention`'s plain lines: a dense one in
bf16, within bf16's rounding, and the two routed families (a window, a
selection, a gate, a layer normed and not turned) in float32, where their
experts are chosen alike."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import afmoe, mellum
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.loss import loss_fn
from ray_tpu.ops import attention
from ray_tpu.ops import qk_prep as qp

TINY = {
    # rotary alone, every layer causal
    "llama": lambda: LlamaConfig.tiny(n_head=2, n_kv_head=1, n_embd=256, block_size=256),
    # normed and rotary: a window, a selection of 128 keys, all keys; a table and a factor (YaRN)
    "mellum": lambda: mellum.MellumConfig.tiny(
        head_dim=128, n_head=2, n_kv_head=1, block_size=256, sliding_window=128, qk_norm=True,
        layer_types=(mellum.SLIDING, mellum.INDEXED, mellum.FULL), index_heads=4, index_dim=16,
        index_top_k=128, num_held=4),
    # gated; the full layer normed and not rotary
    "afmoe": lambda: afmoe.AfmoeConfig.tiny(head_dim=128, n_head=2, n_kv_head=1, block_size=256,
                                            sliding_window=128),
}


@pytest.mark.parametrize("family,dtype,tol", [
    ("llama", jnp.bfloat16, 2e-2), ("mellum", jnp.float32, 2e-4),
    # tier-1 stands near its limit: the gated family (its full layer normed and not turned,
    # which tests/test_qk_prep.py's `norm_alone` holds at the kernels) runs with -m slow
    pytest.param("afmoe", jnp.float32, 2e-4, marks=pytest.mark.slow)])
def test_a_tiny_model_through_the_pair_is_the_plain_lines_model(family, dtype, tol, monkeypatch):
    """Loss and every gradient of a tiny model at heads of 128 with the pair
    and the flash calls on rows (both in interpret mode) against the same
    model on the plain lines with XLA's attention: in float32, where the two
    routed families' experts are chosen alike, the dense one in bf16."""
    cfg = dataclasses.replace(TINY[family](), dtype=dtype)
    model = cfg.family.module(cfg, None)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 257)), jnp.int32)
    idx, targets = tokens[:, :-1], tokens[:, 1:]
    params = model.init(jax.random.PRNGKey(0), idx)["params"]

    def loss(params):
        logits, _ = model.apply({"params": params}, idx, mutable=list(cfg.family.sown))
        return loss_fn(logits, targets)

    plain = jax.value_and_grad(loss)(params)
    calls, real = [], qp.qk_prep
    # the layer asks the path by its name in the module; ops/moe.py and ops/indexer.py, which
    # have no interpret mode to be told of here, ask `_on_tpu` and a name bound at import
    monkeypatch.setattr(attention, "attention_path", lambda t, blocks=None: "flash")
    monkeypatch.setattr(qp, "qk_prep", lambda *a, **kw: (
        calls.append(kw.get("rep", 1)), real(*a, **kw, interpret=True))[1])
    monkeypatch.setattr(attention, "flash_attention_rows", functools.partial(
        attention.flash_attention_rows, interpret=True))
    pair = jax.value_and_grad(loss)(params)
    assert calls == [1, 2] * cfg.n_layer  # q's and k's call in every layer
    assert abs(pair[0] - plain[0]) < 0.1 * tol * abs(plain[0])
    flat = lambda tree: jnp.concatenate([x.reshape(-1) for x in jax.tree.leaves(tree)])
    got, want = flat(pair[1]), flat(plain[1])
    assert jnp.linalg.norm(got - want) < tol * jnp.linalg.norm(want)
