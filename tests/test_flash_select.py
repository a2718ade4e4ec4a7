"""ops/indexer.py and the selected flash kernels of ops/attention.py on the
CPU: the indexer's scores and the exact top-k in interpret mode against the
XLA path and a selection worked row by row; the three selected kernels in
interpret mode against masked einsum-softmax attention, forward and both
gradients, at k < T, k = T and k > T; the tile rule at the benchmark's
shape; the names the calls carry.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention, indexer


def _index_operands(b=2, t=256, heads=4, dim=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, t, heads, dim)), jax.random.normal(ks[1], (b, t, dim)),
            jax.random.normal(ks[2], (b, t, heads)))


def _by_hand(scores, top_k):
    """Row t keeps the min(top_k, t + 1) largest of scores[t, :t + 1], ties
    to the lower position: a stable sort of each row."""
    scores = np.asarray(scores)
    sel = np.zeros(scores.shape, bool)
    for b in range(scores.shape[0]):
        for t in range(scores.shape[1]):
            row = scores[b, t, :t + 1]
            sel[b, t, np.lexsort((np.arange(t + 1), -row))[:min(top_k, t + 1)]] = True
    return sel


@pytest.mark.parametrize("t", [128, 256, 512])
def test_index_scores_kernel_agrees_with_the_einsum(t):
    q, k, w = _index_operands(t=t)
    want = indexer._xla_scores(q, k, w)
    got = indexer._pallas_scores(q, k, w, True)
    causal = np.tril(np.ones((t, t), bool))
    np.testing.assert_allclose(np.where(causal, got, 0), np.where(causal, want, 0),
                               rtol=1e-5, atol=1e-5)
    by_hand = sum(np.asarray(w)[..., h, None] * np.maximum(
        np.einsum("bte,bse->bts", np.asarray(q)[:, :, h], np.asarray(k)), 0) for h in range(4))
    np.testing.assert_allclose(np.where(causal, want, 0), np.where(causal, by_hand, 0),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("path", ["xla", "kernel"])
@pytest.mark.parametrize("top_k", [1, 48, 255, 256, 1000])
def test_selection_is_exact(path, top_k):
    scores = indexer._xla_scores(*_index_operands())
    select = indexer._xla_select if path == "xla" else (
        lambda s, k: indexer._pallas_select(s, k, True))
    sel = np.asarray(indexer.unpack(select(scores, top_k)))
    assert (sel == _by_hand(scores, top_k)).all()
    assert (sel.sum(-1) == np.minimum(np.arange(256) + 1, top_k)).all()


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_ties_go_to_the_lower_position(path):
    """Scores on a coarse grid tie by the dozen at the k-th value, -0.0 among
    them, and a row of one value keeps its first k."""
    scores = jnp.round(indexer._xla_scores(*_index_operands(seed=1))) * jnp.where(
        jnp.arange(256) % 3 == 0, -0.0, 1.0)
    scores = scores.at[0, 200].set(2.5)
    select = indexer._xla_select if path == "xla" else (
        lambda s, k: indexer._pallas_select(s, k, True))
    sel = np.asarray(indexer.unpack(select(scores, 40)))
    assert (sel == _by_hand(scores, 40)).all()
    assert sel[0, 200, :40].all() and not sel[0, 200, 40:].any()


@pytest.mark.parametrize("t", [256, 512, 1024])
def test_packed_mask_and_its_transpose(t):
    width = indexer.mask_width(t)
    assert width == 128 and indexer.mask_width(16384) == 512
    mask = jax.random.randint(jax.random.PRNGKey(0), (2, t, width), 0, 1 << (t // width),
                              jnp.int32)
    seen = np.asarray(indexer.unpack(mask))
    assert seen.shape == (2, t, t)
    assert seen[1, 5, 3 * width // 2] == bool((int(mask[1, 5, width // 2]) >> 1) & 1)
    turned = np.asarray(indexer.unpack(indexer.transpose_packed(mask)))
    assert (turned == seen.swapaxes(1, 2)).all()
    with pytest.raises(ValueError):
        indexer.mask_width(t + 64)


def _attention_case(t, top_k, dtype=jnp.float32, b=2, h=4, d=32):
    ks = jax.random.split(jax.random.PRNGKey(t + top_k), 3)
    q, k, v = (jax.random.normal(key, (b, h, t, d), dtype) for key in ks)
    mask = indexer._xla_select(indexer._xla_scores(*_index_operands(b, t, seed=top_k)), top_k)
    return (q, k, v), mask, indexer.transpose_packed(mask)


def _value_and_grads(fn, operands):
    weight = jnp.cos(jnp.arange(operands[0].shape[-1], dtype=jnp.float32))
    return jax.value_and_grad(
        lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * weight).sum(), (0, 1, 2))(*operands)


@pytest.mark.parametrize("t,top_k", [(256, 32), (256, 200), (512, 64), (512, 300), (1024, 128)])
def test_selected_kernels_agree_with_masked_attention(t, top_k):
    operands, mask, mask_t = _attention_case(t, top_k)
    want, want_grads = _value_and_grads(
        lambda q, k, v: attention.xla_selected_attention(q, k, v, mask), operands)
    got, got_grads = _value_and_grads(
        lambda q, k, v: attention.flash_selected_attention(
            q, k, v, mask, mask_t, top_k, interpret=True), operands)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    for g, w, name in zip(got_grads, want_grads, "qkv"):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=2e-5, err_msg=f"d{name}")
    # and the masked einsum is attention over the selected keys alone
    seen = np.asarray(indexer.unpack(mask))
    q, k, v = (np.asarray(x, np.float64) for x in operands)
    s = np.where(seen[:, None], np.einsum("bhtd,bhsd->bhts", q, k) / np.sqrt(q.shape[-1]), -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    by_hand = np.einsum("bhts,bhsd->bhtd", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(
        attention.xla_selected_attention(*operands, mask), by_hand, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("top_k", [256, 300])
def test_a_selection_of_every_key_is_the_causal_call(top_k):
    """k = T and k > T: every key before a query is selected, the mask says
    nothing, and the call is the causal one, under its own name."""
    operands, mask, mask_t = _attention_case(256, top_k)
    assert np.asarray(indexer.unpack(mask))[0].tolist() == np.tril(np.ones((256, 256), bool)).tolist()
    want, want_grads = _value_and_grads(attention.xla_causal_attention, operands)
    selected = lambda q, k, v: attention.flash_selected_attention(
        q, k, v, mask, mask_t, top_k, interpret=True)
    got, got_grads = _value_and_grads(selected, operands)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=2e-5)
    text = jax.jit(jax.grad(lambda q, k, v: selected(q, k, v).sum(), (0, 1, 2))).lower(
        *operands).as_text()
    assert "flash_sel" not in text


def test_selected_kernels_in_bf16():
    operands, mask, mask_t = _attention_case(512, 96, jnp.bfloat16)
    want, want_grads = _value_and_grads(
        lambda q, k, v: attention.xla_selected_attention(
            *(x.astype(jnp.float32) for x in (q, k, v)), mask), operands)
    got, got_grads = _value_and_grads(
        lambda q, k, v: attention.flash_selected_attention(
            q, k, v, mask, mask_t, 96, interpret=True), operands)
    assert abs(float(got) - float(want)) < 2e-2 * abs(float(want)) + 1.0
    for g, w in zip(got_grads, want_grads):
        assert float(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)).max()) < 0.1


def test_tile_rule_of_a_selected_call():
    """At the benchmark's shape the tile a step owns is the causal call's and
    the tile it loops over one bit of the mask's words; a selection as long
    as the sequence is the causal call; heads of a grid step share a mask."""
    tiles = attention.flash_tiles(32, 16384, 128, jnp.bfloat16, select=2048)
    assert tiles == attention.FlashTiles(1024, 512, 1, None, 2048)
    assert attention._select_vmem_bytes(tiles, 16384, 128, 2) < attention._VMEM_BUDGET
    assert attention.flash_tiles(32, 16384, 128, jnp.bfloat16, select=16384) == \
        attention.flash_tiles(32, 16384, 128, jnp.bfloat16)
    small = attention.flash_tiles(8, 256, 32, jnp.float32, select=64)
    assert (small.block_q, small.block_k, small.select) == (256, 128, 64)
    with pytest.raises(ValueError):
        attention.flash_tiles(8, 1024, 64, jnp.bfloat16, window=128, select=64)


def test_calls_carry_the_selection_in_their_names():
    operands, mask, mask_t = _attention_case(256, 32)
    fn = lambda q, k, v: attention.flash_selected_attention(
        q, k, v, mask, mask_t, 32, interpret=True).sum()
    jaxpr = str(jax.make_jaxpr(jax.grad(fn, (0, 1, 2)))(*operands))
    names = set(re.findall(r"name=(\w+)", jaxpr))
    assert {"flash_sel32_fwd", "flash_sel32_bwd_dq", "flash_sel32_bwd_dkv"} <= names
    assert not names & {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    q, k, w = _index_operands()
    jaxpr = str(jax.make_jaxpr(lambda q, k, w: indexer._pallas_select(
        indexer._pallas_scores(q, k, w, True), 32, True))(q, k, w))
    assert {"index_scores", "index_select"} <= set(re.findall(r"name=(\w+)", jaxpr))
