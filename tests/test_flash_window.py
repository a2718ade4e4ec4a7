"""The windowed flash kernels (ops/attention.py, `FlashTiles.window`) in
interpret mode on the CPU: output, dq, dk and dv (one backward call, the
edge tiles masked on both sides of the band) against a plain float32
windowed attention, over windows that are and are not multiples of a tile,
the window of one key, and the window that is the causal call; the tile rule
for a windowed call; the XLA path with a window.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import FlashTiles, flash_tiles

F32 = jnp.float32


def _reference(q, k, v, window):
    """Query i sees keys j <= i with i - j < window; (B, T, H, D), float32,
    `highest`."""
    hi = jax.lax.Precision.HIGHEST
    t, d = q.shape[1], q.shape[3]
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=hi) / np.sqrt(d)
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    s = jnp.where((ahead >= 0) & (ahead < window), s, -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v, precision=hi)


def _with_grads(attn, q, k, v, w):
    o, vjp = jax.vjp(attn, q, k, v)
    return (o, *vjp(w))


def _operands(t, d=32, b=2, h=1, seed=0):
    return tuple(jax.random.normal(key, (b, t, h, d), F32)
                 for key in jax.random.split(jax.random.PRNGKey(seed + t), 4))


# (t, block_q, block_k, window), and where not one head of 32 a batch row,
# (heads a grid step, heads a row, their width); two batch rows in all
CASES = {
    "window_inside_one_tile": (512, 128, 128, 100),
    "window_of_one_key": (512, 128, 128, 1),
    "window_is_a_tile": (512, 128, 128, 128),
    "window_a_tile_and_one": (512, 128, 128, 129),
    "t_not_a_multiple_of_the_window": (512, 128, 128, 200),
    "t_not_a_multiple_of_the_window_wide": (384, 128, 128, 257),
    "block_q_over_block_k": (512, 256, 128, 300),
    "one_q_tile_four_k_tiles": (512, 512, 128, 100),
    "window_one_short_of_t": (512, 128, 128, 511),
    "edge_tiles_on_both_sides_two_heads": (768, 128, 128, 300, 2, 2, 32),
    "block_q_over_block_k_edges_cut_two_heads": (1024, 256, 128, 333, 2, 2, 32),
    "four_heads_of_32_a_vreg_two_groups": (512, 128, 128, 200, 4, 8, 32),
    "a_pair_of_64_a_vreg_two_groups": (512, 256, 128, 300, 2, 4, 64),
    "one_head_of_128_a_step_of_two": (384, 128, 128, 129, 1, 2, 128),
    "four_heads_of_128_a_row_each": (512, 128, 128, 100, 1, 4, 128),
}


@pytest.mark.parametrize("case", CASES)
def test_windowed_flash_matches_float32_reference(case):
    t, block_q, block_k, window, *heads = CASES[case]
    heads, h, d = heads or (1, 1, 32)
    q, k, v, w = _operands(t, d, h=h)
    tiles = FlashTiles(block_q, block_k, heads, window)
    with jax.default_matmul_precision("highest"):
        got = _with_grads(lambda q, k, v: attention._flash(q, k, v, None, None, tiles, True),
                          q, k, v, w)
    want = _with_grads(lambda q, k, v: _reference(q, k, v, window), q, k, v, w)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        # a window of one key has no gradient to q and k at all
        err = float(jnp.abs(a - b).max()) / max(float(jnp.abs(b).max()), 1.0)
        assert err <= 2e-5, (name, err)


@pytest.mark.parametrize("window", [512, 513, 10_000])
def test_window_of_the_sequence_or_more_is_the_causal_call(window):
    """Under its old name, with its old tiles, bit for bit."""
    t = 512
    q, k, v, w = _operands(t, d=64, b=1, h=2)
    assert flash_tiles(2, t, 64, F32, window) == flash_tiles(2, t, 64, F32)
    causal = attention.flash_causal_attention(q, k, v, interpret=True)
    windowed = attention.flash_causal_attention(q, k, v, window=window, interpret=True)
    np.testing.assert_array_equal(np.asarray(causal), np.asarray(windowed))
    text = str(jax.make_jaxpr(lambda q, k, v: attention.flash_causal_attention(
        q, k, v, window=window, interpret=True))(q, k, v))
    assert "flash_fwd" in text and "flash_win" not in text


def test_windowed_call_says_its_window_in_its_name():
    q, k, v, _ = _operands(512, d=64, b=1, h=2)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: attention.flash_causal_attention(
        q, k, v, window=200, interpret=True).sum(), (0, 1, 2)))(q, k, v))
    for name in ("flash_win200_fwd", "flash_win200_bwd_fused"):
        assert name in text
    # the per-kernel metrics of the causal calls match none of these, and
    # no call is named for one gradient alone
    for name in ("flash_fwd", "flash_bwd_fused_flash", "bwd_dq", "bwd_dkv"):
        assert name not in text


def test_tile_rule_for_a_windowed_call():
    causal = flash_tiles(32, 8192, 128, jnp.bfloat16)
    assert causal == FlashTiles(1024, 1024, 1, None)
    windowed = flash_tiles(32, 8192, 128, jnp.bfloat16, 1024)
    assert windowed.window == 1024
    # a tile of a windowed call is at most half the window: b + w + b scores
    # are visited a row where w are needed
    assert windowed.block_q == windowed.block_k <= 512
    assert 8192 % windowed.block_q == 0
    assert flash_tiles(32, 8192, 128, jnp.bfloat16, 100).block_q == 128
    with pytest.raises(ValueError):
        flash_tiles(32, 8192, 128, jnp.bfloat16, 0)


@pytest.mark.parametrize("window", [1, 7, 64, 100])
def test_xla_path_takes_the_window(window):
    q, k, v, _ = _operands(64, d=16, h=2)
    got = attention.xla_causal_attention(q, k, v, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_reference(q, k, v, window)),
                               rtol=2e-5, atol=2e-5)
    whole = attention.causal_attention(q, k, v, window=window)  # on the CPU: the XLA path
    np.testing.assert_allclose(np.asarray(whole), np.asarray(got), rtol=1e-6, atol=1e-6)
