"""The windowed flash kernels (ops/attention.py, `FlashTiles.window`) in
interpret mode on the CPU: output, dq, dk and dv (one backward call, the
edge tiles masked on both sides of the band) against a plain float32
windowed attention, over windows that are and are not multiples of a tile,
the window of one key, and the window that is the causal call; the same with
the masked tiles cut into sub-tiles and the empty ones skipped; the tile rule
for a windowed call and the count of scores a call computes and needs, over
the benchmark's calls; the XLA path with a window.
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
# (heads a grid step, heads a row, their width[, the sub-tile that masked
# tiles are cut into; else they are computed whole]); two batch rows in all
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
    # a window of several whole tiles, no edge inside a tile: trinity's cell
    # has 2,048 keys of 8,192 in tiles of 1,024 (models/afmoe.py)
    "window_two_tiles_of_64": (8192, 128, 128, 256),
    "window_sixteen_tiles_of_64": (8192, 128, 128, 2048),
    # masked tiles in sub-tiles of 128 (PR 51): windows that are no multiple
    # of the sub-tile, shorter than one, and with their edge on a sub-tile's
    # border (and so on a tile's: no tile of the edge is left); block_q over
    # block_k; the one-tile call; first tiles whose edge range the sequence's
    # start clips (two edge tiles a row); narrow heads
    "cut_window_a_sub_tile_and_one": (1024, 256, 256, 129, 1, 1, 32, 128),
    "cut_window_200": (1024, 512, 512, 200, 1, 1, 32, 128),
    "cut_window_333": (1024, 256, 256, 333, 1, 1, 32, 128),
    "cut_window_shorter_than_a_sub_tile": (1024, 256, 256, 64, 1, 1, 32, 128),
    "cut_window_of_one_key": (512, 256, 256, 1, 1, 1, 32, 128),
    "cut_window_is_a_sub_tile": (1024, 256, 256, 128, 1, 1, 32, 128),
    "cut_window_is_a_tile": (1024, 256, 256, 256, 1, 1, 32, 128),
    "cut_window_a_tile_and_a_sub_tile": (1024, 256, 256, 384, 1, 1, 32, 128),
    "cut_window_is_two_tiles": (1536, 256, 256, 512, 1, 1, 32, 128),
    "cut_block_q_over_block_k": (1024, 512, 256, 333, 1, 1, 32, 128),
    "cut_block_q_four_block_k_the_sub_tile": (1024, 512, 128, 200, 1, 1, 32, 128),
    "cut_the_one_tile": (512, 512, 512, 200, 1, 1, 32, 128),
    "cut_the_one_q_tile_two_k_tiles": (512, 512, 256, 129, 1, 1, 32, 128),
    "cut_two_edge_tiles_a_row_clipped_at_the_start": (1536, 256, 256, 700, 1, 1, 32, 128),
    "cut_window_one_short_of_t": (512, 256, 256, 511, 1, 1, 32, 128),
    "cut_a_pair_of_64_a_vreg_two_groups": (1024, 256, 256, 300, 2, 4, 64, 128),
    "cut_four_heads_of_32_a_vreg_two_groups": (512, 256, 256, 200, 4, 8, 32, 128),
    "cut_one_head_of_128_a_row_each": (512, 256, 256, 129, 1, 2, 128, 128),
    "cut_sub_tiles_of_256_in_512": (1024, 512, 512, 333, 1, 1, 32, 256),
    # the rule's own tiles at a real size (None): 1,024 in sub-tiles of 128
    # for a head of 128 over 2,048 tokens under a window of 1,024, two grid
    # steps, the second's edge tile the diagonal's complement
    "cut_the_rules_tiles_t2048_d128": (2048, None, None, 1024, 1, 1, 128),
}


@pytest.mark.parametrize("case", CASES)
def test_windowed_flash_matches_float32_reference(case):
    t, block_q, block_k, window, *heads = CASES[case]
    heads, h, d, *sub = heads or (1, 1, 32)
    q, k, v, w = _operands(t, d, h=h)
    tiles = FlashTiles(block_q, block_k, heads, window).cut(sub[0] if sub else None)
    if block_q is None:
        tiles = flash_tiles(h, t, d, F32, window)
        assert tiles == FlashTiles(1024, 1024, 1, window).cut(128)
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
    """A windowed call takes the causal call's tile, cut like it (PR 51: with
    masked tiles cut, what a tile wastes no longer grows with it; until then
    a tile was at most half the window)."""
    causal = flash_tiles(32, 8192, 128, jnp.bfloat16)
    assert causal == FlashTiles(1024, 1024, 1, None).cut(128)
    for window in (2048, 1024, 100):
        assert flash_tiles(32, 8192, 128, jnp.bfloat16, window) == causal._replace(window=window)
    assert not hasattr(attention, "_WINDOW_TILE")
    # a tile that is one sub-tile has nothing to skip; the backward cuts a
    # tile from two sub-tiles a side, the forward from eight
    assert flash_tiles(1, 128, 128, jnp.bfloat16, 100) == FlashTiles(128, 128, 1, 100)
    assert flash_tiles(1, 512, 128, jnp.bfloat16, 100) == FlashTiles(512, 512, 1, 100, sub_bwd=128)
    with pytest.raises(ValueError):
        flash_tiles(32, 8192, 128, jnp.bfloat16, 0)


# The benchmark's flash calls by cell: (heads of a batch row, t, d, window,
# latent's shared width, selection), the parent's windowed tile (half the
# window), needed over computed with the masked tiles whole on the parent's
# tiles (ISSUE 51's table), and the least the rule's tiles must reach in the
# forward and in the backward call (a tile under 1,024 the forward leaves whole).
CALLS = {
    "gpt2_small.t256": ((12, 256, 64, None, None, None), None, 0.502, 0.502, 0.66),
    "gpt2_small.t1024": ((12, 1024, 64, None, None, None), None, 0.500, 0.80, 0.80),
    "mistral_7b_l8.fsdp4_t8192": ((32, 8192, 128, None, None, None), None, 0.889, 0.98, 0.98),
    "mellum2_12b_l4_ep4.t8192.window": ((32, 8192, 128, 1024, None, None), 512, 0.667, 0.80, 0.80),
    "mellum2_12b_l4_ep4.t8192.full": ((32, 8192, 128, None, None, None), None, 0.889, 0.98, 0.98),
    "trinity_mini_l5_ep16.t8192.window": ((32, 8192, 128, 2048, None, None), 1024, 0.667, 0.80, 0.80),
    "granite4_h_micro_l10.t4096": ((32, 4096, 64, None, None, None), None, 0.800, 0.96, 0.96),
    "lfm2_8b_a1b_l5_ep4.t8192": ((32, 8192, 64, None, None, None), None, 0.889, 0.98, 0.98),
    "kanana2_30b_l5_ep8.t8192": ((32, 8192, 128, None, 64, None), None, 0.941, 0.941, 0.98),
    "nemotron3_nano_l9_ep16.t8192": ((32, 8192, 128, None, None, None), None, 0.889, 0.98, 0.98),
    "keye_vl2_30b_l4_ep8.t16384": ((32, 16384, 128, None, None, 2048), None, 0.221, 0.22, 0.22),
}


@pytest.mark.parametrize("cell", sorted(CALLS))
def test_scores_needed_over_computed_for_the_cells_calls(cell):
    """`flash_scores` walks the bounds the kernels loop over: with masked
    tiles whole it gives the table the change started from, with the rule's
    sub-tiles at least 0.80 for both windowed calls and `t1024`'s, forward
    and backward; the selected call is as it was."""
    (h, t, d, window, shared, select), parent_tile, whole, least_fwd, least_bwd = CALLS[cell]
    tiles = flash_tiles(h, t, d, jnp.bfloat16, window, select, shared)
    assert (tiles.sub_bwd is None) == (select is not None)
    parent = tiles.cut(None)
    if parent_tile:
        parent = parent._replace(block_q=parent_tile, block_k=parent_tile)
    computed, needed = attention.flash_scores(parent, t)
    assert needed == sum(min(row + 1, window or select or t) for row in range(t))
    assert round(needed / computed, 3) == whole
    for backward, least in ((False, least_fwd), (True, least_bwd)):
        computed, needed_now = attention.flash_scores(tiles, t, backward)
        assert needed_now == needed and least <= round(needed / computed, 3) <= 1.0


@pytest.mark.parametrize("case", sorted(c for c in CASES if CASES[c][0] <= 1536 and CASES[c][1]))
def test_the_count_is_of_the_tiles_the_kernels_visit(case):
    """Every score a query sees lies in a sub-tile that `_strips` names, and
    `flash_scores` counts those sub-tiles and the plain tiles and no other:
    a mask of what the loops' bounds visit, entry by entry."""
    t, block_q, block_k, window, *heads = CASES[case]
    sub = heads[3] if len(heads) > 3 else None
    tiles = FlashTiles(block_q, block_k, 1, window).cut(sub)
    visited = np.zeros((t, t), bool)
    ratio = block_q // block_k
    for i in range(t // block_q):
        rows = slice(i * block_q, (i + 1) * block_q)
        (first, last), edge = attention._before(i * ratio, window, block_q, block_k)
        for j in range(first, last):
            visited[rows, j * block_k:(j + 1) * block_k] = True
        masked = [(i * ratio + s, s * block_k) for s in range(ratio)]
        for j, off in masked + [(j, off) for j, off, there in edge if there]:
            if sub is None:
                visited[rows, j * block_k:(j + 1) * block_k] = True
                continue
            for a, (b, crossed) in enumerate(attention._strips(off, block_q, block_k, sub, window)):
                visited[i * block_q + a * sub:i * block_q + (a + 1) * sub,
                        j * block_k + b * sub:j * block_k + (b + len(crossed)) * sub] = True
    ahead = np.arange(t)[:, None] - np.arange(t)[None, :]
    seen = (ahead >= 0) & (ahead < window)
    assert not (seen & ~visited).any()
    assert attention.flash_scores(tiles, t) == (int(visited.sum()), int(seen.sum()))


@pytest.mark.parametrize("window", [1, 7, 64, 100])
def test_xla_path_takes_the_window(window):
    q, k, v, _ = _operands(64, d=16, h=2)
    got = attention.xla_causal_attention(q, k, v, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_reference(q, k, v, window)),
                               rtol=2e-5, atol=2e-5)
    whole = attention.causal_attention(q, k, v, window=window)  # on the CPU: the XLA path
    np.testing.assert_allclose(np.asarray(whole), np.asarray(got), rtol=1e-6, atol=1e-6)
