"""The latent attention's pallas pair (ops/attention.py: flash_mla_fwd,
flash_mla_bwd_fused) in interpret mode on the CPU: the output and each of
the five gradients (q's two parts, the heads' own keys, the key all heads
share, the values) against the published expanded form, k = [k_own ; k_shared
repeated to every head], in plain float32 at `highest` precision, at the
published head widths (128 + 64 deep scores, 128-wide values); what a
careless kernel would drop; the tile rule; the calls' names and what they
read and write; and that the causal kernels it shares its loops with lower
to what they did.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import FlashTiles, flash_tiles

F32, BF16 = jnp.float32, jnp.bfloat16
TOL = {F32: 2e-5, BF16: 2e-2}  # tests/test_flash_kernel.py's
NAMES = ("o", "dq", "dq_shared", "dk", "dk_shared", "dv")
D, R = 128, 64


def _expanded(q, q2, k, k2, v):
    """The published form: a head's key is its own 128 and the token's
    shared 64, one (T, T) softmax a head, no tiling."""
    hi = jax.lax.Precision.HIGHEST
    b, t, h, _ = q.shape
    qq = jnp.concatenate([q, q2], -1)
    kk = jnp.concatenate([k, jnp.broadcast_to(k2[:, :, None], (b, t, h, k2.shape[-1]))], -1)
    s = jnp.einsum("bthd,bshd->bhts", qq, kk, precision=hi) / np.sqrt(qq.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v, precision=hi)


def _operands(b, t, h, dtype, seed=0):
    """Random and so distinct a head and a batch row: a block index map that
    takes another head's lanes or another row's shows in every result."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = ((b, t, h, D), (b, t, h, R), (b, t, h, D), (b, t, R), (b, t, h, D), (b, t, h, D))
    *ops, w = (jax.random.normal(k, s, F32) for k, s in zip(ks, shapes))
    return [x.astype(dtype) for x in ops], w


def _with_grads(attn, ops, w):
    o, vjp = jax.vjp(attn, *ops)
    return (o, *vjp(w.astype(o.dtype)))


def _errors(got, want):
    return {name: float(jnp.abs(g.astype(F32) - r).max() / jnp.abs(r).max())
            for name, g, r in zip(NAMES, got, want)}


# (b, h, t, dtype, block_q, block_k, heads a grid step[, the sub-tile that
# masked tiles are cut into]); None: the rule's (a tile of 384 goes in
# sub-tiles of 128); tiles given without a sub-tile are computed whole
CASES = {
    "one_tile": (1, 2, 128, F32, None, None, None),
    "one_tile_of_256_bf16": (2, 2, 256, BF16, 256, 256, 2),
    "t384_the_rule_s_one_tile": (2, 4, 384, F32, None, None, None),
    "t384_is_no_multiple_of_its_256_tile_s_neighbour": (2, 2, 384, F32, 128, 128, 2),
    "block_q_over_block_k": (1, 2, 512, F32, 256, 128, 2),
    "four_heads_a_grid_step": (1, 4, 256, F32, 128, 128, 4),
    "two_groups_of_two_three_tiles_bf16": (1, 4, 384, BF16, 128, 128, 2),
    "cut_one_tile_of_256": (2, 2, 256, F32, 256, 256, 2, 128),
    "cut_two_tiles_of_256_two_groups": (1, 4, 512, F32, 256, 256, 2, 128),
    "cut_block_q_over_block_k_bf16": (1, 2, 512, BF16, 512, 256, 2, 128),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_latent_pair_matches_the_expanded_form(case):
    b, h, t, dtype, block_q, block_k, heads, *sub = CASES[case]
    ops, w = _operands(b, t, h, dtype)
    tiles = flash_tiles(h, t, D, dtype, shared=R)
    if case == "t384_the_rule_s_one_tile":
        # no power of two: three vregs of rows, which the backward cuts
        assert tiles[:2] == (384, 384) and (tiles.sub_fwd, tiles.sub_bwd) == (None, 128)
    if block_q:
        tiles = FlashTiles(block_q, block_k, heads).cut(sub[0] if sub else None)
    got = _with_grads(lambda *a: attention._flash_latent(*a, tiles, True), ops, w)
    want = _with_grads(_expanded, [x.astype(F32) for x in ops], w)
    assert got[0].dtype == dtype and got[4].shape == (b, t, R)
    errors = _errors(got, want)
    assert max(errors.values()) < TOL[dtype], errors


def test_the_plain_form_is_the_expanded_form():
    ops, w = _operands(2, 96, 3, F32, seed=1)
    with jax.default_matmul_precision("highest"):
        got = _with_grads(attention.xla_latent_attention, ops, w)
    errors = _errors(got, _with_grads(_expanded, ops, w))
    assert max(errors.values()) < 1e-5, errors


WRONG = {
    "shared_part_left_out": lambda q, q2, k, k2, v: (q, jnp.zeros_like(q2), k, k2, v),
    "scaled_by_the_own_width_alone": lambda q, q2, k, k2, v: (
        q * np.sqrt((D + R) / D), q2 * np.sqrt((D + R) / D), k, k2, v),
    "one_head_s_shared_part_for_all": lambda q, q2, k, k2, v: (
        q, jnp.broadcast_to(q2[:, :, :1], q2.shape), k, k2, v),
}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_the_comparison_sees_what_a_kernel_could_drop(wrong):
    """The expanded form on operands changed as a careless kernel would
    change them is outside the tolerance the pair is held to."""
    ops, w = _operands(1, 256, 2, F32)
    want = _with_grads(_expanded, ops, w)
    off = _expanded(*WRONG[wrong](*ops))
    assert float(jnp.abs(off - want[0]).max() / jnp.abs(want[0]).max()) > 50 * TOL[F32]


def test_the_shared_key_s_gradient_is_the_sum_over_the_heads():
    """dk_shared of four heads is the sum of two calls' of two heads each."""
    ops, w = _operands(1, 256, 4, F32, seed=2)
    tiles = FlashTiles(128, 128, 2)  # two grid steps of heads a batch row, two tiles each
    whole = _with_grads(lambda *a: attention._flash_latent(*a, tiles, True), ops, w)[4]
    q, q2, k, k2, v = ops
    total = 0.0
    for pair in range(2):
        cut = slice(2 * pair, 2 * pair + 2)
        part = _with_grads(lambda *a: attention._flash_latent(*a, tiles, True),
                           [q[:, :, cut], q2[:, :, cut], k[:, :, cut], k2, v[:, :, cut]],
                           w[:, :, cut])
        total = total + part[4]
    np.testing.assert_allclose(whole, total, rtol=1e-5, atol=1e-5)


def test_tile_rule_for_the_cell_and_what_it_refuses():
    """At the benchmark's shape the second parts go two to a vreg, so a grid
    step takes heads in pairs, and the reckoned VMEM holds the tile under
    1,024; a window, a selection or an own part that straddles vregs is no
    latent call."""
    tiles = flash_tiles(32, 8192, D, BF16, shared=R)
    assert tiles == FlashTiles(512, 512, 2, sub_bwd=128)
    need = (attention._vmem_bytes(tiles, 8192, D, 2)
            + attention._shared_vmem_bytes(tiles, 8192, R, 2))
    assert need <= attention._VMEM_BUDGET
    big = FlashTiles(1024, 1024, 2)
    assert (attention._vmem_bytes(big, 8192, D, 2)
            + attention._shared_vmem_bytes(big, 8192, R, 2)) > attention._VMEM_BUDGET
    assert flash_tiles(32, 256, D, BF16, shared=R).heads == 4  # a short call: more heads a step
    for bad in (dict(window=128), dict(select=128)):
        with pytest.raises(ValueError):
            flash_tiles(4, 1024, D, BF16, shared=R, **bad)
    with pytest.raises(ValueError):
        flash_tiles(4, 1024, 64, BF16, shared=R)
    with pytest.raises(ValueError):  # one head's second part is half a vreg
        attention._flash_latent(*_operands(1, 128, 2, F32)[0], FlashTiles(128, 128, 1), True)


_CALL = re.compile(r'kernel_name = "(flash_\w+)".*?: \((.*?)\) -> (.*)$', re.M)
_TENSOR = re.compile(r"tensor<([\dx]+)x(\w+)>")


def test_the_calls_names_operands_and_results():
    """Lowered for a TPU at the cell's shape: one call each way under its
    name, which the causal calls' readers do not match; no operand or result
    holds 32 keys 192 wide or a value wider than 128, the shared key comes
    in and its gradient goes out once a token (128 lanes: the key twice),
    and the output leaves as (B, T, H * 128), where the output projection
    reads it."""
    b, t, h = 2, 8192, 32
    shapes = ((b, t, h, D), (b, t, h, R), (b, t, h, D), (b, t, R), (b, t, h, D))
    ops = [jax.ShapeDtypeStruct(s, BF16) for s in shapes]
    loss = lambda *a: attention.flash_latent_attention(*a).astype(F32).sum()
    text = jax.jit(jax.grad(loss, argnums=range(5))).trace(*ops).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = {name: ([(d, ty) for d, ty in _TENSOR.findall(ins)],
                    [(d, ty) for d, ty in _TENSOR.findall(outs)])
             for name, ins, outs in _CALL.findall(text)}
    assert sorted(calls) == ["flash_mla_bwd_fused", "flash_mla_fwd"]
    own, second, key = ("2x8192x4096", "bf16"), ("2x8192x2048", "bf16"), ("2x8192x128", "bf16")
    rows = ("64x1x8192", "f32")
    assert calls["flash_mla_fwd"] == ([own, own, own, second, key], [own, rows])
    assert calls["flash_mla_bwd_fused"] == (
        [own, own, own, own, own, rows, second, key],
        [own, own, own, second, ("2x8192x128", "f32")])
    for pattern in (r"flash_fwd", r"flash_(?:win\d+_|sel\d+_)?bwd_fused"):  # the accepted readers'
        assert not [name for name in calls if re.search(pattern, name)]
    assert "x6144x" not in "".join(d for ins, outs in calls.values() for d, _ in ins + outs)


def test_residuals_carry_names_a_policy_can_save():
    ops, _ = _operands(1, 128, 2, F32)
    tiles = flash_tiles(2, 128, D, F32, shared=R)
    jaxpr = str(jax.make_jaxpr(lambda *a: attention._flash_latent_fwd_rule(*a, tiles, True))(*ops))
    for name in ("attn_q", "attn_k", "attn_v", "attn_q_shared", "attn_k_shared", "attn_out",
                 "attn_lse"):
        assert f"name={name}]" in jaxpr or f"name={name}\n" in jaxpr, name
