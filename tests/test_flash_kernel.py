"""The pallas flash kernels (ops/attention.py) in interpret mode on the CPU:
output, dq, dk and dv against a plain float32 reference at `highest`
precision, over tile shapes the chip's cells use and the ones that break a
careless tile loop; the tile rule itself over every shape the cells, the
compile tests and these cases send it; and the names on the backward's
residuals, by which a checkpoint policy keeps the forward kernel from running
twice under remat.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import FlashTiles, flash_tiles

F32, BF16 = jnp.float32, jnp.bfloat16
# max-abs error over the reference's max-abs value. float32's bound is one
# only float32 operands can meet: a bf16 cast of any operand costs 4e-3.
TOL = {F32: 2e-5, BF16: 2e-2}


def _reference(q, k, v):
    """Causal attention in float32 at the highest matmul precision, no tiling."""
    hi = jax.lax.Precision.HIGHEST
    t, d = q.shape[-2:]
    s = jnp.einsum("htd,hsd->hts", q, k, precision=hi) / np.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("hts,hsd->htd", jax.nn.softmax(s, axis=-1), v, precision=hi)


def _with_grads(attn, q, k, v, w):
    """(o, dq, dk, dv) of attn under the loss sum(o * w)."""
    o, vjp = jax.vjp(attn, q, k, v)
    return (o, *vjp(w.astype(o.dtype)))


# (bh, t, d, dtype, block_q, block_k, heads, scale of q); None: the rule's
CASES = {
    "t256_one_diagonal_tile": (2, 256, 64, BF16, 256, 256, 1, 1),
    "t256_the_rules_tiles": (5, 256, 64, BF16, None, None, None, 1),
    "t1024_d64_the_rules_tiles": (2, 1024, 64, BF16, None, None, None, 1),
    "t1024_d64_128_tiles": (1, 1024, 64, BF16, 128, 128, 1, 1),
    "t512_d128": (2, 512, 128, BF16, 256, 256, 1, 1),
    "t1024_d128_the_rules_tiles": (1, 1024, 128, BF16, None, None, None, 1),
    "block_q_over_block_k": (2, 512, 64, BF16, 256, 128, 1, 1),
    "block_q_four_block_k": (1, 512, 64, F32, 512, 128, 1, 1),
    "heads_2_of_bh_3": (3, 256, 64, BF16, 256, 256, 2, 1),
    "heads_4_of_bh_5_two_tiles": (5, 256, 64, F32, 128, 128, 4, 1),
    "float32_stays_float32_d64": (2, 512, 64, F32, 256, 128, 2, 1),
    "float32_stays_float32_d128": (1, 384, 128, F32, 128, 128, 1, 1),
    "large_scores_float32": (2, 512, 64, F32, 128, 128, 1, 40),
    "large_scores_bf16": (2, 512, 64, BF16, 256, 128, 2, 40),
}


@pytest.mark.parametrize("case", CASES)
def test_flash_matches_float32_reference(case):
    bh, t, d, dtype, block_q, block_k, heads, q_scale = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case) + t), 4)
    q, k, v, w = (jax.random.normal(key, (bh, t, d), F32) for key in keys)
    q, k, v, w = ((q * q_scale).astype(dtype), k.astype(dtype), v.astype(dtype),
                  w.astype(dtype))
    tiles = flash_tiles(bh, t, d, dtype)
    tiles = FlashTiles(block_q or tiles.block_q, block_k or tiles.block_k,
                       heads or tiles.heads)
    got = _with_grads(lambda q, k, v: attention._flash(q, k, v, tiles, True), q, k, v, w)
    want = _with_grads(_reference, *(x.astype(F32) for x in (q, k, v, w)))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == dtype and a.shape == (bh, t, d), name
        a = np.asarray(a.astype(F32))
        assert np.isfinite(a).all(), name
        err = np.abs(a - np.asarray(b)).max() / np.abs(b).max()
        assert err < TOL[dtype], f"{name}: {err}"


def test_public_entry_takes_overrides_and_refuses_tiles_that_skip_keys():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 256, 64), BF16)
    o = attention.flash_causal_attention(q, q, q, block_q=256, block_k=128, interpret=True)
    ref = attention.xla_causal_attention(q, q, q)
    assert float(jnp.abs(o.astype(F32) - ref.astype(F32)).max()) < 2e-2
    with pytest.raises(ValueError, match="multiple of block_k"):
        attention.flash_causal_attention(q, q, q, block_q=128, block_k=256, interpret=True)
    with pytest.raises(ValueError, match="divide the seq len"):
        attention.flash_causal_attention(q, q, q, block_q=192, block_k=64, interpret=True)


# (bh, t, d): the three cells' per-chip calls, chip_smoke.py's (one chip and
# the dp=2,tp=2 shard), tests/test_tpu_compile.py's, the cases above, a prime
# bh, a t with no divisor but 128 and itself, and a long one.
SHAPES = sorted({
    (1536, 256, 64), (384, 1024, 64), (32, 8192, 128),
    (192, 1024, 64), (48, 1024, 64), (32, 4096, 128),
    *((c[0], c[1], c[2]) for c in CASES.values()),
    (7, 256, 64), (13, 640, 64), (1, 1408, 128), (4, 32768, 128), (3, 256, 256),
})


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_tile_rule_is_legal_for(shape, dtype):
    bh, t, d = shape
    tiles = flash_tiles(bh, t, d, dtype)
    assert isinstance(tiles, FlashTiles)
    for block in (tiles.block_q, tiles.block_k):
        assert block % 128 == 0 and t % block == 0
    assert tiles.block_q % tiles.block_k == 0
    assert 1 <= tiles.heads <= bh
    assert (attention._vmem_bytes(tiles, t, d, jnp.dtype(dtype).itemsize)
            <= attention._VMEM_BUDGET)
    # pure: the same call, the same tiles
    assert flash_tiles(bh, t, d, dtype) == tiles


# --------------------------------------------------------------------------
# the backward's residuals by name: a checkpoint policy saves them across remat
# --------------------------------------------------------------------------

KEEP = jax.checkpoint_policies.save_only_these_names("attn_out", "attn_lse")


def _mapped(attn):
    """attn under a shard_map over the batch on four of conftest.py's virtual
    devices, as parallel/train_step.py:attn_for_mesh wraps the kernel."""
    from jax.sharding import Mesh, PartitionSpec as P

    spec = P("dp", None, None, None)
    return jax.shard_map(attn, mesh=Mesh(np.array(jax.devices()[:4]), ("dp",)),
                         in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)


def _flash(**kw):
    return functools.partial(attention.flash_causal_attention, interpret=True, **kw)


# attention callable on (B, H, T, D), and the name its forward call carries
REMAT_CASES = {
    "causal": (_flash(), "flash_fwd"),
    "window": (_flash(window=128), "flash_win128_fwd"),
    "shard_map": (_mapped(_flash()), "flash_fwd"),
    "shard_map_window": (_mapped(_flash(window=128)), "flash_win128_fwd"),
}


def _two_blocks(attn, remat):
    """Loss of two blocks x + attention(x @ w), each under `remat`."""
    def block(x, w):
        b, t, c = x.shape
        h = (x @ w).reshape(b, t, 2, c // 2).transpose(0, 2, 1, 3)
        return x + attn(h, h, h).transpose(0, 2, 1, 3).reshape(b, t, c)

    def loss(x, ws):
        for w in ws:
            x = remat(block)(x, w)
        return (x.astype(F32) ** 2).mean()

    return jax.grad(loss, argnums=(0, 1))


def _pallas_calls(jaxpr):
    """Names of the pallas calls of a jaxpr, those of its sub-jaxprs included
    (the printed text shows a jaxpr that two equations share once)."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _pallas_calls(sub)
    return names


@pytest.mark.parametrize("case", REMAT_CASES)
def test_policy_saves_the_kernel_s_output_and_logsumexp_across_remat(case):
    """Two rematted blocks hold two forward calls a block; under a policy
    that saves `attn_out` and `attn_lse` one, and one of each backward
    kernel as before; the gradients are the same in every bit. A name
    without a policy, and with no remat at all, changes no call."""
    attn, fwd = REMAT_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(keys[0], (4, 256, 128), BF16)
    ws = [jax.random.normal(k, (128, 128), BF16) / 11 for k in keys[1:]]

    def calls(remat):
        names = _pallas_calls(jax.make_jaxpr(_two_blocks(attn, remat))(x, ws).jaxpr)
        return {kind: sum(n.endswith(kind) for n in names) for kind in ("fwd", "bwd_dq", "bwd_dkv")}, names

    unnamed = functools.partial(jax.checkpoint, policy=None)
    saved = functools.partial(jax.checkpoint, policy=KEEP)
    for remat, want_fwd in ((lambda f: f, 2), (unnamed, 4), (saved, 2)):
        got, names = calls(remat)
        assert got == {"fwd": want_fwd, "bwd_dq": 2, "bwd_dkv": 2}
        assert fwd in names and all(n.startswith(fwd[:-3]) for n in names)
    for a, b in zip(jax.tree.leaves(jax.jit(_two_blocks(attn, saved))(x, ws)),
                    jax.tree.leaves(jax.jit(_two_blocks(attn, unnamed))(x, ws))):
        assert np.isfinite(np.asarray(a.astype(F32))).all()
        np.testing.assert_array_equal(np.asarray(a.astype(F32)), np.asarray(b.astype(F32)))
