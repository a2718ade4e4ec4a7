"""ops/moe.py's `ExpertShare` at two cells' sizes (mellum's layer and lfm2's)
compiled for a described TPU v5e, as tests/test_tpu_compile.py and with no
chip: the kernels it runs and the bytes the compiler books for it. The
layer is four families' and none's own, and its six cases take a third of
these files' time: a file of their own."""

import math
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import attention
from tests._tpu_compile import _bytes_accessed, _kinds


# ExpertShare at two cells' sizes: width, hidden, experts, top_k, held, router
_EXPERT_LAYERS = {"mellum": (2304, 896, 64, 8, 16, "softmax"),
                  "lfm2": (2048, 1792, 32, 4, 8, "sigmoid")}


def _expert_layer(name, one_chip, **fields):
    """(the layer, its parameters' and a (2, 8192, width) input's shapes on
    the described chip)."""
    from ray_tpu.ops.moe import ExpertShare

    width, hidden, experts, top_k, held, router = _EXPERT_LAYERS[name]
    layer = ExpertShare(width, hidden, experts, top_k, 0, held, router=router, **fields)
    x = jax.ShapeDtypeStruct((2, 8192, width), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, width), jnp.bfloat16)))["params"])
    return layer, params, x



@pytest.mark.parametrize("products_kept", [True, False], ids=["products_kept", "none_kept"])
@pytest.mark.parametrize("name,pr43_bytes,pr44_bytes,kept_bytes", [
    ("mellum", 59_120_476_160, 52_494_639_104, 58_500_816_896),
    # 100 s for the pair, `-m slow`: mellum's pair is the same layer's code at another size, fast
    pytest.param("lfm2", 40_023_392_256, 36_307_546_112, 40_541_192_192, marks=pytest.mark.slow)])
def test_expert_share_compiles_at_the_cell_s_size(
        one_chip, monkeypatch, name, pr43_bytes, pr44_bytes, kept_bytes, products_kept):
    """16 held experts of 64, top-8 (mellum's layer), and 8 of 32, top-4
    (lfm2's), on 16,384 tokens: the three grouped matmuls and their six
    gradients are megablox's kernels under the names the compiler gives them
    (gmm, tgmm: what the benchmark's moe_gmm metrics look for), once for the
    buffer with headroom and once for the buffer of every row; the sum back
    to the tokens is `moe_token_sum` in each (the gradient's: the forward's is
    not part of a gradient), and the plan's gathers bring no scatter of rows.

    `cost_analysis()["bytes accessed"]` books a `cond` at its dearer branch,
    the one for a step that overflowed (the same program with the predicate
    a constant reads 50.46e9 / 34.95e9 bytes for that branch alone and 26.01e9
    / 19.94e9 for the one with headroom). A layer told that nothing keeps its
    products is PR 44's program to the byte, fewer than PR 43's. One whose
    products are kept makes them on the buffer with headroom outside the
    `cond`, whatever the step routed, so a step that overflowed pays them on
    top of its own branch: 6.01e9 / 4.23e9 bytes more than PR 44 booked, held
    here as the exact number and not under a ceiling (PERF.md section 6, PR
    45, has such a step timed on the chip). What that buys is the test
    below."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    layer, params, x = _expert_layer(name, one_chip, products_kept=products_kept)
    width, top_k, held, experts = (_EXPERT_LAYERS[name][i] for i in (0, 3, 4, 2))
    loss = lambda p, x: layer.apply({"params": p}, x).astype(jnp.float32).sum()
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile()
    text = compiled.as_text()
    kinds = _kinds(text)
    assert kinds == {"gmm": 2 * 6, "tgmm": 2 * 3, "moe_token_sum": 2}, kinds
    # megablox's group metadata is made with scatters of a few hundred
    # elements; none is as long as the tokens
    scattered = re.findall(r"= \w+\[([\d,]*)\]\S* scatter\(", text)
    assert all(math.prod(map(int, s.split(","))) < 1024 for s in scattered), scattered
    # the buffer with headroom, 1.5 x the even load, and the one of every row
    rows, every = int(1.5 * 16384 * top_k * held / experts), 16384 * top_k
    assert f"bf16[{rows},{width}]" in text and f"bf16[{every},{width}]" in text
    booked = _bytes_accessed(compiled)
    if products_kept:
        assert booked == kept_bytes, booked
    else:
        assert booked == pr44_bytes < pr43_bytes, booked


@pytest.mark.slow  # 80 s: test_expert_share_compiles_at_the_cell_s_size[mellum-…] compiles the same layer and books its bytes, fast
@pytest.mark.parametrize("name,pr44_bytes", [("mellum", 33_753_649_152), ("lfm2", 24_842_141_696)])
def test_expert_share_s_step_that_fits_reads_fewer_bytes_than_pr_44_s(
        one_chip, monkeypatch, name, pr44_bytes):
    """The program of a step whose rows fit the buffer with headroom, which
    is every step of every cell: the same layers under `jax.checkpoint` with
    a policy that keeps the plan and the three products, loss and gradients,
    the predicate a constant so that the other branch is not in the program.
    It runs the three forward grouped matmuls once (PR 44's tree ran them
    again in the backward pass: 9 `gmm` where 6 stand) and reads fewer bytes
    than this test's own program compiled on PR 44's tree, where the names
    are identities: 31.66e9 / 23.05e9 against 33.75e9 / 24.84e9."""
    from ray_tpu.ops import moe

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(moe, "_fits", lambda plan, room: jnp.bool_(True))
    layer, params, x = _expert_layer(name, one_chip)
    keep = jax.checkpoint_policies.save_only_these_names(moe.ROUTE_PLAN, *moe.KEPT_PRODUCTS)
    loss = lambda p, x: layer.apply({"params": p}, x).astype(jnp.float32).sum()
    compiled = jax.jit(jax.value_and_grad(jax.checkpoint(loss, policy=keep),
                                          argnums=(0, 1))).lower(params, x).compile()
    kinds = _kinds(compiled.as_text())
    assert kinds == {"gmm": 6, "tgmm": 3, "moe_token_sum": 2}, kinds
    assert _bytes_accessed(compiled) < 0.95 * pr44_bytes, _bytes_accessed(compiled)
