"""Mixture-of-Experts + expert parallelism (green-field; no reference
counterpart — SURVEY §2.4 lists EP/MoE as absent upstream).

Covers: routing/capacity semantics, parity with a dense FFN when all
experts are identical, the Switch load-balance loss, and a sharded
end-to-end training step on an 8-device dp x ep mesh with the experts'
leading dim partitioned over 'ep'.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_top_k_routing_capacity_and_weights():
    from ray_tpu.ops.moe import top_k_routing

    B, S, E, cap = 1, 4, 2, 2
    # All tokens prefer expert 0 strongly.
    probs = jnp.tile(jnp.array([0.9, 0.1], jnp.float32), (B, S, 1))
    dispatch, combine = top_k_routing(probs, k=1, capacity=cap)
    # Expert 0 admits only `cap` tokens (earliest positions win)...
    assert float(dispatch[0, :, 0].sum()) == cap
    assert float(dispatch[0, 0, 0].sum()) == 1.0
    assert float(dispatch[0, 1, 0].sum()) == 1.0
    # ...and the overflowing tokens are dropped entirely (k=1).
    assert float(dispatch[0, 2].sum()) == 0.0
    assert float(dispatch[0, 3].sum()) == 0.0
    # top-1 combine weights are renormalized to 1 for admitted tokens.
    assert np.isclose(float(combine[0, 0].sum()), 1.0)

    # k=2 with generous capacity: every token reaches both experts and the
    # combine weights sum to 1.
    dispatch, combine = top_k_routing(probs, k=2, capacity=S)
    assert np.allclose(np.asarray(dispatch.sum(axis=(2, 3))), 2.0)
    assert np.allclose(np.asarray(combine.sum(axis=(2, 3))), 1.0, atol=1e-6)


def test_moe_matches_dense_when_experts_identical():
    """With identical experts and k=1, routing is irrelevant: the MoE layer
    must reproduce the plain FFN."""
    from ray_tpu.ops.moe import MoE, MoEConfig

    B, S, C, F, E = 2, 8, 16, 32, 4
    layer = MoE(
        d_model=C, d_ff=F,
        moe=MoEConfig(num_experts=E, top_k=1, capacity_factor=float(E)),
        dtype=jnp.float32,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, C), jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    w1 = np.asarray(params["wi"][0])
    w2 = np.asarray(params["wo"][0])
    params["wi"] = jnp.tile(w1[None], (E, 1, 1))
    params["wo"] = jnp.tile(w2[None], (E, 1, 1))

    out, _ = layer.apply({"params": params}, x, mutable=["losses"])
    import flax.linen as nn

    expect = np.asarray(nn.gelu(x @ w1, approximate=True) @ w2)
    assert np.allclose(np.asarray(out), expect, atol=1e-4)


def test_load_balance_loss_uniform_is_one():
    from ray_tpu.ops.moe import load_balance_loss, top_k_routing

    B, S, E = 2, 16, 4
    probs = jnp.full((B, S, E), 1.0 / E, jnp.float32)
    # Break argmax ties deterministically with a tiny tilt per token.
    tilt = jax.random.uniform(jax.random.PRNGKey(0), (B, S, E)) * 1e-4
    dispatch, _ = top_k_routing(probs + tilt, k=1, capacity=S)
    loss = float(load_balance_loss(probs, dispatch))
    assert 0.8 < loss < 1.3  # ~1.0 for uniform routing


def test_trainstep_with_moe_config_on_ep_mesh():
    """The product TrainStep accepts a GPT2MoEConfig: dp=2 x ep=2 x tp=2
    mesh, experts sharded over 'ep', loss (incl. routed aux) decreases."""
    from ray_tpu.models.gpt2_moe import GPT2MoEConfig
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.train_step import TrainStep

    cfg = GPT2MoEConfig.tiny_moe(dtype=jnp.float32, use_flash_attention=False)
    mesh = make_mesh({"dp": 2, "fsdp": 1, "sp": 1, "tp": 2, "ep": 2})
    ts = TrainStep(cfg, mesh, learning_rate=1e-3)
    state = ts.init(jax.random.PRNGKey(0))

    wi_sharding = state["params"]["h_0"]["moe"]["wi"].sharding
    assert "ep" in (wi_sharding.spec[0] or ()), wi_sharding.spec

    rng = np.random.default_rng(0)
    idx = rng.integers(0, cfg.vocab_size, (4, 32)).astype(np.int32)
    batch = ts.shard_batch({"idx": idx, "targets": np.roll(idx, -1, axis=1)})
    losses = []
    for _ in range(4):
        state, m = ts.step(state, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


# The step of the one family whose layers add a term to the loss (the
# "losses" collection), `GPT2MoEConfig.tiny_moe()` on dp=2 x ep=2, as
# tests/test_mellum.py:_step_text gives it: taken on PR 46's parent's tree
# before `TrainStep` stopped knowing its families by name. No cell runs it.
# Plain attention: a shard_map over two axes prints them as a frozenset, whose
# order is the interpreter's hash seed's.
GPT2_MOE_STEP = "08ff1237632ef3b4257b0dbba5e78cb7b7c928d367f27dbac74e0dea3c11eac8"


def test_the_capacity_routed_step_lowers_to_its_pinned_step():
    import hashlib

    from ray_tpu.models.gpt2_moe import GPT2MoEConfig
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.train_step import TrainStep
    from tests.test_mellum import _step_text

    ts = TrainStep(GPT2MoEConfig.tiny_moe(use_flash_attention=False),
                   make_mesh({"dp": 2, "ep": 2}, devices=jax.devices()[:4]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((4, 64), jnp.int32)
    text = _step_text(ts, state, {"idx": tok, "targets": tok})
    assert hashlib.sha256(text.encode()).hexdigest() == GPT2_MOE_STEP


def test_moe_model_trains_on_dp_ep_mesh():
    """8 virtual devices as dp=2 x ep=4: one full fwd/bwd/update step of the
    MoE transformer with experts sharded over 'ep', and sharded forward
    matches the unsharded forward."""
    import optax

    from ray_tpu.models.gpt2_moe import (
        GPT2MoEConfig,
        GPT2_MOE_SHARDING_RULES,
        forward_with_aux,
        init_params,
        moe_loss_fn,
    )
    from ray_tpu.parallel.mesh import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = GPT2MoEConfig.tiny_moe(dtype=jnp.float32, use_flash_attention=False)
    mesh = make_mesh({"dp": 2, "ep": 4})
    params = init_params(cfg)
    specs = GPT2_MOE_SHARDING_RULES.tree_specs(params)
    # Expert tensors really carry the ep axis.
    assert specs["h_0"]["moe"]["wi"] == P("ep", "fsdp", "tp")

    def prune(spec):
        # Axes absent from this mesh (fsdp/tp here) fall back to replicated.
        return P(*(a if a in mesh.shape else None for a in spec))

    sharded = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, prune(s))),
        params,
        specs,
    )

    rng = np.random.default_rng(0)
    idx = rng.integers(0, cfg.vocab_size, (4, 32)).astype(np.int32)
    targets = np.roll(idx, -1, axis=1)
    batch_sharding = NamedSharding(mesh, P("dp", None))
    idx_s = jax.device_put(idx, batch_sharding)
    tgt_s = jax.device_put(targets, batch_sharding)

    # Parity: sharded vs single-device logits.
    logits_ref, aux_ref = forward_with_aux(cfg, params, idx)
    logits_sh, aux_sh = jax.jit(
        lambda p, i: forward_with_aux(cfg, p, i)
    )(sharded, idx_s)
    assert np.allclose(
        np.asarray(logits_sh), np.asarray(logits_ref), atol=2e-3
    )
    assert np.isclose(float(aux_sh), float(aux_ref), atol=1e-4)
    assert float(aux_sh) > 0.0  # aux loss flows

    # One optimizer step under jit on the mesh: loss finite and decreasing
    # over a few steps on a fixed batch.
    opt = optax.adam(1e-3)
    opt_state = opt.init(sharded)

    @jax.jit
    def step(p, o, i, t):
        loss, grads = jax.value_and_grad(
            lambda pp: moe_loss_fn(cfg, pp, i, t)
        )(p)
        updates, o = opt.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    losses = []
    p, o = sharded, opt_state
    for _ in range(4):
        p, o, loss = step(p, o, idx_s, tgt_s)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


# --------------------------------------------------------------------------
# ExpertShare's two passes that end at the tokens: `sum_by_token` (the
# buffer's rows in token order, summed by a kernel) against the gathers from
# the tokens' side that it replaced, kept here as the plain statement.
# --------------------------------------------------------------------------


@jax.custom_vjp
def _plain_dispatch(x, order, inv, held):
    """x[order // k] with the backward pass of a gather for every assignment."""
    return x[order // inv.shape[1]]


def _plain_dispatch_fwd(x, order, inv, held):
    return _plain_dispatch(x, order, inv, held), (inv, held)


def _plain_dispatch_bwd(res, g):
    inv, held = res
    dx = jnp.where(held[..., None], g[inv], 0).astype(jnp.float32).sum(1)
    return dx.astype(g.dtype), None, None, None


_plain_dispatch.defvjp(_plain_dispatch_fwd, _plain_dispatch_bwd)


def _plain_combine(y, gates, inv, held):
    rows = jnp.where(held[..., None], y[inv], 0)
    return (rows.astype(jnp.float32) * gates[..., None]).sum(1)


def _routing(name, n, k):
    """(idx (n, k) over 16 experts, experts held, whether the buffer is the
    one of every assignment) for a case's name."""
    rng = np.random.default_rng(sum(map(ord, name)) + k)
    idx = np.stack([rng.permutation(16)[:k] for _ in range(n)])
    held, whole = {"all": 16, "quarter": 4, "eighth": 2}.get(name, 2), False
    if name == "none_held":  # the first tokens choose nothing that is held
        idx[:40] = np.arange(2, 2 + k)
    elif name == "same_experts":  # every token the same k experts, two of them held
        idx[:] = np.arange(k)
    elif name == "overflow":  # every token both held experts: past any headroom
        idx[:] = np.arange(k)
        whole = True
    return jnp.asarray(idx, jnp.int32), held, whole


_SUM_CASES = ([(share, k) for k in (4, 6, 8) for share in ("all", "quarter", "eighth")]
              + [(name, 4) for name in ("none_held", "same_experts", "overflow")])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name,k", _SUM_CASES, ids=[f"{name}_k{k}" for name, k in _SUM_CASES])
def test_rows_summed_by_token_equal_the_gathers_from_the_tokens_side(name, k, dtype, monkeypatch):
    """Values and both gradients (x and gates) through dispatch_rows and
    combine_rows with the kernel in interpret mode, against the plain
    statement: equal to float32 rounding of a sum of at most k terms. What the
    buffer holds past the rows routed here is NaN on both sides and reaches
    nothing; a token with no held row gets exact zeros."""
    import functools

    from ray_tpu.ops import moe

    monkeypatch.setattr(moe, "sum_by_token", functools.partial(moe.sum_by_token, interpret=True))
    n, c = 256, 128
    idx, num_held, whole = _routing(name, n, k)
    plan = moe.route_plan(idx, 0, num_held)
    order, inv, held, group_sizes, _ = plan
    total = int(group_sizes.sum())
    rows = n * k if whole or num_held == 16 else -(-max(total, 1) // 256) * 256
    assert total <= rows and (name != "overflow" or total == 2 * n)
    at, back, clipped = order[:rows], moe.token_order(plan, rows), jnp.minimum(inv, rows - 1)
    x = jax.random.normal(jax.random.PRNGKey(k), (n, c), jnp.float32).astype(dtype)
    gates = jax.random.uniform(jax.random.PRNGKey(k + 1), (n, k), jnp.float32, 0.05, 1.0)
    target = jax.random.normal(jax.random.PRNGKey(k + 2), (n, c), jnp.float32)
    routed_here = (jnp.arange(rows) < total)[:, None]
    experts = lambda taken: jnp.where(routed_here, jnp.tanh(taken * 1.5), jnp.nan)

    def new(x, gates):
        out = experts(moe.dispatch_rows(x, at, held, back))
        return moe.combine_rows(out, gates, at, clipped, held, back)

    def plain(x, gates):
        return _plain_combine(experts(_plain_dispatch(x, at, clipped, held)), gates, clipped, held)

    loss = lambda f: lambda x, gates: (f(x, gates) * target).sum()
    got, want = new(x, gates), plain(x, gates)
    assert got.dtype == want.dtype == jnp.float32 and bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6, atol=2e-6)
    nobody = ~np.asarray(held).any(1)
    assert (name != "none_held" or nobody[:40].all()) and not np.asarray(got)[nobody].any()
    for g_got, g_want in zip(jax.grad(loss(new), (0, 1))(x, gates),
                             jax.grad(loss(plain), (0, 1))(x, gates)):
        assert g_got.dtype == g_want.dtype and bool(jnp.isfinite(g_got).all())
        np.testing.assert_allclose(np.asarray(g_got, np.float32), np.asarray(g_want, np.float32),
                                   rtol=2e-6, atol=2e-6)


def test_without_the_kernel_the_sum_is_the_same_sum():
    """Where the kernel does not run (no TPU, or sizes that are no whole
    blocks) the same rows in the same order through XLA's segment sum."""
    from ray_tpu.ops import moe

    idx, num_held, _ = _routing("quarter", 200, 6)
    plan = moe.route_plan(idx, 0, num_held)
    rows = 520
    back = moe.token_order(plan, rows)
    y = jax.random.normal(jax.random.PRNGKey(0), (rows, 24), jnp.float32)
    gates = jax.random.uniform(jax.random.PRNGKey(1), (200, 6), jnp.float32)
    want = _plain_combine(y, gates, jnp.minimum(plan[1], rows - 1), plan[2])
    np.testing.assert_allclose(np.asarray(moe.sum_by_token(y, back, 200, gates)),
                               np.asarray(want), rtol=2e-6, atol=2e-6)


# --------------------------------------------------------------------------
# The expert layer's backward reads what its forward wrote (PR 45)
# --------------------------------------------------------------------------


def _parent_s_experts_in_buffer():
    """`_experts_in_buffer` as it stood before PR 45: the forward rule keeps
    its operands alone and the backward differentiates `_expert_rows`, its
    forward again, in the branch it takes."""
    import functools

    from ray_tpu.ops import moe

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
    def experts(form, rooms, dtype, kept, plan, weights, x, gates):  # `kept`: PR 45's, not read
        return jax.lax.cond(moe._fits(plan, rooms[0]),
                            functools.partial(moe._expert_rows, form, rooms[0], dtype),
                            functools.partial(moe._expert_rows, form, rooms[1], dtype),
                            plan, weights, x, gates)

    def fwd(form, rooms, dtype, kept, plan, weights, x, gates):
        return experts(form, rooms, dtype, kept, plan, weights, x, gates), (plan, weights, x, gates)

    def bwd(form, rooms, dtype, kept, res, g):
        plan, *operands = res

        def back(rows, plan, *operands):
            return jax.vjp(functools.partial(moe._expert_rows, form, rows, dtype, plan),
                           *operands)[1](g)

        grads = jax.lax.cond(moe._fits(plan, rooms[0]), functools.partial(back, rooms[0]),
                             functools.partial(back, rooms[1]), plan, *operands)
        return (None, *grads)

    experts.defvjp(fwd, bwd)
    return experts


# (the names a block's policy keeps, what the family handed the layer as
# `products_kept`): the read form under each policy, one that keeps nothing
# of it too, and the form a plan with no product takes
_KEPT_CASES = [((), True), (("moe_plan",), True), (("moe_plan", "moe_gate", "moe_up"), True),
               (("moe_out",), True), (("moe_plan", "moe_gate", "moe_up", "moe_out"), True),
               ((), False), (("moe_plan",), False)]


@pytest.mark.parametrize("form", ["SWIGLU", "RELU2"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("overflows", [False, True], ids=["fits", "overflows"])
@pytest.mark.parametrize("router,hand_up", [("softmax", False), ("sigmoid", True)],
                         ids=["softmax", "sigmoid_hands_up_choices"])
@pytest.mark.parametrize("kept,products_kept", _KEPT_CASES, ids=[
    ("-".join(k) or "none") + ("" if p else "-told_none_is_kept") for k, p in _KEPT_CASES])
def test_gradients_are_the_parent_s_bit_for_bit_whatever_remat_keeps(
        kept, products_kept, router, hand_up, overflows, dtype, form, monkeypatch):
    """`ExpertShare` under `nn.remat` with a policy that keeps these names,
    against the same layer with the parent's `_experts_in_buffer` (whose
    backward runs `jax.vjp` of `_expert_rows`): the output and the gradients
    to the input and to every parameter are equal bit for bit, in the buffer
    with headroom (1,024 tokens, top-2 of 8, two held: room for 1,024 of
    2,048 assignments) and where every token goes to both held experts and
    the step takes the buffer of every assignment; in the form that reads
    kept products and in the one a layer takes that was told none is kept;
    for SwiGLU experts and for experts of two matrices under relu squared
    (which have no gate product: a policy that names one keeps nothing by it)."""
    import flax.linen as nn

    from ray_tpu.ops import moe

    layer = nn.remat(moe.ExpertShare, policy=jax.checkpoint_policies.save_only_these_names(*kept))(
        24, 40, 8, 2, 2, 2, dtype, router=router, hand_up_choices=hand_up,
        products_kept=products_kept, form=getattr(moe, form))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 512, 24), jnp.float32)
    params = layer.init(jax.random.PRNGKey(4), x)["params"]
    assert ("gate" in params) == (form == "SWIGLU") and {"up", "down"} <= set(params)
    if overflows:
        x = jnp.abs(x)
        kernel = jnp.zeros_like(params["router"]["kernel"]).at[:, 2].set(2.0).at[:, 3].set(1.0)
        params = {**params, "router": {"kernel": kernel}}
    target = jax.random.normal(jax.random.PRNGKey(5), x.shape, jnp.float32)

    def loss(params, x):
        y, sown = layer.apply({"params": params}, x.astype(dtype), mutable=["choices", "moe_load",
                                                                           "moe_router"])
        y = y[0] if hand_up else y
        return (y.astype(jnp.float32) * target).sum(), (y, sown["moe_load"])

    run = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))
    (_, (y, load)), grads = run(params, x)
    assert (int(load["walked"][0]) == 2048) == overflows
    assert bool(load["read"][0]) == (products_kept and not overflows)
    monkeypatch.setattr(moe, "_experts_in_buffer", _parent_s_experts_in_buffer())
    (_, (want_y, _)), want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(
        params, x)
    np.testing.assert_array_equal(np.asarray(y, np.float32), np.asarray(want_y, np.float32))
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        assert np.abs(np.asarray(ref)).max() > 0 or got.ndim == 1  # the selection bias takes none
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
def test_the_transposes_written_out_are_megablox_s_own_rule(dtype):
    """`_megablox_grads` (what the backward that reads kept products runs on a
    TPU, where `jax.vjp` of `megablox.ops.gmm` would trace a forward call
    beside them) against that `jax.vjp`, both in interpret mode: two row
    tiles of 512, three groups, one of them empty and rows past the groups'
    sum, float32 matrices cast to the rows' dtype as `grouped_matmul` casts
    them. Equal bit for bit on the rows that belong to a group (the others
    are not defined) and on every matrix: a jax whose rule changes (a dtype,
    `transpose_rhs`, `group_offset`) fails here, not silently on the chip."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from ray_tpu.ops import moe

    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    lhs = jax.random.normal(keys[0], (1024, 128), jnp.float32).astype(dtype)
    rhs = jax.random.normal(keys[1], (3, 128, 256), jnp.float32)
    g = jax.random.normal(keys[2], (1024, 256), jnp.float32).astype(dtype)
    group_sizes = jnp.asarray([600, 0, 300], jnp.int32)

    def forward(lhs, rhs):
        return megablox.gmm(lhs, rhs.astype(lhs.dtype), group_sizes, lhs.dtype, moe._GMM_TILING,
                            None, None, False, True)

    want_lhs, want_rhs = jax.vjp(forward, lhs, rhs)[1](g)
    got_lhs, got_rhs = moe._megablox_grads(lhs, rhs, group_sizes, g, interpret=True)
    assert got_lhs.dtype == want_lhs.dtype == dtype
    assert got_rhs.dtype == want_rhs.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got_lhs[:900], np.float32),
                                  np.asarray(want_lhs[:900], np.float32))
    np.testing.assert_array_equal(np.asarray(got_rhs), np.asarray(want_rhs))
    assert float(jnp.abs(want_rhs[1]).max()) == 0.0 and float(jnp.abs(want_rhs[0]).max()) > 0
