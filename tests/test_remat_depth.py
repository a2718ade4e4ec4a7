"""models/remat.py's depths in a lowered step: a rung saved in k of its n
layers is saved in the step in exactly those layers, for a dense, a routed and
a hybrid family at small shapes; and where every depth is 0 or all of a rung's
layers, the step is the one that the rule before PR 62 lowered (one policy of
all the plan's names round every block), text for text.
"""

import collections
import importlib

import flax.linen as nn
import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import pytest

from ray_tpu.models import remat
from ray_tpu.models.gpt2 import GPT2Config
from ray_tpu.models.kimi_linear import KimiLinearConfig
from ray_tpu.models.mellum import MellumConfig
from ray_tpu.models.sdar import SDARConfig
from ray_tpu.ops import attention, kda, kda_norm, short_conv
from ray_tpu.parallel.mesh import kernel_tally, make_mesh
from ray_tpu.parallel.train_step import TrainStep

BATCH = (2, 64)
KIMI_LAYERS = ("kda", "kda", "kda", "mla", "kda")
# the delta rule's kernels' shapes: chunks of 64, heads of 128 lanes
KIMI = dict(layer_types=KIMI_LAYERS, num_held=4, kda_heads=1, kda_head_dim=128, kda_chunk=64,
            n_embd=128)
# family kind: a small configuration of it and the name whose depth the step is read for
FAMILIES = {
    "dense": (lambda: GPT2Config.tiny(n_layer=4), "attn_q"),
    "routed": (lambda: MellumConfig.tiny(), "attn_q"),
    "routed_doubled_stream": (lambda: SDARConfig.tiny(n_layer=4), "attn_q"),
    "hybrid": (lambda: KimiLinearConfig.tiny(**KIMI), "kda_states"),
}


def _plan_at(cfg, limit):
    family = importlib.import_module(type(cfg).__module__)
    return family.remat_plan(cfg, remat.step_shape(BATCH, {}), limit)


def _limit_for(cfg, name, want):
    """A chip's limit under which the family's plan saves `name` at a depth
    that `want(depth, layers that make its rung)` holds of: the rule takes a
    depth by the room, so a sweep finds one."""
    _, _, of = next(d for d in _plan_at(cfg, None).depths if name in d[0])
    whole = _plan_at(cfg, 1 << 50).reckoned_bytes
    for limit in range(whole * 10 // 9 + 4096, 0, -4096):
        if want(_plan_at(cfg, limit).depth(name), of):
            return limit
    raise AssertionError(f"no limit saves {name} in such a number of its {of} layers")


def _times_named(jaxpr, counts=None):
    """How often each `checkpoint_name` is an equation of a jaxpr or of the
    jaxprs inside its equations (a remat's second forward pass, a jit's, a
    branch's), each where it is used."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            counts[eqn.params["name"]] += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _times_named(inner, counts)
    return counts


def _on_tpu(monkeypatch):
    for mod in (attention, short_conv, kda, kda_norm):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)


def _step(cfg, monkeypatch, limit):
    monkeypatch.setattr(remat, "chip_limit", lambda stream: limit)
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
    state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct(BATCH, jnp.int32)
    return ts, state, {"idx": tok, "targets": tok}


@pytest.mark.parametrize("kind", FAMILIES)
def test_a_rung_at_depth_k_is_saved_in_the_last_k_of_its_layers(kind, monkeypatch):
    """Under a limit that has room for some layers of a rung and not all: the
    policies handed to `nn.remat`, one a block, save the rung's names in the
    last k of the layers that make them and in no other that makes them; and
    in the step's jaxpr a layer that makes a name and does not save it makes
    it twice (the second time under its remat), one that saves it once (read
    for the dense family's `mlp_up`; a name that a kernel's own forward rule
    gives is no equation of the second pass: the hybrid's calls are counted in
    the lowered step below, the routed layer's in tests/test_mellum.py)."""
    build, name = FAMILIES[kind]
    cfg = build()
    limit = _limit_for(cfg, name, lambda k, of: 0 < k < of)
    handed = []  # the policies the newest trace of the model gave `nn.remat`, in the blocks' order
    real, real_policy = nn.remat, remat.block_policy

    def recording(target, *args, policy=None, **kwargs):
        handed.append(policy)
        return real(target, *args, policy=policy, **kwargs)

    def newest(*args):
        handed.clear()
        return real_policy(*args)

    monkeypatch.setattr(nn, "remat", recording)
    monkeypatch.setattr(remat, "block_policy", newest)
    ts, state, batch = _step(cfg, monkeypatch, limit)
    traced = ts._step.trace(state, batch)
    plan = remat.traced(cfg)
    (rung, k, of), = [d for d in plan.depths if name in d[0]]
    assert 0 < k < of
    # a block's halves under a remat of their own (models/kimi_linear.py) take their block's
    halves = len(handed) // cfg.n_layer
    assert len(handed) == halves * cfg.n_layer and halves == (2 if kind == "hybrid" else 1)
    policies = handed[::halves]
    assert all(handed[i] is policies[i // halves] for i in range(len(handed)))
    assert len(policies) == cfg.n_layer == len(plan.by_layer)
    named = jax.ad_checkpoint.checkpoint_name
    for i, (policy, names) in enumerate(zip(policies, plan.by_layer)):
        for n in plan.names:
            jaxpr = jax.make_jaxpr(lambda x: named(x, n))(1.0)
            (eqn,) = jaxpr.eqns
            assert bool(policy(eqn.primitive, *eqn.invars, **eqn.params)) == (n in names), (i, n)
    makers = [i for i in range(cfg.n_layer) if kind != "hybrid" or KIMI_LAYERS[i] == "kda"]
    assert len(makers) == of
    assert [i for i in makers if name in plan.by_layer[i]] == makers[-k:]
    if kind == "dense":  # its MLP's product is named where it is made, whatever the device
        assert plan.depth("mlp_up") == 4
        assert _times_named(traced.jaxpr.jaxpr)["mlp_up"] == 4
        limit = _limit_for(cfg, "mlp_up", lambda k, of: k == 0)
        ts, state, batch = _step(cfg, monkeypatch, limit)
        assert _times_named(ts._step.trace(state, batch).jaxpr.jaxpr)["mlp_up"] == 2 * 4 - 0


def test_a_hybrid_s_kernel_runs_twice_in_the_layers_that_do_not_save_its_outputs(monkeypatch):
    """kimi_linear's layers ['kda', 'kda', 'kda', 'mla', 'kda'] with room for
    the delta rule's outputs in three KDA layers of four: layers 1, 2 and 4
    keep them, and the lowered step calls kda_fwd 4 + (4 - 3) times."""
    _on_tpu(monkeypatch)
    cfg = KimiLinearConfig.tiny(**KIMI)
    tallies = {}
    for k in (0, 3, 4):
        limit = _limit_for(cfg, "kda_states", lambda depth, of, k=k: depth == k)
        ts, state, batch = _step(cfg, monkeypatch, limit)
        text = ts._step.trace(state, batch).lower(lowering_platforms=("tpu",)).as_text()
        plan = remat.traced(cfg)
        assert plan.depth("kda_states") == k
        assert plan.saved_in("kda_states") == {
            0: (False,) * 5, 3: (False, True, True, True, True), 4: (True,) * 5}[k]
        tallies[k] = kernel_tally(text)
        assert tallies[k]["kda_fwd"] == 4 + (4 - k) and tallies[k]["kda_bwd"] == 4
        assert ts.telemetry is None and remat.traced(cfg).saved_bytes == sum(plan.layer_bytes)
    assert tallies[0]["kda_fwd"] == 8 and tallies[4]["kda_fwd"] == 4


@pytest.mark.parametrize("kind", FAMILIES)
@pytest.mark.parametrize("room", ["none", "every_rung_whole"])
def test_where_every_depth_is_none_or_all_the_step_is_the_one_policy_s(kind, room, monkeypatch):
    """With no limit (the first rung alone) and with room for everything, the
    step lowered from the plan's policies a layer is, text for text, the step
    that one policy of all the plan's names round every block lowers to: what
    the rule before PR 62 handed every block. (A hybrid's layers differ in
    what they make, and a name a layer does not make is an identity there.)"""
    from tests.test_mellum import _traced_text

    cfg = FAMILIES[kind][0]()
    limit = None if room == "none" else 1 << 50
    ts, state, batch = _step(cfg, monkeypatch, limit)
    by_depth = _traced_text(ts._step.trace(state, batch))
    plan = remat.traced(cfg)
    assert all(k in (0, of) for _, k, of in plan.depths)
    assert (len(plan.names) > 3) == (room != "none")

    def one_policy(family_plan, cfg, batch_shape, stream):
        remat._traced = (cfg, plan)
        return (jax.checkpoint_policies.save_only_these_names(*plan.names),) * cfg.n_layer

    monkeypatch.setattr(remat, "block_policy", one_policy)
    ts, state, batch = _step(cfg, monkeypatch, limit)
    assert _traced_text(ts._step.trace(state, batch)) == by_depth
