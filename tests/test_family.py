"""The seam between `TrainStep` and the families (models/__init__.py:Family):
a family stated wholly in this file trains through the step with no edit
under ray_tpu/, and the two files above the families spell none of their
names.
"""

import ast
import dataclasses
import os
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.models import Family
from ray_tpu.ops.moe import SELECTION_BIAS
from ray_tpu.parallel.mesh import ShardingRules, make_mesh, pin
from ray_tpu.parallel.train_step import TrainStep
from ray_tpu.train import _telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class ToyConfig:
    vocab_size: int = 64
    block_size: int = 32
    width: int = 16
    use_flash_attention: bool = False
    attn_fn: Any = None

    @property
    def family(self):
        return TOY_FAMILY


class Toy(nn.Module):
    """Two layers: an embedding and a head; `drift` shifts the stream under
    a stop_gradient, and the step's own sown count is what moves it."""

    config: ToyConfig
    stream: Any = None

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.width, name="emb")(idx)
        drift = self.param("drift", nn.initializers.zeros, (cfg.width,), jnp.float32)
        x = pin(x + jax.lax.stop_gradient(drift), self.stream)
        self.sow("toy_seen", "odd_tokens", (idx % 2).sum())
        return nn.Dense(cfg.vocab_size, name="head")(x)


def _odd(sown):
    return sown["toy_seen"]["odd_tokens"][0].astype(jnp.float32)


TOY_FAMILY = Family(
    module=Toy, rules=ShardingRules([(r"emb/embedding", P(None, "fsdp"))], default=P()),
    sown=("toy_seen",),
    metrics=lambda cfg, sown, params, tokens: {"toy_odd_share": _odd(sown) / tokens},
    held_leaf=("drift", lambda params, sown: {**params, "drift": params["drift"] + _odd(sown) / 1024}))


def test_a_family_stated_in_a_test_file_trains_through_the_step():
    cfg = ToyConfig()
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), weight_decay=0.5,
                   learning_rate=3e-2)
    try:
        assert isinstance(ts.model, Toy)
        state = ts.init(jax.random.PRNGKey(0))
        # the held leaf is none of the optimizer's: no moment is kept for it
        paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(state["opt_state"])[0]]
        assert any(getattr(k, "key", None) == "head" for p in paths for k in p)
        assert not [p for p in paths if any(getattr(k, "key", None) == "drift" for k in p)]
        idx = np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 16)).astype(np.int32)
        odd = float((idx % 2).sum())
        batch = ts.shard_batch({"idx": idx, "targets": np.roll(idx, -1, axis=1)})
        losses = []
        for step in range(1, 4):
            state, m = ts.step(state, batch)
            losses.append(float(m["loss"]))
            # moved by the rule alone: no moment, no decay
            np.testing.assert_array_equal(np.asarray(state["params"]["drift"]),
                                          np.full(cfg.width, step * odd / 1024, np.float32))
            assert float(m["toy_odd_share"]) == odd / idx.size
        assert losses[-1] < losses[0]
        jax.block_until_ready(m)
        ts.telemetry.settle()
        assert ts.telemetry.step_gauges == {"toy_odd_share": odd / idx.size}
        assert _telemetry.auto_report_metrics()["telemetry/toy_odd_share"] == odd / idx.size
    finally:
        _telemetry.set_current_recorder(None)


FAMILY_WORDS = {"moe_load", "moe_router", "attn_keys", "attn_gate", "ssm_stats", "kda_stats", "attn_stats",
                "losses",
                SELECTION_BIAS}


def _imported(path):
    """The file's syntax tree and every module or name it imports, dotted."""
    with open(os.path.join(ROOT, path)) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            imported |= {node.module} | {f"{node.module}.{a.name}" for a in node.names}
    return tree, imported


@pytest.mark.parametrize("path", ["ray_tpu/parallel/train_step.py", "ray_tpu/train/_telemetry.py"])
def test_the_layers_above_the_families_name_none_of_them(path):
    """Neither file imports a family's module (models/remat.py and
    models/loss.py are none), and none of its string constants is, whole, a
    collection's name or the held leaf's (docstrings may speak of them)."""
    tree, imported = _imported(path)
    families = {m for m in imported if m.startswith("ray_tpu.models.") and not m.startswith(
        ("ray_tpu.models.remat", "ray_tpu.models.loss"))}
    assert not families
    strings = {n.value for n in ast.walk(tree)
               if isinstance(n, ast.Constant) and isinstance(n.value, str)}
    assert not strings & FAMILY_WORDS


MODEL_FILES = sorted(f[:-3] for f in os.listdir(os.path.join(ROOT, "ray_tpu", "models"))
                     if f.endswith(".py") and f != "__init__.py")
SHARED = {"remat", "loss", "layers"}  # what any family's file may import of ray_tpu.models
VARIANTS = {"gpt2_moe": {"gpt2"}}  # a variant of a family imports that family: the one


@pytest.mark.parametrize("name", sorted(set(MODEL_FILES) - {"remat", "loss"}))
def test_a_family_s_file_imports_no_other_family_s(name):
    """models/__init__.py's rule: a family's file imports `ray_tpu.models`
    itself, `.remat`, `.loss` and `.layers`, and no other family's file,
    however the import is spelt, and no private name of any; `layers.py`,
    which they all import, imports no family."""
    _, imported = _imported(f"ray_tpu/models/{name}.py")
    modules = {m.split(".")[2] for m in imported if m.startswith("ray_tpu.models.")}
    allowed = SHARED - {"layers"} if name == "layers" else SHARED | VARIANTS.get(name, set())
    assert not (modules & set(MODEL_FILES)) - allowed
    assert not [m for m in imported
                if m.startswith("ray_tpu.models.") and m.rsplit(".", 1)[1].startswith("_")]
