"""GPT-2 with Mixture-of-Experts FFN blocks (expert parallelism).

Green-field TPU-native capability (the reference has no MoE — SURVEY §2.4):
every ``moe_every``-th block swaps its dense MLP for a top-k routed MoE
(ray_tpu/ops/moe.py). Experts shard over the 'ep' mesh axis; everything
else follows the dense GPT-2 Megatron layout.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.models import Family
from ray_tpu.models.gpt2 import (
    GPT2Config,
    GPT2_SHARDING_PATTERNS,
    CausalSelfAttention,
    MLP,
)
from ray_tpu.models.loss import loss_fn
from ray_tpu.ops.moe import MOE_SHARDING_PATTERNS, MoE, MoEConfig
from ray_tpu.parallel.mesh import ShardingRules, pin


@dataclasses.dataclass(frozen=True)
class GPT2MoEConfig(GPT2Config):
    moe: MoEConfig = MoEConfig()
    moe_every: int = 2  # every Nth block is an MoE block (1 = all)

    @classmethod
    def tiny_moe(cls, **kw):
        base = dict(
            vocab_size=512, block_size=128, n_layer=2, n_head=4, n_embd=128,
            moe=MoEConfig(num_experts=4, top_k=2),
            moe_every=1,
        )
        base.update(kw)
        return cls(**base)

    def matmul_params(self) -> int:
        """Active parameters: an MoE block's token passes the router and
        top_k of the experts' two matrices in place of the dense MLP."""
        d = self.n_embd
        moe_blocks = self.n_layer // self.moe_every
        moe_mlp = self.moe.top_k * 8 * d * d + d * self.moe.num_experts
        return super().matmul_params() + moe_blocks * (moe_mlp - 8 * d * d)


class MoEBlock(nn.Module):
    config: GPT2MoEConfig
    stream: Any = None  # the residual stream's sharding, or None (models/gpt2.py)

    @nn.compact
    def __call__(self, x, deterministic=True):
        cfg = self.config
        x = pin(x, self.stream)
        x = pin(x + CausalSelfAttention(cfg, name="attn")(
            nn.LayerNorm(dtype=cfg.dtype, name="ln_1")(x), deterministic
        ), self.stream)
        x = x + MoE(
            d_model=cfg.n_embd,
            d_ff=4 * cfg.n_embd,
            moe=cfg.moe,
            dtype=cfg.dtype,
            name="moe",
        )(nn.LayerNorm(dtype=cfg.dtype, name="ln_2")(x), deterministic)
        return pin(x, self.stream)


class DenseBlock(nn.Module):
    config: GPT2MoEConfig
    stream: Any = None

    @nn.compact
    def __call__(self, x, deterministic=True):
        cfg = self.config
        x = pin(x, self.stream)
        x = pin(x + CausalSelfAttention(cfg, name="attn")(
            nn.LayerNorm(dtype=cfg.dtype, name="ln_1")(x), deterministic
        ), self.stream)
        x = x + MLP(cfg, name="mlp")(
            nn.LayerNorm(dtype=cfg.dtype, name="ln_2")(x), deterministic
        )
        return pin(x, self.stream)


class GPT2MoE(nn.Module):
    config: GPT2MoEConfig
    stream: Any = None  # parallel/mesh.py:stream_sharding of the step's mesh

    @nn.compact
    def __call__(self, idx, deterministic=True):
        cfg = self.config
        B, T = idx.shape
        pos = jnp.arange(T)[None]
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype, name="wte")
        wpe = nn.Embed(cfg.block_size, cfg.n_embd, dtype=cfg.dtype, name="wpe")
        x = wte(idx) + wpe(pos)
        for i in range(cfg.n_layer):
            is_moe = (i % cfg.moe_every) == (cfg.moe_every - 1)
            block = MoEBlock if is_moe else DenseBlock
            x = block(cfg, self.stream, name=f"h_{i}")(x, deterministic)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
        return wte.attend(x.astype(jnp.float32))


def init_params(config: GPT2MoEConfig, rng=None):
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    idx = jnp.zeros((2, min(8, config.block_size)), dtype=jnp.int32)
    return GPT2MoE(config).init(rng, idx)["params"]


def forward_with_aux(config: GPT2MoEConfig, params, idx):
    """Returns (logits, total_moe_aux_loss)."""
    logits, state = GPT2MoE(config).apply(
        {"params": params}, idx, mutable=["losses"]
    )
    aux_leaves = jax.tree.leaves(state.get("losses", {}))
    aux = sum(aux_leaves) if aux_leaves else jnp.float32(0.0)
    return logits, aux


def moe_loss_fn(config: GPT2MoEConfig, params, idx, targets):
    logits, aux = forward_with_aux(config, params, idx)
    return loss_fn(logits, targets) + aux


# MoE rules first: they are more specific than the dense fallbacks.
GPT2_MOE_SHARDING_RULES = ShardingRules(
    MOE_SHARDING_PATTERNS + GPT2_SHARDING_PATTERNS,
    default=P(),
)
# A capacity-routed layer sows its load-balance and router-z terms (ops/moe.py:
# MoE, "losses"): the one family whose layers add to the loss.
GPT2MoEConfig.family = Family(module=GPT2MoE, rules=GPT2_MOE_SHARDING_RULES,
                              sown=("losses",), loss_terms=("losses",))
