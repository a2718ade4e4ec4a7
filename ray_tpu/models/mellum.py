"""Decoder whose layers differ in kind, with an expert layer in the MLP's
place (Mellum 2: sliding-window and full attention mixed 3:1, every MLP a
top-8-of-64 SwiGLU expert layer, no shared expert).

Built from models/llama.py's pieces: RMSNorm, rotate-half rotary, the
grouped-query projections and the attention call are `LlamaAttention`, told
per layer what its kind changes: the window, and the rotary table (plain for
`sliding_attention`; YaRN, with its factor on cos and sin, for
`full_attention`). `head_dim` is a size of its own (heads x head_dim is not
the hidden size). The MLP is ops/moe.py's `ExpertShare`: the router sees
every expert of the layer, this program computes the experts it holds
(`first_expert`, `num_held`: one chip's share under expert parallelism) and
leaves out what the others would add.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ray_tpu.models import remat
from ray_tpu.models.llama import LLAMA_SHARDING_PATTERNS, LlamaAttention, RMSNorm, loss_fn  # noqa: F401
from ray_tpu.ops.moe import EXPERT_SHARE_SHARDING_PATTERNS, ExpertShare
from ray_tpu.parallel.mesh import ShardingRules, pin

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN (Peng et al. 2023) as transformers' `_compute_yarn_parameters`
    takes it from a config's rope parameters, `truncate` at its default."""

    factor: float
    original_max_position_embeddings: int
    attention_factor: float
    beta_fast: float = 32.0
    beta_slow: float = 1.0


def yarn_inv_freq(head_dim: int, theta: float, yarn: YarnScaling) -> np.ndarray:
    """(head_dim // 2,) float32: frequencies that turn more than beta_fast
    times within the original length are kept, those that turn less than
    beta_slow times are divided by `factor`, a linear ramp between."""
    f32 = np.float32
    pos_freq = f32(theta) ** (np.arange(0, head_dim, 2, dtype=f32) / f32(head_dim))
    extrapolation, interpolation = f32(1) / pos_freq, f32(1) / (f32(yarn.factor) * pos_freq)

    def correction(turns):  # the dimension that turns `turns` times in the original length
        return (head_dim * math.log(yarn.original_max_position_embeddings / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(yarn.beta_fast)), 0)
    high = min(math.ceil(correction(yarn.beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2, dtype=f32) - f32(low))
                   / f32(max(high - low, 1e-3)), 0, 1).astype(f32)
    return (interpolation * ramp + extrapolation * (f32(1) - ramp)).astype(f32)


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    block_size: int = 8192
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    n_embd: int = 2304
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    sliding_window: int = 1024
    rope_theta: float = 5e5
    yarn: Optional[YarnScaling] = None  # of the full_attention layers
    rms_eps: float = 1e-6
    expert_dim: int = 896
    num_experts: int = 64  # the router's width
    top_k: int = 8
    first_expert: int = 0
    num_held: Optional[int] = None  # experts computed here; None: all
    dtype: Any = jnp.bfloat16
    use_flash_attention: bool = True
    # As LlamaConfig.attn_fn, called with window=<n> in a windowed layer.
    attn_fn: Any = None
    # Steps over which TrainStep's learning rate climbs from 0. The rows an
    # expert works on are what the router sends it: at the full rate from
    # step 0 the routing collapses within 30 steps (measured on the v5e,
    # PERF.md section 6, PR 29), and with it the step's work.
    lr_warmup_steps: int = 2000

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def experts_held(self) -> int:
        return self.num_experts if self.num_held is None else self.num_held

    def matmul_params(self) -> int:
        """q and o (d x heads x head_dim), k and v (d x kv heads x head_dim)
        and the router of each layer, the expert matrices a token meets at
        even routing (top_k experts, of which held / num_experts are here),
        and the untied head. The embedding table multiplies nothing."""
        d, hd = self.n_embd, self.head_dim
        attn = 2 * d * self.n_head * hd + 2 * d * self.n_kv_head * hd
        experts = self.top_k * self.experts_held / self.num_experts * 3 * d * self.expert_dim
        return int(self.n_layer * (attn + d * self.num_experts + experts)
                   + self.vocab_size * d)

    def flops_per_token(self, seq_len: int) -> int:
        """6 x matmul parameters + 12 x heads x head_dim x the keys a query
        sees on average: T/2 in a full layer, w - w^2/(2T) under a window w
        (GPT2Config.flops_per_token's rule, a window counted for what it
        needs). The experts' term is the even-routing load."""
        keys = 0.0
        for kind in self.layer_types:
            w = self.sliding_window if kind == SLIDING else seq_len
            keys += seq_len / 2 if w >= seq_len else w - w * w / (2 * seq_len)
        return int(6 * self.matmul_params() + 12 * self.n_head * self.head_dim * keys)

    def rotary(self, kind: str):
        """(inv_freq as a tuple or None for the plain table, factor on cos
        and sin) of a layer kind, computed once in float32."""
        if kind == FULL and self.yarn is not None:
            return (tuple(yarn_inv_freq(self.head_dim, self.rope_theta, self.yarn).tolist()),
                    float(self.yarn.attention_factor))
        return None, 1.0

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, block_size=128, n_head=4, n_kv_head=2, head_dim=16,
                    n_embd=48, sliding_window=16, expert_dim=32, num_experts=8, top_k=2,
                    yarn=YarnScaling(4.0, 32, 1.1386))
        base.update(kw)
        return cls(**base)


class MellumBlock(nn.Module):
    config: MellumConfig
    kind: str
    stream: Any = None  # the residual stream's sharding, or None (models/llama.py)

    @nn.compact
    def __call__(self, x, pos_offset=0):
        cfg = self.config
        x = pin(x, self.stream)
        inv_freq, scale = cfg.rotary(self.kind)
        attn = LlamaAttention(
            cfg, window=cfg.sliding_window if self.kind == SLIDING else None,
            inv_freq=inv_freq, rope_scale=scale, name="attn")
        x = pin(x + attn(RMSNorm(cfg.rms_eps, name="attn_norm")(x), pos_offset), self.stream)
        moe = ExpertShare(cfg.n_embd, cfg.expert_dim, cfg.num_experts, cfg.top_k,
                          cfg.first_expert, cfg.num_held, cfg.dtype, name="moe")
        return pin(x + moe(RMSNorm(cfg.rms_eps, name="moe_norm")(x)), self.stream)


# What a block's remat saves after the flash kernel's output and logsumexp
# (models/remat.py): the kernel's operands, and the ms of a step they spared
# for a GiB held in the benchmark's cell on a v5e (PERF.md section 6, PR 33).
# The expert layer's own residuals have no names yet.
REMAT_RUNGS = ((("attn_q", "attn_k", "attn_v"), 37.6),)


def remat_plan(cfg: MellumConfig, shape: remat.StepShape, limit) -> remat.RematPlan:
    """What the blocks of a step of this shape save across remat, under a
    chip's `limit` of bytes: a pure function of its arguments."""
    d, hd = cfg.n_embd, cfg.head_dim
    layer = (2 * d * cfg.n_head * hd + 2 * d * cfg.n_kv_head * hd + d * cfg.num_experts
             + cfg.experts_held * 3 * d * cfg.expert_dim)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    # the expert layer's backward works in buffers of a row an assignment,
    # every token's top_k of them: 7.5 such buffers in the step compiled for
    # a v5e at the benchmark's cell and at twice its rows (4.2 and 8.2 GiB)
    routed = int(7.5 * shape.rows * shape.seq_len * cfg.top_k * d * itemsize)
    held = remat.held_bytes(
        shape, params=cfg.n_layer * layer + 2 * cfg.vocab_size * d, width=d,
        vocab=cfg.vocab_size, n_layer=cfg.n_layer, itemsize=itemsize, block=routed)
    return remat.plan(REMAT_RUNGS, remat.attention_bytes(shape, cfg.n_head, hd, itemsize),
                      cfg.n_layer, held, limit)


class Mellum(nn.Module):
    config: MellumConfig
    stream: Any = None  # parallel/mesh.py:stream_sharding of the step's mesh

    @nn.compact
    def __call__(self, idx, pos_offset=0):
        cfg = self.config
        # Unit variance an element: at flax's 1/sqrt(d) the running mean that
        # causal attention adds swamps a token's own embedding, neighbouring
        # tokens route alike and the experts' load swings with the data.
        x = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype, name="tok_emb",
                     embedding_init=nn.initializers.normal(1.0))(idx)
        keep = remat.block_policy(remat_plan, cfg, idx.shape, self.stream)  # as models/llama.py's
        for i, kind in enumerate(cfg.layer_types):
            x = nn.remat(MellumBlock, policy=keep)(
                cfg, kind, self.stream, name=f"h_{i}")(x, pos_offset)
        x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")(x.astype(jnp.float32))


MELLUM_SHARDING_RULES = ShardingRules(
    EXPERT_SHARE_SHARDING_PATTERNS + LLAMA_SHARDING_PATTERNS, default=P())
