"""Llama-family decoder in flax, written TPU-first.

Second model family of the zoo (beside GPT-2 and its MoE variant): RMSNorm,
rotary position embeddings, grouped-query attention, SwiGLU MLP, untied LM
head, no biases anywhere. The reference framework ships no model code at all
(Ray Train wraps user torch models — reference
python/ray/train/torch/torch_trainer.py:11); the zoo exists so the framework's
Train/Tune/bench stack has first-party TPU workloads.

The norm, the rotary, the attention and the MLP are models/layers.py's, with
their TPU design notes; the block's and the model's:
- the residual stream is pinned at the block boundaries to the sharding the
  model is given (`stream`: parallel/mesh.py:stream_sharding, the batch's own
  split), so under 'fsdp' XLA gathers a block's weights and not its
  activations; with none (one device) nothing is emitted;
- each block is wrapped in nn.remat (jax.checkpoint): the backward pass gets
  the block's input and computes its activations again, but for the
  residuals a plan keeps by name (models/remat.py): the flash kernel's output
  and logsumexp always, so the kernel runs once a layer, then what of
  REMAT_RUNGS the chip's memory allows.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.models import Family, layers, remat
from ray_tpu.models.layers import LlamaAttention, LlamaMLP, RMSNorm
from ray_tpu.models.loss import loss_fn  # noqa: F401
from ray_tpu.parallel.mesh import ShardingRules, pin


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    block_size: int = 2048
    n_layer: int = 8
    n_head: int = 8
    n_kv_head: int = 4
    n_embd: int = 512
    intermediate: Optional[int] = None  # default: the 8/3 SwiGLU rule, rounded
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    use_flash_attention: bool = True
    # Override the attention primitive, e.g. ring attention bound to a mesh.
    # Signature (q, k, v) -> out, all (B, T, H, D) with H == n_head.
    attn_fn: Any = None

    family: ClassVar[Family]  # what TrainStep asks of it: set at the foot of this file

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @property
    def mlp_dim(self) -> int:
        if self.intermediate is not None:
            return self.intermediate
        # 2/3 * 4 * n_embd rounded up to a multiple of 128 (MXU lane width).
        raw = int(8 * self.n_embd / 3)
        return (raw + 127) // 128 * 128

    def matmul_params(self) -> int:
        """q and o (d x d), k and v (d x kv heads x head_dim), gate, up and
        down (d x mlp_dim) of each block, and the untied head. The embedding
        table multiplies nothing."""
        d = self.n_embd
        kv = self.n_kv_head * self.head_dim
        return (self.n_layer * (2 * d * d + 2 * d * kv + 3 * d * self.mlp_dim)
                + self.vocab_size * d)

    def flops_per_token(self, seq_len: int) -> int:
        """The rule of GPT2Config.flops_per_token; grouped queries save
        memory, not operations."""
        attn_width = self.n_head * self.head_dim
        return 6 * self.matmul_params() + 6 * self.n_layer * seq_len * attn_width

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, block_size=128, n_layer=2, n_head=4,
                    n_kv_head=2, n_embd=128)
        base.update(kw)
        return cls(**base)

    @classmethod
    def llama_160m(cls, **kw):
        base = dict(vocab_size=32000, block_size=1024, n_layer=12, n_head=12,
                    n_kv_head=4, n_embd=768)
        base.update(kw)
        return cls(**base)


class LlamaBlock(nn.Module):
    config: LlamaConfig
    stream: Any = None  # the residual stream's sharding, or None

    @nn.compact
    def __call__(self, x, pos_offset=0):
        cfg = self.config
        x = pin(x, self.stream)
        x = pin(x + LlamaAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_eps, name="attn_norm")(x), pos_offset
        ), self.stream)
        x = x + LlamaMLP(cfg, name="mlp")(RMSNorm(cfg.rms_eps, name="mlp_norm")(x))
        return pin(x, self.stream)


# What a block's remat saves after the flash kernel's output and logsumexp
# (models/remat.py), and the ms of a step each spared for a GiB held at
# Mistral-7B's widths on a v5e (PERF.md section 6, PR 33): the outputs of
# `gate` and `up` spare those matmuls' second run (and under fsdp their
# kernels' second gather), the kernel's operands the q, k, v projections',
# the rotary embedding and the repeat of the key-value heads. On a v5e the
# cell's step has no room for both whole: until PR 62 it saved `mlp_up` whole
# and no operand; from then (models/remat.py's depths) the operands in the
# last seven layers of eight and `mlp_up` in the last six, +1.47% on the
# chip; since PR 65, held to the chip's own limit to within 64 MiB, the
# operands whole and `mlp_up` in the last seven.
REMAT_RUNGS = ((("mlp_up",), 30.7), (("attn_q", "attn_k", "attn_v"), 38.9))


def remat_plan(cfg: LlamaConfig, shape: remat.StepShape, limit) -> remat.RematPlan:
    """What the blocks of a step of this shape save across remat, under a
    chip's `limit` of bytes: a pure function of its arguments."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    name_bytes = remat.attention_bytes(shape, cfg.n_head, cfg.head_dim, itemsize)
    name_bytes["mlp_up"] = 2 * shape.rows * shape.seq_len * cfg.mlp_dim * itemsize // shape.tp
    held = remat.held_bytes(
        shape, params=cfg.matmul_params() + cfg.vocab_size * cfg.n_embd, width=cfg.n_embd,
        vocab=cfg.vocab_size, n_layer=cfg.n_layer, itemsize=itemsize)
    return remat.plan(REMAT_RUNGS, name_bytes, cfg.n_layer, held, limit)


class Llama(nn.Module):
    config: LlamaConfig
    stream: Any = None  # parallel/mesh.py:stream_sharding of the step's mesh

    @nn.compact
    def __call__(self, idx, pos_offset=0):
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype, name="tok_emb")(idx)
        keep = remat.block_policy(remat_plan, cfg, idx.shape, self.stream)
        for i in range(cfg.n_layer):
            x = nn.remat(LlamaBlock, policy=keep[i])(cfg, self.stream, name=f"h_{i}")(x, pos_offset)
        x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                          name="lm_head")(x.astype(jnp.float32))
        return logits


def init_params(config: LlamaConfig, rng=None):
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    idx = jnp.zeros((2, min(8, config.block_size)), dtype=jnp.int32)
    return Llama(config).init(rng, idx)["params"]


def forward(config: LlamaConfig, params, idx, pos_offset=0):
    return Llama(config).apply({"params": params}, idx, pos_offset)


def num_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


LLAMA_SHARDING_RULES = ShardingRules(layers.LLAMA_SHARDING_PATTERNS, default=P())
LlamaConfig.family = Family(module=Llama, rules=LLAMA_SHARDING_RULES)
