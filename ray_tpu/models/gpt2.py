"""GPT-2 in flax, written TPU-first.

This is the framework's flagship train/bench model (BASELINE.json config 2:
GPT-2-124M data-parallel). Design notes for the MXU/HBM:

- all matmuls in bf16 with fp32 accumulation (`preferred_element_type`),
  params kept in fp32 for the optimizer, cast per-step;
- attention uses the fused pallas flash kernel when available
  (ray_tpu/ops/attention.py), falling back to a plain einsum softmax that XLA
  fuses well on TPU;
- static shapes everywhere; the whole step is one jit;
- tensor-parallel PartitionSpecs follow the Megatron layout: column-parallel
  qkv/fc1 (shard output dim on 'tp'), row-parallel proj/fc2 (shard input dim),
  so each block needs exactly one psum on the 'tp' axis per sublayer — XLA
  inserts it from the shardings;
- 'fsdp' shards every weight's first dim (ZeRO-3-style gather-per-layer under
  pjit), 'sp' shards the sequence dim of activations; the residual stream is
  pinned at the block boundaries to the sharding the model is given (`stream`:
  parallel/mesh.py:stream_sharding), so it is the weights that are gathered.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, ClassVar, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.models import Family, remat
from ray_tpu.models.loss import loss_fn  # noqa: F401
from ray_tpu.parallel.mesh import ShardingRules, pin


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    block_size: int = 1024
    n_layer: int = 12
    n_head: int = 12
    n_embd: int = 768
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    use_flash_attention: bool = True
    # Override the attention primitive, e.g. a shard_map-wrapped ring
    # attention bound to a mesh (ray_tpu/parallel/train_step.py). Signature
    # (q, k, v) -> out, all (B, T, H, D).
    attn_fn: Any = None

    family: ClassVar[Family]  # what TrainStep asks of it: set at the foot of this file

    @classmethod
    def gpt2_124m(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, block_size=128, n_layer=2, n_head=4, n_embd=128)
        base.update(kw)
        return cls(**base)

    def matmul_params(self) -> int:
        """Parameters a token is multiplied by: qkv, the attention projection
        and the two MLP matrices of each block, and the head. The embedding
        tables (wte as a look-up, wpe) multiply nothing; the tied head does,
        once."""
        d = self.n_embd
        return self.n_layer * 12 * d * d + self.vocab_size * d

    def flops_per_token(self, seq_len: int) -> int:
        """Model FLOPs a trained token needs at this sequence length: 6 x
        matmul parameters (2 forward, 4 backward) + causal attention, QK^T
        and PV at 2*T*d a token over all heads, half of it under the mask,
        three times over: 6*L*T*n_head*head_dim. Recomputed operations
        (remat, the flash kernels' own recompute) are not counted."""
        return 6 * self.matmul_params() + 6 * self.n_layer * seq_len * self.n_embd


class CausalSelfAttention(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True):
        cfg = self.config
        B, T, C = x.shape
        head_dim = C // cfg.n_head
        qkv = nn.Dense(3 * C, dtype=cfg.dtype, name="c_attn")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, cfg.n_head, head_dim)
        k = k.reshape(B, T, cfg.n_head, head_dim)
        v = v.reshape(B, T, cfg.n_head, head_dim)

        if cfg.attn_fn is not None:
            y = cfg.attn_fn(q, k, v)
        elif cfg.use_flash_attention:
            from ray_tpu.ops.attention import causal_attention

            y = causal_attention(q, k, v)
        else:
            att = jnp.einsum(
                "bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32
            ) / math.sqrt(head_dim)
            mask = jnp.tril(jnp.ones((T, T), dtype=bool))
            att = jnp.where(mask[None, None], att, -1e30)
            att = jax.nn.softmax(att, axis=-1).astype(cfg.dtype)
            y = jnp.einsum("bhts,bshd->bthd", att, v)
        y = y.reshape(B, T, C)
        return nn.Dense(C, dtype=cfg.dtype, name="c_proj")(y)


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True):
        cfg = self.config
        h = checkpoint_name(nn.Dense(4 * cfg.n_embd, dtype=cfg.dtype, name="c_fc")(x), "mlp_up")
        h = nn.gelu(h, approximate=True)
        return nn.Dense(cfg.n_embd, dtype=cfg.dtype, name="c_proj")(h)


class Block(nn.Module):
    config: GPT2Config
    stream: Any = None  # the residual stream's sharding, or None

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


# What a block's remat saves after the flash kernel's output and logsumexp
# (models/remat.py), and the ms of a step each spared for a GiB held at
# GPT-2 small's widths on a v5e: the kernel's operands spare the c_attn
# matmul's second run and the split of its output into q, k and v that is
# made again with it (7.9 at T=256, 7.1 at T=1,024: PERF.md section 6,
# PR 42; 14.0 while the layout copies round the kernel were made again too,
# PR 33); the c_fc output spares that matmul's.
REMAT_RUNGS = ((("attn_q", "attn_k", "attn_v"), 7.5), (("mlp_up",), 6.5))


def remat_plan(cfg: GPT2Config, shape: remat.StepShape, limit) -> remat.RematPlan:
    """What the blocks of a step of this shape save across remat, under a
    chip's `limit` of bytes: a pure function of its arguments."""
    d, itemsize = cfg.n_embd, jnp.dtype(cfg.dtype).itemsize
    name_bytes = remat.attention_bytes(shape, cfg.n_head, d // cfg.n_head, itemsize)
    name_bytes["mlp_up"] = shape.rows * shape.seq_len * 4 * d * itemsize // shape.tp
    held = remat.held_bytes(
        shape, params=cfg.matmul_params() + cfg.block_size * d, width=d,
        vocab=cfg.vocab_size, n_layer=cfg.n_layer, itemsize=itemsize)
    return remat.plan(REMAT_RUNGS, name_bytes, cfg.n_layer, held, limit)


class GPT2(nn.Module):
    config: GPT2Config
    stream: Any = None  # parallel/mesh.py:stream_sharding of the step's mesh

    @nn.compact
    def __call__(self, idx, deterministic=True):
        cfg = self.config
        B, T = idx.shape
        pos = jnp.arange(T)[None]
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype, name="wte")
        wpe = nn.Embed(cfg.block_size, cfg.n_embd, dtype=cfg.dtype, name="wpe")
        x = wte(idx) + wpe(pos)
        # remat each block (jax.checkpoint): the backward pass gets the
        # block's input and computes its activations again, but for the
        # residuals the plan keeps by name: the flash kernel's output and
        # logsumexp always, so the kernel runs once a layer, then
        # what of REMAT_RUNGS the chip's memory allows.
        keep = remat.block_policy(remat_plan, cfg, idx.shape, self.stream)
        for i in range(cfg.n_layer):
            x = nn.remat(Block, policy=keep[i])(cfg, self.stream, name=f"h_{i}")(x, deterministic)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
        # weight-tied head
        logits = wte.attend(x.astype(jnp.float32))
        return logits


def init_params(config: GPT2Config, rng=None):
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    model = GPT2(config)
    idx = jnp.zeros((2, min(8, config.block_size)), dtype=jnp.int32)
    return model.init(rng, idx)["params"]


def forward(config: GPT2Config, params, idx):
    return GPT2(config).apply({"params": params}, idx)


def num_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# Megatron-style tensor-parallel layout + fsdp on the complementary dim.
# Rule paths match flax param pytree paths like 'h_3/attn/c_attn/kernel'.
GPT2_SHARDING_PATTERNS = [
    (r"wte/embedding", P("tp", "fsdp")),
    (r"wpe/embedding", P(None, "fsdp")),
    (r"attn/c_attn/kernel", P("fsdp", "tp")),   # column parallel
    (r"attn/c_attn/bias", P("tp")),
    (r"attn/c_proj/kernel", P("tp", "fsdp")),   # row parallel
    (r"attn/c_proj/bias", P()),
    (r"mlp/c_fc/kernel", P("fsdp", "tp")),
    (r"mlp/c_fc/bias", P("tp")),
    (r"mlp/c_proj/kernel", P("tp", "fsdp")),
    (r"mlp/c_proj/bias", P()),
    (r"ln_", P()),
]
GPT2_SHARDING_RULES = ShardingRules(GPT2_SHARDING_PATTERNS, default=P())
GPT2Config.family = Family(module=GPT2, rules=GPT2_SHARDING_RULES)
