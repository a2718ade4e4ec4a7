"""Llama-family decoder in flax, written TPU-first.

Second model family of the zoo (beside GPT-2 and its MoE variant): RMSNorm,
rotary position embeddings, grouped-query attention, SwiGLU MLP, untied LM
head, no biases anywhere. The reference framework ships no model code at all
(Ray Train wraps user torch models — reference
python/ray/train/torch/torch_trainer.py:11); the zoo exists so the framework's
Train/Tune/bench stack has first-party TPU workloads.

TPU design notes:
- all matmuls bf16 with fp32 accumulation; params fp32 for the optimizer;
- RoPE is applied in fp32 (sin/cos precision matters at long context) and is
  sequence-shift aware so it composes with sequence parallelism: pass
  `pos_offset` to shift positions per sp shard;
- GQA repeats KV heads via a broadcast-reshape that XLA folds into the
  attention einsum — no materialized copy in HBM;
- attention uses the fused pallas flash kernel via ops/attention.py, or an
  injected `attn_fn` (e.g. a shard_map-wrapped ring attention for the 'sp'
  axis, ray_tpu/parallel/train_step.py);
- tensor-parallel layout is Megatron-style: column-parallel q/k/v/gate/up
  (shard output dim on 'tp'), row-parallel o/down (shard input dim), one psum
  per sublayer inserted by XLA from the shardings;
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
import math
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


def rms_norm(x, weight, eps):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dtype) * weight


class RMSNorm(nn.Module):
    eps: float = 1e-5
    groups: int = 1  # equal parts of the last axis, each normed on its own; one weight over all

    @nn.compact
    def __call__(self, x, gate=None, within=None):
        """The norm of x, or with `gate` of x * silu(gate) (a Mamba mixer's
        grouped norm: ops/gated_norm.py, which says what `within` is)."""
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        if self.groups == 1 and gate is None:
            return rms_norm(x, w.astype(x.dtype), self.eps)
        from ray_tpu.ops.gated_norm import gated_norm, norm_by_group

        if gate is None:
            return norm_by_group(x, w, self.eps, self.groups)
        return gated_norm(x, gate, w, self.eps, self.groups, within)


class _NormWeight(nn.Module):
    """An RMSNorm's leaf alone, `<name>/weight` (width,) float32: for a layer
    whose norm a kernel computes (`LlamaAttention._on_rows`)."""

    @nn.compact
    def __call__(self, width):
        return self.param("weight", nn.initializers.ones, (width,), jnp.float32)


def rope_angles(head_dim: int, theta: float, positions, inv_freq=None):
    """(T,) int positions -> (T, head_dim//2) fp32 angles; `inv_freq`
    (head_dim//2 floats) in place of the plain theta^(-2i/head_dim)."""
    if inv_freq is None:
        inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
    return positions.astype(jnp.float32)[:, None] * inv[None, :]


def apply_rope(x, angles, scale: float = 1.0):
    """x (B, T, H, D); angles (T, D//2). Rotate-half convention, fp32 math;
    cos and sin both times `scale` (YaRN's attention factor)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(dtype)


class LlamaAttention(nn.Module):
    """`config` is a LlamaConfig or any config with its attention fields
    (models/mellum.py). A layer of a model whose layers differ in kind says
    how it differs: `window` keys a query sees (None: all before it), its
    own rotary table `inv_freq` and the factor on the table's cos and sin,
    `qk_norm` an RMSNorm over each head of q and k before the rotary,
    `rotary` False for a layer with no positional encoding at all, `q_scale`
    a further factor on q (the kernel fixes the scores' 1/sqrt(head_dim);
    models/granite.py: a published multiplier in its place), `select`, a
    module that names the keys each query sees from the layer's input:
    `select(x, pos_offset)` gives (packed mask, its transpose, keys a query
    at most) or None where every key before a query is seen
    (models/mellum.py:Indexer), and `gate`, an output gate (Trinity's
    `gate_proj`; models/afmoe.py sets it): a fifth projection `wg` of the
    layer's input, as wide as the heads together, whose sigmoid multiplies
    the kernel's output element by element before the output projection,
    wo(y * sigmoid(W_g x)), the product in float32. The projection is the
    named residual `attn_gate` (models/remat.py), and the layer sows the
    sigmoid's mean into "attn_gate": 0.5 at initialisation."""

    config: Any
    window: Optional[int] = None
    inv_freq: Optional[tuple] = None
    rope_scale: float = 1.0
    qk_norm: bool = False
    select: Any = None
    rotary: bool = True
    q_scale: float = 1.0
    gate: bool = False

    @nn.compact
    def __call__(self, x, pos_offset=0):
        cfg = self.config
        B, T, C = x.shape
        hd = cfg.head_dim
        from ray_tpu.ops.attention import attention_path

        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=cfg.dtype, name=name)
        # heads of one vreg's 128 lanes that a layer norms or turns on their way
        # into the flash calls stay where the projections wrote them: `_on_rows`
        on_rows = (cfg.attn_fn is None and cfg.use_flash_attention and hd == 128
                   and (self.qk_norm or self.rotary) and attention_path(T) == "flash")
        heads = (lambda a, n: a) if on_rows else (lambda a, n: a.reshape(B, T, n, hd))
        q = heads(dense(cfg.n_head * hd, "wq")(x), cfg.n_head)
        k = heads(dense(cfg.n_kv_head * hd, "wk")(x), cfg.n_kv_head)
        v = dense(cfg.n_kv_head * hd, "wv")(x).reshape(B, T, cfg.n_kv_head, hd)
        if self.gate:
            g = checkpoint_name(dense(cfg.n_head * hd, "wg")(x), "attn_gate")
        # a window as long as the sequence holds all of it
        window = self.window if self.window is not None and self.window < T else None
        if on_rows:
            y = self._on_rows(x, q, k, v, pos_offset, window)
        else:
            y = self._on_heads(x, q, k, v, pos_offset, window)
        y = y.reshape(B, T, cfg.n_head * hd)
        if self.gate:
            with jax.named_scope("attn.gate"):
                open_ = jax.nn.sigmoid(g.astype(jnp.float32))
                self.sow("attn_gate", "mean", open_.mean())
                y = (y.astype(jnp.float32) * open_).astype(y.dtype)
        return dense(C, "wo")(y)

    @nn.nowrap  # no scope of its own: the layer's scopes are what they were
    def _on_heads(self, x, q, k, v, pos_offset, window):
        """The plain form, q, k, v and the result (B, T, H, D): the norm, the
        rotary and the repeat of the key-value heads as XLA compiles them."""
        cfg = self.config
        B, T, _, hd = q.shape
        if self.qk_norm:
            with jax.named_scope("attn.qk_norm"):
                q = RMSNorm(cfg.rms_eps, name="q_norm")(q)
                k = RMSNorm(cfg.rms_eps, name="k_norm")(k)
        chosen = None if self.select is None else self.select(x, pos_offset)

        if self.rotary:
            with jax.named_scope("attn.rope"):
                positions = jnp.arange(T) + pos_offset
                ang = rope_angles(hd, cfg.rope_theta, positions, self.inv_freq)
                q = apply_rope(q, ang, self.rope_scale)
                k = apply_rope(k, ang, self.rope_scale)
        if self.q_scale != 1.0:
            q = q * self.q_scale

        if cfg.n_kv_head != cfg.n_head:
            rep = cfg.n_head // cfg.n_kv_head
            # broadcast-reshape; XLA folds this into the attention contraction
            k = jnp.broadcast_to(k[:, :, :, None, :], (B, T, cfg.n_kv_head, rep, hd)
                                 ).reshape(B, T, cfg.n_head, hd)
            v = jnp.broadcast_to(v[:, :, :, None, :], (B, T, cfg.n_kv_head, rep, hd)
                                 ).reshape(B, T, cfg.n_head, hd)

        if chosen is not None:
            if cfg.attn_fn is not None:
                raise NotImplementedError("attention over selected keys runs on one device")
            from ray_tpu.ops.attention import selected_attention

            with jax.named_scope("attn.selected"):
                y = selected_attention(q, k, v, *chosen)
        elif cfg.attn_fn is not None:
            y = cfg.attn_fn(q, k, v) if window is None else cfg.attn_fn(q, k, v, window=window)
        elif cfg.use_flash_attention:
            from ray_tpu.ops.attention import causal_attention

            y = causal_attention(q, k, v, window=window)
        else:
            att = jnp.einsum("bthd,bshd->bhts", q, k,
                             preferred_element_type=jnp.float32) / math.sqrt(hd)
            mask = jnp.tril(jnp.ones((T, T), dtype=bool))
            if window is not None:
                mask = mask & ~jnp.tril(jnp.ones((T, T), dtype=bool), -window)
            att = jnp.where(mask[None, None], att, -1e30)
            att = jax.nn.softmax(att, axis=-1).astype(cfg.dtype)
            y = jnp.einsum("bhts,bshd->bthd", att, v)
        return y

    @nn.nowrap
    def _on_rows(self, x, q, k, v, pos_offset, window):
        """The same through ops/qk_prep.py on a TPU: q and k (B, T, heads *
        D) as `wq` and `wk` wrote them go normed, turned and repeated into
        the rows the flash calls take, a pass each; the norms' leaves are
        the plain form's."""
        from ray_tpu.ops.attention import _as_rows, flash_attention_rows
        from ray_tpu.ops.qk_prep import qk_prep, rope_tables

        cfg = self.config
        B, T, _, hd = v.shape
        rep = cfg.n_head // cfg.n_kv_head
        chosen = None if self.select is None else self.select(x, pos_offset)
        w_q = w_k = tables = None
        if self.qk_norm:
            w_q, w_k = (_NormWeight(name=name)(hd) for name in ("q_norm", "k_norm"))
        if self.rotary:
            with jax.named_scope("attn.rope"):
                ang = rope_angles(hd, cfg.rope_theta, jnp.arange(T) + pos_offset, self.inv_freq)
                tables = rope_tables(ang, self.rope_scale)
        with jax.named_scope("attn.qk_norm" if self.qk_norm else "attn.rope"):
            q = qk_prep(q, w_q, tables, eps=cfg.rms_eps, scale=self.q_scale)
            k = qk_prep(k, w_k, tables, rep=rep, eps=cfg.rms_eps)
        v = _as_rows(jnp.broadcast_to(v[:, :, :, None, :], (B, T, cfg.n_kv_head, rep, hd)
                                      ).reshape(B, T, cfg.n_head, hd))
        if chosen is None:
            return flash_attention_rows(q, k, v, cfg.n_head, window=window)
        with jax.named_scope("attn.selected"):
            return flash_attention_rows(q, k, v, cfg.n_head, select=chosen)


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=cfg.dtype, name=name)
        gate, up = (checkpoint_name(dense(cfg.mlp_dim, name)(x), "mlp_up")
                    for name in ("gate", "up"))
        return dense(cfg.n_embd, "down")(nn.silu(gate) * up)


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
# the rotary embedding and the repeat of the key-value heads.
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
            x = nn.remat(LlamaBlock, policy=keep)(cfg, self.stream, name=f"h_{i}")(x, pos_offset)
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


# Megatron-style TP layout + fsdp on the complementary dim. Paths are flax
# pytree paths like 'h_3/attn/wq/kernel'.
LLAMA_SHARDING_PATTERNS = [
    (r"tok_emb/embedding", P("tp", "fsdp")),
    (r"attn/w[qkv]/kernel", P("fsdp", "tp")),   # column parallel
    (r"attn/wo/kernel", P("tp", "fsdp")),       # row parallel
    (r"mlp/(gate|up)/kernel", P("fsdp", "tp")),
    (r"mlp/down/kernel", P("tp", "fsdp")),
    (r"lm_head/kernel", P("fsdp", "tp")),
    (r"norm", P()),
]
LLAMA_SHARDING_RULES = ShardingRules(LLAMA_SHARDING_PATTERNS, default=P())
LlamaConfig.family = Family(module=Llama, rules=LLAMA_SHARDING_RULES)
