"""Decoder whose layers differ in kind, with an expert layer in the MLP's
place (Mellum 2: sliding-window and full attention mixed 3:1, every MLP a
top-8-of-64 SwiGLU expert layer, no shared expert).

Built from models/layers.py's pieces: RMSNorm, rotate-half rotary, the
grouped-query projections and the attention call are `LlamaAttention`, told
per layer what its kind changes: the window, and the rotary table (plain for
`sliding_attention`; YaRN, with its factor on cos and sin, for
`full_attention`). `head_dim` is a size of its own (heads x head_dim is not
the hidden size). The MLP is ops/moe.py's `ExpertShare`: the router sees
every expert of the layer, this program computes the experts it holds
(`first_expert`, `num_held`: one chip's share under expert parallelism) and
leaves out what the others would add.

A third kind of layer, `indexed_attention` (Keye-VL 2.0's language model:
DeepSeek-V3.2's sparse attention on a Qwen3-MoE decoder), chooses its keys:
an `Indexer` scores every causal pair from the layer's input with a few small
heads (ops/indexer.py), each query keeps its `index_top_k` best keys, and
attention runs over those alone (ops/attention.py:selected_attention). The
selection is a set of integers, so the indexer sees its input under
`stop_gradient` and takes no gradient from the loss; the configuration has
no objective of the indexer's own and none is added. `qk_norm` is Qwen3's
RMSNorm over each head of q and k.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ray_tpu.models import Family, layers, remat
from ray_tpu.models.layers import LlamaAttention, RMSNorm, apply_rope, rope_angles
from ray_tpu.ops import indexer
from ray_tpu.ops import moe
from ray_tpu.ops.moe import EXPERT_SHARE_SHARDING_PATTERNS, ExpertShare
from ray_tpu.parallel.mesh import ShardingRules, pin

SLIDING, FULL, INDEXED = "sliding_attention", "full_attention", "indexed_attention"


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
    qk_norm: bool = False  # RMSNorm over each head of q and k, before the rotary
    # of the indexed_attention layers: the indexer's heads, their width, and
    # the keys a query keeps
    index_heads: int = 16
    index_dim: int = 64
    index_top_k: int = 2048
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

    family: ClassVar[Family]  # what TrainStep asks of it: set at the foot of this file

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

    def index_params(self) -> int:
        """An indexer's three matrices: its query heads, its one key head and
        a weight a head. They take no gradient."""
        return self.n_embd * (self.index_heads * self.index_dim + self.index_dim
                              + self.index_heads)

    def flops_per_token(self, seq_len: int) -> int:
        """6 x matmul parameters + 12 x heads x head_dim x the keys a query
        sees on average: T/2 in a full layer, w - w^2/(2T) under a window w
        or a selection of w keys (GPT2Config.flops_per_token's rule, a
        window counted for what it needs). The experts' term is the
        even-routing load. An indexer runs forward only: 2 x its matrices
        and 2 x heads x width x T/2 of scores."""
        keys, index = 0.0, 0.0
        for kind in self.layer_types:
            w = {SLIDING: self.sliding_window, INDEXED: self.index_top_k}.get(kind, seq_len)
            keys += seq_len / 2 if w >= seq_len else w - w * w / (2 * seq_len)
            if kind == INDEXED:
                index += 2 * self.index_params() + self.index_heads * self.index_dim * seq_len
        return int(6 * self.matmul_params() + 12 * self.n_head * self.head_dim * keys + index)

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


class Indexer(nn.Module):
    """(B, T, C) -> which keys each query of an `indexed_attention` layer
    sees: (packed mask, its transpose, keys a query at most), or None where
    the sequence is no longer than `index_top_k` and every key before a query
    is seen. DeepSeek-V3.2's lightning indexer: `index_heads` query heads of
    `index_dim` against one key head (LayerNorm, rotary over the whole
    width at the layer's theta), ReLU, a learned weight a head and query;
    the top `index_top_k` of each query's causal row, exactly
    (ops/indexer.py). Sows into "attn_keys" (TrainStep's telemetry) the mean
    number of keys a query kept, `selected`, and of compare-and-count passes
    a block of rows took to find them, `select_passes`."""

    config: MellumConfig

    @nn.compact
    def __call__(self, x, pos_offset=0):
        cfg = self.config
        B, T, _ = x.shape
        x = jax.lax.stop_gradient(x)
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=cfg.dtype, name=name)
        with jax.named_scope("attn.index"):
            q = dense(cfg.index_heads * cfg.index_dim, "wq")(x).reshape(
                B, T, cfg.index_heads, cfg.index_dim)
            k = nn.LayerNorm(dtype=cfg.dtype, name="k_norm")(dense(cfg.index_dim, "wk")(x))
            w = dense(cfg.index_heads, "ww")(x)
            if cfg.index_top_k >= T:  # the matrices are made at any length
                return None
            ang = rope_angles(cfg.index_dim, cfg.rope_theta, jnp.arange(T) + pos_offset)
            q, k = apply_rope(q, ang), apply_rope(k[:, :, None, :], ang)[:, :, 0, :]
            # the matrices take no gradient either: a set of integers has none
            scores = indexer.index_scores(*jax.lax.stop_gradient((q, k, w)))
        with jax.named_scope("attn.select"):
            mask, passes = indexer.index_select(scores, cfg.index_top_k)
            self.sow("attn_keys", "selected",
                     jax.lax.population_count(mask).sum(-1).astype(jnp.float32).mean())
            self.sow("attn_keys", "select_passes", passes.astype(jnp.float32).mean())
            return mask, indexer.transpose_packed(mask), cfg.index_top_k


class MellumBlock(nn.Module):
    config: MellumConfig
    kind: str
    stream: Any = None  # the residual stream's sharding, or None (models/llama.py)
    # whether the blocks' remat plan keeps any of the expert layer's products
    # (ops/moe.py:ExpertShare.products_kept)
    products_kept: bool = True

    @nn.compact
    def __call__(self, x, pos_offset=0):
        cfg = self.config
        x = pin(x, self.stream)
        inv_freq, scale = cfg.rotary(self.kind)
        attn = LlamaAttention(
            cfg, window=cfg.sliding_window if self.kind == SLIDING else None,
            inv_freq=inv_freq, rope_scale=scale, qk_norm=cfg.qk_norm,
            select=Indexer(cfg, name="indexer") if self.kind == INDEXED else None, name="attn")
        x = pin(x + attn(RMSNorm(cfg.rms_eps, name="attn_norm")(x), pos_offset), self.stream)
        moe = ExpertShare(cfg.n_embd, cfg.expert_dim, cfg.num_experts, cfg.top_k,
                          cfg.first_expert, cfg.num_held, cfg.dtype,
                          products_kept=self.products_kept, name="moe")
        return pin(x + moe(RMSNorm(cfg.rms_eps, name="moe_norm")(x)), self.stream)


# What a block's remat saves after the first rung (the flash kernel's output
# and logsumexp, and the expert layer's choices and plan, `moe_plan`: integers,
# 2.6 MB a layer: models/remat.py): the kernel's operands, and the ms of a step
# they spared for a GiB held in the benchmark's cell on a v5e (PERF.md section
# 6, PR 33); the expert layer's three products (ops/moe.py:KEPT_PRODUCTS), a
# rung each so that a step with room for one takes one (my chip runs, PR 45,
# calls 1 and 7; one process a set of names, 8 steps by the host's clock, not
# the benchmark's 40 s window; PERF.md section 6): the gate and the up
# product spare a grouped matmul each under remat, together 2.7 ms of the
# 355.97 a step takes with no product kept, for 0.33 GiB each (half of the
# pair's each: the rule adds worths; against the same form of the layer with
# nothing kept, which is 4.1 ms slower than the form a plan without products
# takes, they were 5.9 and 6.3 alone and 7.3 together); the down product its
# matmul and silu(gate) * up before it, 4.9 ms for 0.84 GiB beside the pair.
# The price is a step that overflowed its headroom: with the pair kept it
# pays the headroom buffer's forward work on top of its own, 478.19 ms
# against the parent's 440.55 (call 7, every layer forced to overflow).
# On a v5e the cell has no room for all three products: until PR 62 it kept
# the gate's and the up's whole; from then (models/remat.py's depths) the
# down product in the last three layers of four and the gate's in the last
# two, +0.1 to +0.4% on the chip (PERF.md section 6, PR 62); since PR 65,
# held to the chip's own limit to within 64 MiB, the down and the gate's
# whole and the up's in the last three layers.
REMAT_RUNGS = ((("attn_q", "attn_k", "attn_v"), 37.6),
               (("moe_gate",), 4.1), (("moe_up",), 4.1), (("moe_out",), 5.8))


def remat_plan(cfg: MellumConfig, shape: remat.StepShape, limit) -> remat.RematPlan:
    """What the blocks of a step of this shape save across remat, under a
    chip's `limit` of bytes: a pure function of its arguments."""
    d, hd = cfg.n_embd, cfg.head_dim
    layer = (2 * d * cfg.n_head * hd + 2 * d * cfg.n_kv_head * hd + d * cfg.num_experts
             + cfg.experts_held * 3 * d * cfg.expert_dim)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    # the expert layer's backward works in buffers of a row an assignment,
    # every token's top_k of them (both of `ExpertShare`'s buffers are
    # compiled, and the one of every assignment sets the size): 6.5 such
    # buffers, 7.5 until the gradient's rows were gathered in the stream's
    # dtype (PR 44; PERF.md section 6, PR 45, has the readings)
    routed = int(6.5 * shape.rows * shape.seq_len * cfg.top_k * d * itemsize)
    name_bytes = remat.attention_bytes(shape, cfg.n_head, hd, itemsize)
    name_bytes.update(moe.named_bytes(shape.rows * shape.seq_len, cfg.top_k, cfg.experts_held,
                                      cfg.num_experts, d, cfg.expert_dim, itemsize))
    first = remat.FIRST_RUNG + (moe.ROUTE_PLAN,)
    indexed = remat.layers_of(cfg.layer_types, INDEXED)
    if indexed and cfg.index_top_k < shape.seq_len:
        # an indexed layer also holds its selection, the transposed
        # relation's packed mask (int32 words: what the one backward call
        # reads), in every layer where it holds any (a model's layers are of
        # this kind or none is); and its forward works in a row of float32
        # scores a query, read once by the selection
        layer += cfg.index_params()
        first += ("attn_sel",)
        name_bytes["attn_sel"] = shape.rows * shape.seq_len * max(128, shape.seq_len // 32) * 4
        routed = max(routed, 2 * shape.rows * shape.seq_len * shape.seq_len * 4)
    held = remat.held_bytes(
        shape, params=cfg.n_layer * layer + 2 * cfg.vocab_size * d, width=d,
        vocab=cfg.vocab_size, n_layer=cfg.n_layer, itemsize=itemsize, block=routed)
    return remat.plan(REMAT_RUNGS, name_bytes, cfg.n_layer, held, limit, first,
                      {"attn_sel": indexed})


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
        keep = remat.block_policy(remat_plan, cfg, idx.shape, self.stream)
        products = remat.traced(cfg).saved_in(*moe.KEPT_PRODUCTS)
        for i, kind in enumerate(cfg.layer_types):
            x = nn.remat(MellumBlock, policy=keep[i])(
                cfg, kind, self.stream, products[i], name=f"h_{i}")(x, pos_offset)
        x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")(x.astype(jnp.float32))


MELLUM_SHARDING_RULES = ShardingRules(
    EXPERT_SHARE_SHARDING_PATTERNS + layers.LLAMA_SHARDING_PATTERNS, default=P())


def step_metrics(cfg, sown, params, tokens):
    """`Family.metrics`: the expert layers' (a dropless layer adds no term to
    the loss: what goes out is the rows its held experts worked on), and of
    the layers that select their keys (`Indexer`; it sows nothing at a length
    the selection says nothing) the keys a query kept and the compare-and-count
    passes over its row's scores that finding them took a block of rows, means
    over those layers."""
    metrics = moe.step_metrics(cfg, sown, params, tokens)
    selected = jax.tree_util.tree_leaves_with_path(sown.get("attn_keys", {}))
    for name, metric in (("selected", "attn_keys_selected_mean"),
                         ("select_passes", "attn_select_passes_mean")):
        sowed = [x for path, x in selected if jax.tree_util.DictKey(name) in path]
        if sowed:
            metrics[metric] = sum(sowed) / len(sowed)
    return metrics


MellumConfig.family = Family(module=Mellum, rules=MELLUM_SHARDING_RULES,
                             sown=("moe_load", "attn_keys"), metrics=step_metrics)
