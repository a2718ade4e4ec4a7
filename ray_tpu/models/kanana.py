"""Decoder whose attention reads its keys and values through a latent (MLA)
and whose MLPs are dense in the leading layers and routed after them, with a
shared expert beside the routed ones (kanana-2-30b-a3b-instruct-2601:
`model_type` deepseek_v3; HF `modeling_deepseek_v3.py`).

The equations, with d the hidden size, H heads and x the residual stream:

    x0 = E[idx]
    a layer:  x <- x + attn(RMSNorm(x));  x <- x + ffn(RMSNorm(x))
    logits = W_head RMSNorm(x)       untied; operands in the compute dtype,
                                     float32 sums
    loss   = mean cross-entropy of the next token             float32

attention: models/layers.py's `LatentAttention`, which states the equations
and what computes them, as it stands by default (every layer turns q_pe and
k_pe); a head's widths `nope_dim` (128), `rope_dim` (64) and `v_dim` (128),
the latent `kv_latent` (512) wide.

ffn of the first `num_dense_layers` layers: W_down (silu(W_gate h) * W_up h),
`intermediate` wide. Of the others: ops/moe.py's `ExpertShare` with the
SIGMOID router (models/lfm2.py says what it computes; here top 6 of 128, the
gates over their sum + 1e-20, times 2.448), of which this program computes
`num_held` experts from `first_expert` on, **plus the shared expert**, one
SwiGLU `shared_experts * expert_dim` wide that every token passes through:
under a share it is whole on every chip, and counted once when shares are
summed.

All blocks are one parameter group, `p_0`, which sows its routed blocks'
choices stacked, (routed blocks, B, T, top_k): `layers.sow_choices`. The
bias's rule, the initialisers and the absence of an auxiliary loss are under
`assumed` in bench/configs/kanana2_30b_l5_ep8.json.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.models import Family, layers, remat
from ray_tpu.models.layers import LatentAttention, LlamaMLP, RMSNorm, SharedExpert
from ray_tpu.ops import moe
from ray_tpu.ops.moe import EXPERT_SHARE_SHARDING_PATTERNS, SIGMOID, ExpertShare
from ray_tpu.parallel.mesh import ShardingRules, pin


@dataclasses.dataclass(frozen=True)
class KananaConfig:
    vocab_size: int = 128256
    block_size: int = 32768
    n_embd: int = 2048
    n_layer: int = 48
    num_dense_layers: int = 1
    n_head: int = 32
    kv_latent: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    intermediate: int = 6144  # of the dense layers' MLP
    expert_dim: int = 768
    num_experts: int = 128  # the router's width
    top_k: int = 6
    first_expert: int = 0
    num_held: Optional[int] = None  # experts computed here; None: all
    shared_experts: int = 2  # one SwiGLU this many expert widths wide
    routed_scaling: float = 2.448
    gate_eps: float = 1e-20
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    use_flash_attention: bool = True
    attn_fn: Any = None  # set under a mesh, which the latent pair has no form for yet
    lr_warmup_steps: int = 2000  # as MellumConfig.lr_warmup_steps

    family: ClassVar[Family]  # what TrainStep asks of it: set at the foot of this file

    @property
    def mlp_dim(self) -> int:
        return self.intermediate

    @property
    def shared_dim(self) -> int:
        return self.shared_experts * self.expert_dim

    @property
    def experts_held(self) -> int:
        return self.num_experts if self.num_held is None else self.num_held

    @property
    def routed_layers(self) -> int:
        return max(0, self.n_layer - self.num_dense_layers)

    def attention_params(self) -> int:
        d, h = self.n_embd, self.n_head
        return (d * h * (self.nope_dim + self.rope_dim) + d * (self.kv_latent + self.rope_dim)
                + self.kv_latent * h * (self.nope_dim + self.v_dim) + h * self.v_dim * d)

    def matmul_params(self) -> int:
        """Each layer's four attention matrices and its MLP (the dense one's
        three matrices; a routed one's router, the shared expert whole and
        the expert matrices a token meets at even routing: top_k experts, of
        which held / num_experts are here), and the untied head. The
        embedding is a look-up."""
        d = self.n_embd
        experts = self.top_k * self.experts_held / self.num_experts * 3 * d * self.expert_dim
        routed = d * self.num_experts + 3 * d * self.shared_dim + experts
        dense = self.n_layer - self.routed_layers
        return int(self.n_layer * self.attention_params() + dense * 3 * d * self.intermediate
                   + self.routed_layers * routed + self.vocab_size * d)

    def flops_per_token(self, seq_len: int) -> int:
        """6 x matmul parameters + the causal term, which for a score nope +
        rope deep and a value v wide is 6 T H (nope + rope + v) / 2 a layer
        (GPT2Config.flops_per_token's rule, where both are the head's one
        width)."""
        return (6 * self.matmul_params() + 3 * self.n_layer * seq_len * self.n_head
                * (self.nope_dim + self.rope_dim + self.v_dim))

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, block_size=128, n_embd=64, n_layer=3, n_head=4,
                    kv_latent=32, nope_dim=16, rope_dim=8, v_dim=16, intermediate=96,
                    expert_dim=32, num_experts=8, top_k=2)
        base.update(kw)
        return cls(**base)


class KananaBlock(nn.Module):
    """A block and the choices of its expert layer, (x, (B, T, top_k)); a
    block with a dense MLP hands up None."""

    config: KananaConfig
    routed: bool
    stream: Any = None  # the residual stream's sharding, or None (models/llama.py)

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = pin(x, self.stream)
        x = pin(x + LatentAttention(cfg, name="attn")(RMSNorm(cfg.rms_eps, name="attn_norm")(x)),
                self.stream)
        h = RMSNorm(cfg.rms_eps, name="mlp_norm")(x)
        if not self.routed:
            return pin(x + LlamaMLP(cfg, name="mlp")(h), self.stream), None
        y, chosen = ExpertShare(
            cfg.n_embd, cfg.expert_dim, cfg.num_experts, cfg.top_k, cfg.first_expert,
            cfg.num_held, cfg.dtype, router=SIGMOID, scaling=cfg.routed_scaling,
            hand_up_choices=True, gate_eps=cfg.gate_eps,
            products_kept=False,  # no plan of this family keeps them: REMAT_RUNGS says why
            name="moe")(h)
        with jax.named_scope("moe.shared"):
            y = y + SharedExpert(cfg, name="shared")(h)
        return pin(x + y, self.stream), chosen


# What a block's remat saves after the first rung (the latent pair's output
# and logsumexp), and the ms of a step each spared for a GiB held in the
# benchmark's cell on a v5e (my chip run, PR 43, call 2; PERF.md section 6:
# 552.35 ms a step with the first rung alone): the kernels' operands spare
# the four projections before them, the latent's norm and the rotary (20.44
# ms for 2.21 GiB); the shared expert's gate and up those two matmuls in
# four layers (2.66 ms for 0.375 GiB); the dense MLP's likewise in one (4.28
# ms for 0.375 GiB). The first rung also holds the expert layers' choices
# and plans (`moe_plan`: integers, 2.1 MB a layer; 1.9 ms a step). The expert
# layer's three products (ops/moe.py:KEPT_PRODUCTS) are no rung here: in this
# cell they spared nothing (my chip run, PR 45, call 7; one process a set, 8
# steps by the host's clock: 495.87 ms a step with none of them, 496.58 with
# the gate and the up product, 0.21 GiB, 496.00 with all three, 0.49 GiB).
# These experts are 768 wide, their twelve forward calls under remat 5.7 ms
# a step, and the form of the layer that reads kept products costs that
# much again over the form that keeps none (the rows gathered and silu(gate)
# * up once more, a pass over each residual, the sums' gather of a kept
# `moe_out` from HBM): so the layer is told none is kept and takes that form.
REMAT_RUNGS = ((("attn_q", "attn_k", "attn_v", "attn_q_shared", "attn_k_shared"), 9.3),
               (("shared_up",), 7.1), (("mlp_up",), 11.4))


def remat_plan(cfg: KananaConfig, shape: remat.StepShape, limit) -> remat.RematPlan:
    """What the blocks of a step of this shape save across remat, under a
    chip's `limit` of bytes: a pure function of its arguments. A name's
    bytes are one layer's, and `made_in` says which layers make it."""
    d, itemsize = cfg.n_embd, jnp.dtype(cfg.dtype).itemsize
    tokens = shape.rows * shape.seq_len
    head = lambda width: tokens * cfg.n_head * width * itemsize
    dense = cfg.n_layer - cfg.routed_layers
    name_bytes = dict(
        attn_out=head(cfg.v_dim), attn_lse=tokens * cfg.n_head * 4,
        attn_q=head(cfg.nope_dim), attn_k=head(cfg.nope_dim), attn_v=head(cfg.v_dim),
        attn_q_shared=head(cfg.rope_dim),
        attn_k_shared=tokens * max(cfg.rope_dim, 128) * itemsize,  # a vreg of lanes a token
        shared_up=2 * tokens * cfg.shared_dim * itemsize,
        mlp_up=2 * tokens * cfg.intermediate * itemsize,
        moe_plan=moe.named_bytes(tokens, cfg.top_k, cfg.experts_held, cfg.num_experts, d,
                                 cfg.expert_dim, itemsize)[moe.ROUTE_PLAN])
    routed = range(cfg.num_dense_layers, cfg.n_layer)
    made_in = dict(shared_up=routed, moe_plan=routed, mlp_up=range(dense))
    params = (cfg.n_layer * cfg.attention_params() + dense * 3 * d * cfg.intermediate
              + cfg.routed_layers * (d * cfg.num_experts + 3 * d * cfg.shared_dim
                                     + cfg.experts_held * 3 * d * cfg.expert_dim)
              + 2 * cfg.vocab_size * d)  # embedding and the untied head
    held = remat.held_bytes(
        shape, params=params, width=d, vocab=cfg.vocab_size, n_layer=cfg.n_layer,
        itemsize=itemsize, block=_block_bytes(cfg, itemsize) * tokens)
    return remat.plan(REMAT_RUNGS, name_bytes, cfg.n_layer, held, limit,
                      remat.FIRST_RUNG + (moe.ROUTE_PLAN,), made_in)


def _block_bytes(cfg: KananaConfig, itemsize: int) -> int:
    """What the largest block's backward works in, bytes a token, from its
    widths: the latent pair's operands and output (q's two parts, a head's
    own keys, the values, o), each with its gradient; and of a routed block
    the expert layer's buffers of a row an assignment that are as wide as the
    stream (the rows gathered and the rows given back, each with its
    gradient; the ones an expert wide are live a grouped matmul at a time),
    of a dense one the MLP's gate and up with their gradients. 172 KB a token
    at the published widths in bf16: the step compiled for a v5e at the
    benchmark's cell holds 11.51 GiB with the first rung alone, 12.71 with
    the kernels' operands saved too and 13.27 with every rung, where this
    makes the rule reckon 11.51, 12.71 and 13.46 (tests/test_tpu_compile.py)."""
    heads = 2 * cfg.n_head * (2 * cfg.nope_dim + cfg.rope_dim + 2 * cfg.v_dim) * itemsize
    experts = cfg.top_k * 4 * cfg.n_embd * itemsize
    dense = 4 * cfg.intermediate * itemsize
    return heads + (experts if cfg.routed_layers else dense)


class KananaGroup(nn.Module):
    """Every block of the model, each under nn.remat: the one parameter group."""

    config: KananaConfig
    keep: Any  # the blocks' checkpoint policies, one a layer
    stream: Any = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        choices = []
        for i in range(cfg.n_layer):
            x, chosen = nn.remat(KananaBlock, policy=self.keep[i])(
                cfg, i >= cfg.num_dense_layers, self.stream, name=f"h_{i}")(x)
            choices.append(chosen)
        layers.sow_choices(self, choices)
        return x


class Kanana(nn.Module):
    config: KananaConfig
    stream: Any = None  # parallel/mesh.py:stream_sharding of the step's mesh

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype, name="tok_emb",
                     embedding_init=nn.initializers.normal(0.02))(idx)
        keep = remat.block_policy(remat_plan, cfg, idx.shape, self.stream)
        x = KananaGroup(cfg, keep, self.stream, name="p_0")(x)
        x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
        return layers.untied_head(self, cfg, x)


KANANA_SHARDING_RULES = ShardingRules(
    layers.LATENT_SHARDING_PATTERNS + layers.SHARED_EXPERT_SHARDING_PATTERNS
    + layers.UNTIED_HEAD_SHARDING_PATTERNS + EXPERT_SHARE_SHARDING_PATTERNS
    + layers.LLAMA_SHARDING_PATTERNS, default=P())
KananaConfig.family = Family(  # as models/lfm2.py's: the same router
    module=Kanana, rules=KANANA_SHARDING_RULES, sown=("moe_load", "moe_router"),
    metrics=moe.step_metrics, held_leaf=moe.SELECTION_BIAS_HELD)
