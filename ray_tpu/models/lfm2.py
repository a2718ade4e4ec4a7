"""Decoder whose layers mix along time with a gated short convolution or with
attention, three to one, and whose MLPs are dense in the leading layers and
routed after them (LFM2-8B-A1B: `model_type` lfm2_moe; HF
`modeling_lfm2_moe.py`).

The equations, with d the hidden size, E the (tied) embedding and x the
residual stream:

    x0 = E[idx]
    a layer:  x <- x + op(RMSNorm(x));  x <- x + ffn(RMSNorm(x))
    logits = E RMSNorm(x)            operands in the compute dtype, float32 sums
    loss   = mean cross-entropy of the next token             float32

`conv` operator (ops/short_conv.py; pallas kernels gated_conv_fwd and
gated_conv_bwd on a TPU):

    [B | C | u] = W_in h                       d -> 3 d, no bias
    y_t = C_t * sum_{j<k} w_j * (B * u)_{t-(k-1)+j}    depthwise, causal, k = 3
                                               taps, no bias, no activation
    out = W_out y                              d -> d, no bias

`full_attention` operator: `LlamaAttention` with an RMSNorm over each head
of q and k before the rotary (half-split form over the whole head), grouped
queries, causal, no bias.

ffn of the first `num_dense_layers` layers: W_down (silu(W_gate h) * W_up h),
`intermediate` wide. Of the others: ops/moe.py's `ExpertShare` with the
SIGMOID router (scores sigmoid(W_r h) in float32; the `top_k` experts of a
token are the top of score + bias, its gates the scores at those, over their
sum + 1e-6, times `routed_scaling`), SwiGLU experts `expert_dim` wide, of
which this program computes `num_held` from `first_expert` on: one chip's
share under expert parallelism, what the others would add left out. The bias
is a leaf of the parameters that no gradient moves: TrainStep keeps it out of
AdamW and moves it from the step's own routing counts
(ops/moe.py:move_selection_bias).

All blocks are one parameter group, `p_0` (`p_0/h_0` ..): the kinds of block
differ in structure, and what takes gradients a group at a time
(bench/worker.py, a pipeline stage) asks the groups for one structure. The
group sows one entry into "choices": its routed blocks' indices stacked,
(routed blocks, B, T, top_k) (`layers.sow_choices`).

Each block is under nn.remat with the plan of models/remat.py; the operator's
named scopes (conv.in_proj, conv.mix, conv.out_proj) reach every op's
metadata. Departures from the published code, all under `assumed` in
bench/configs/lfm2_8b_a1b_l5_ep4.json: the taps are stored (k, d) and not
(d, 1, k); the head is tied (the row does not say); the bias's rule.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.models import Family, layers, remat
from ray_tpu.models.layers import LlamaAttention, LlamaMLP, RMSNorm
from ray_tpu.ops import moe
from ray_tpu.ops.moe import EXPERT_SHARE_SHARDING_PATTERNS, SIGMOID, ExpertShare
from ray_tpu.ops.short_conv import gated_short_conv
from ray_tpu.parallel.mesh import ShardingRules, pin

CONV, ATTENTION = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    block_size: int = 128000
    n_embd: int = 2048
    layer_types: Tuple[str, ...] = (CONV, ATTENTION, CONV, CONV, CONV)
    num_dense_layers: int = 1
    n_head: int = 32
    n_kv_head: int = 8
    intermediate: int = 7168  # of the dense layers' MLP
    conv_taps: int = 3
    expert_dim: int = 1792
    num_experts: int = 32  # the router's width
    top_k: int = 4
    first_expert: int = 0
    num_held: Optional[int] = None  # experts computed here; None: all
    routed_scaling: float = 1.0
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    use_flash_attention: bool = True
    attn_fn: Any = None  # as LlamaConfig.attn_fn
    # as MellumConfig.lr_warmup_steps: the rows an expert works on are what
    # the router sends it, and the routing does not survive the full rate
    # from step 0
    lr_warmup_steps: int = 2000

    family: ClassVar[Family]  # what TrainStep asks of it: set at the foot of this file

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @property
    def mlp_dim(self) -> int:
        return self.intermediate

    @property
    def experts_held(self) -> int:
        return self.num_experts if self.num_held is None else self.num_held

    def routed(self, layer: int) -> bool:
        return layer >= self.num_dense_layers

    def operator_params(self, kind: str) -> int:
        d = self.n_embd
        if kind == ATTENTION:
            return 2 * d * d + 2 * d * self.n_kv_head * self.head_dim
        return 4 * d * d  # W_in (d x 3 d) and W_out

    def matmul_params(self) -> int:
        """Each layer's operator (W_in and W_out, or q, k, v, o) and MLP (the
        dense one's three matrices; a routed one's router and the expert
        matrices a token meets at even routing: top_k experts, of which held
        / num_experts are here), and the tied matrix once, as the head. The
        taps and the norms multiply element by element."""
        d = self.n_embd
        experts = self.top_k * self.experts_held / self.num_experts * 3 * d * self.expert_dim
        return int(sum(self.operator_params(kind) + (
            d * self.num_experts + experts if self.routed(i) else 3 * d * self.intermediate)
            for i, kind in enumerate(self.layer_types)) + self.vocab_size * d)

    def flops_per_token(self, seq_len: int) -> int:
        """6 x matmul parameters + the causal attention term of the attention
        layers (GPT2Config.flops_per_token's rule, 6 T d each). The
        convolution's 2 k + 2 operations a channel are bytes' work and are
        left out, as every family leaves out its element-wise work."""
        return (6 * self.matmul_params()
                + 6 * self.layer_types.count(ATTENTION) * seq_len * self.n_embd)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, block_size=128, n_embd=128, n_head=4, n_kv_head=2,
                    layer_types=(CONV, ATTENTION, CONV), intermediate=192, expert_dim=64,
                    num_experts=8, top_k=2)
        base.update(kw)
        return cls(**base)


class ShortConv(nn.Module):
    """(B, T, d) -> (B, T, d): the module docstring's `conv` operator."""

    config: Lfm2Config

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        dense = lambda width, name: nn.Dense(width, use_bias=False, dtype=cfg.dtype, name=name)
        with jax.named_scope("conv.in_proj"):
            bcu = checkpoint_name(dense(3 * cfg.n_embd, "in_proj")(h), "conv_bcu")
        with jax.named_scope("conv.mix"):
            w = self.param("conv_kernel", layers.conv_init, (cfg.conv_taps, cfg.n_embd),
                           jnp.float32)
            y = checkpoint_name(gated_short_conv(bcu, w), "conv_y")
        with jax.named_scope("conv.out_proj"):
            return dense(cfg.n_embd, "out_proj")(y)


class Lfm2Block(nn.Module):
    """A block and the choices of its expert layer, (x, (B, T, top_k)); a
    block with a dense MLP hands up None."""

    config: Lfm2Config
    kind: str
    routed: bool
    stream: Any = None  # the residual stream's sharding, or None (models/llama.py)
    # whether the blocks' remat plan keeps any of the expert layer's products
    # (ops/moe.py:ExpertShare.products_kept)
    products_kept: bool = True

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = pin(x, self.stream)
        h = RMSNorm(cfg.rms_eps, name="operator_norm")(x)
        if self.kind == ATTENTION:
            mixed = LlamaAttention(cfg, qk_norm=True, name="attn")(h)
        else:
            mixed = ShortConv(cfg, name="conv")(h)
        x = pin(x + mixed, self.stream)
        h = RMSNorm(cfg.rms_eps, name="ffn_norm")(x)
        if not self.routed:
            return pin(x + LlamaMLP(cfg, name="mlp")(h), self.stream), None
        y, chosen = ExpertShare(
            cfg.n_embd, cfg.expert_dim, cfg.num_experts, cfg.top_k, cfg.first_expert,
            cfg.num_held, cfg.dtype, router=SIGMOID, scaling=cfg.routed_scaling,
            hand_up_choices=True, products_kept=self.products_kept,
            name="moe")(h)
        return pin(x + y, self.stream), chosen


# What a block's remat saves after the first rung (the flash kernel's output
# and logsumexp in the attention layer), and the ms of a step each spared for
# a GiB held in the benchmark's cell on a v5e (my chip run, PR 41; PERF.md
# section 6): the operator's three streams and its mixed output spare W_in's
# second run and gated_conv_fwd's (11.8 ms for 1.0 GiB); the dense MLP's gate
# and up those two matmuls' (4.3 ms for 0.44 GiB); the flash kernel's operands
# the q, k, v projections', the head norms and the rotary (2.1 ms for 0.19
# GiB). The expert layer's three products (ops/moe.py:KEPT_PRODUCTS), a rung
# each (my chip runs, PR 45, calls 1 and 7; one process a set of names, 8
# steps by the host's clock, not the benchmark's 40 s window): together they
# spare 12.76 ms of the 243.35 a step takes with no product kept, for 1.03
# GiB; each has of that the share it had one at a time (the gate product
# spares its grouped matmul's second run, 4.10 ms for 0.33 GiB, the up
# product likewise 4.60, the down product its matmul and silu(gate) * up
# before it, 4.75 ms for 0.375 GiB, against the same form of the layer with
# nothing kept, which is 2.7 ms slower than the form a plan without products
# takes). The price is a step that overflowed its headroom: with the products
# kept it pays the headroom buffer's forward work on top of its own, 312.62
# ms against the parent's 289.61 (call 7, every layer forced to overflow).
# The first rung also holds the expert layers' choices and plans
# (`moe_plan`: integers, 1.4 MB a layer).
REMAT_RUNGS = ((("conv_bcu", "conv_y"), 11.8), (("mlp_up",), 9.9),
               (("attn_q", "attn_k", "attn_v"), 11.1),
               (("moe_gate",), 11.9), (("moe_up",), 13.3), (("moe_out",), 12.0))


def remat_plan(cfg: Lfm2Config, shape: remat.StepShape, limit) -> remat.RematPlan:
    """What the blocks of a step of this shape save across remat, under a
    chip's `limit` of bytes: a pure function of its arguments. A name's
    bytes are one layer's, and `made_in` says which layers make it."""
    d, itemsize = cfg.n_embd, jnp.dtype(cfg.dtype).itemsize
    tokens = shape.rows * shape.seq_len
    kinds = cfg.layer_types
    name_bytes = remat.attention_bytes(shape, cfg.n_head, cfg.head_dim, itemsize)
    made_in = dict.fromkeys(name_bytes, remat.layers_of(kinds, ATTENTION))
    dense = min(cfg.num_dense_layers, cfg.n_layer)
    name_bytes.update(
        conv_bcu=3 * tokens * d * itemsize, conv_y=tokens * d * itemsize,
        mlp_up=2 * tokens * cfg.intermediate * itemsize // shape.tp)
    made_in.update(dict.fromkeys(("conv_bcu", "conv_y"), remat.layers_of(kinds, CONV)),
                   mlp_up=range(dense))
    routed = cfg.n_layer - dense
    products = moe.named_bytes(tokens, cfg.top_k, cfg.experts_held, cfg.num_experts, d,
                               cfg.expert_dim, itemsize)
    name_bytes.update(products)
    made_in.update(dict.fromkeys(products, range(dense, cfg.n_layer)))
    params = (sum(cfg.operator_params(kind) for kind in kinds)
              + dense * 3 * d * cfg.intermediate
              + routed * (d * cfg.num_experts + cfg.experts_held * 3 * d * cfg.expert_dim)
              + 2 * cfg.vocab_size * d)  # the tied matrix, and the logits' gradient to it
    held = remat.held_bytes(
        shape, params=params, width=d, vocab=cfg.vocab_size, n_layer=cfg.n_layer,
        itemsize=itemsize, block=_block_bytes(cfg, itemsize) * tokens)
    return remat.plan(REMAT_RUNGS, name_bytes, cfg.n_layer, held, limit,
                      remat.FIRST_RUNG + (moe.ROUTE_PLAN,), made_in)


def _block_bytes(cfg: Lfm2Config, itemsize: int) -> int:
    """What the largest block's backward works in, bytes a token, from its
    widths. A routed block: the expert layer's buffers of a row an
    assignment, every token's top_k of them (both of `ExpertShare`'s buffers
    are compiled, and the one of every row sets the size): the rows gathered
    and the rows given back, d wide, and gate, up and their product,
    expert_dim wide, each with its gradient; and the gradient's rows gathered
    in the combine's backward and in the dispatch's, in the stream's dtype
    since PR 44 (float32 before: 250 KB a token then, 217 now). A dense
    block: the MLP's gate and up and their gradients. Beside either, the
    operator's three streams and its output with their gradients. At the
    published widths in bf16 the step compiled for a v5e at the benchmark's
    cell holds 11.84 GiB with the first rung alone, 11.67 with the older
    rungs and 12.02 with the expert layer's products too, where this makes
    the rule reckon 11.69, 11.69 and 12.40 (PERF.md section 6, PR 45;
    tests/test_tpu_compile.py)."""
    operator = 2 * 4 * cfg.n_embd * itemsize
    experts = cfg.top_k * itemsize * (6 * cfg.n_embd + 6 * cfg.expert_dim)
    dense = 4 * cfg.intermediate * itemsize
    return operator + (experts if cfg.n_layer > cfg.num_dense_layers else dense)


class Lfm2Group(nn.Module):
    """Every block of the model, each under nn.remat: the one parameter group."""

    config: Lfm2Config
    keep: Any  # the blocks' checkpoint policies, one a layer
    stream: Any = None
    products_kept: Any = ()  # as the blocks', one a layer

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        choices = []
        for i, kind in enumerate(cfg.layer_types):
            x, chosen = nn.remat(Lfm2Block, policy=self.keep[i])(
                cfg, kind, cfg.routed(i), self.stream, self.products_kept[i], name=f"h_{i}")(x)
            choices.append(chosen)
        layers.sow_choices(self, choices)
        return x


class Lfm2(nn.Module):
    config: Lfm2Config
    stream: Any = None  # parallel/mesh.py:stream_sharding of the step's mesh

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        emb = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype, name="tok_emb",
                       embedding_init=nn.initializers.normal(0.02))
        x = emb(idx)
        keep = remat.block_policy(remat_plan, cfg, idx.shape, self.stream)
        products = remat.traced(cfg).saved_in(*moe.KEPT_PRODUCTS)
        x = Lfm2Group(cfg, keep, self.stream, products, name="p_0")(x)
        x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
        # the tied head under models/llama.py's untied one's name (models/
        # granite.py says why): operands in the compute dtype, float32 logits
        with jax.named_scope("lm_head"):
            return jnp.dot(x, emb.embedding.astype(cfg.dtype).T,
                           preferred_element_type=jnp.float32)


LFM2_SHARDING_RULES = ShardingRules([
    # the three streams stay whole on a chip: the mixing kernel takes them
    # side by side
    (r"conv/in_proj/kernel", P("fsdp", None)),
    (r"conv/out_proj/kernel", P(None, "fsdp")),
    (r"conv/conv_kernel", P()),
] + EXPERT_SHARE_SHARDING_PATTERNS + layers.LLAMA_SHARDING_PATTERNS, default=P())
# A router that selects under a bias sows every expert's tokens beside the
# held experts' rows: they are a gauge, and what moves the bias.
Lfm2Config.family = Family(
    module=Lfm2, rules=LFM2_SHARDING_RULES, sown=("moe_load", "moe_router"),
    metrics=moe.step_metrics, held_leaf=moe.SELECTION_BIAS_HELD)
