"""Decoder whose every block is a norm and one mixer, of three kinds in a
pattern of its own: a Mamba-2 mixer, an expert layer, or attention with no
positional encoding (NVIDIA-Nemotron-3-Nano-30B-A3B: `model_type` nemotron_h;
HF `modeling_nemotron_h.py`; `hybrid_override_pattern` of letters M, E, *).

The equations, with d the hidden size and x the residual stream:

    x0 = E[idx]                                no multiplier
    a block:  x <- x + mixer(RMSNorm(x))       the sum in the stream's dtype
    logits = W_head RMSNorm(x)       untied; operands in the compute dtype,
                                     float32 sums
    loss   = mean cross-entropy of the next token             float32

`M`: models/layers.py's `Mamba2Mixer`, which states the equations and what
computes them: H heads of P, inner width H P (not `expand` x d), eight
groups of B and C, and the gated norm over each group's H P / G channels on
its own (`norm_groups`).

`*`: `LlamaAttention(rotary=False)`: `n_head` query and `n_kv_head` key-value
heads of `head_dim` (heads x head_dim is not d), causal softmax of q k^T /
sqrt(head_dim), no bias, **no rotary and no other position**: the Mamba
layers carry order.

`E`: ops/moe.py's `ExpertShare` with the SIGMOID router (models/lfm2.py says
what it computes; here top 6 of 128, one group, the gates over their sum +
1e-20, times 2.5) and experts of **two matrices under relu squared**, W_down
relu(W_up u)^2 (`moe.RELU2`: no gate matrix), of which this program computes
`num_held` from `first_expert` on, **plus the shared expert**, the same form
`shared_dim` wide, that every token passes through: under a share it is
whole on every chip, and counted once when shares are summed.

All blocks are one parameter group, `p_0` (`p_0/h_0` ..), of three unlike
structures; the group sows one entry into "choices", its expert layers'
indices stacked, (expert layers, B, T, top_k): `layers.sow_choices`.
Each block is under nn.remat with the plan of models/remat.py. What the
published config leaves open (no clamp on Delta, Mamba-2's own initialisers,
the bias's rule, no auxiliary loss) is under `assumed` in
bench/configs/nemotron3_nano_l9_ep16.json.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.models import Family, layers, remat
from ray_tpu.models.layers import LlamaAttention, Mamba2Mixer, RMSNorm, SharedExpert
from ray_tpu.ops import moe
from ray_tpu.ops.moe import EXPERT_SHARE_SHARDING_PATTERNS, RELU2, SIGMOID, ExpertShare
from ray_tpu.parallel.mesh import ShardingRules, pin

MAMBA, EXPERTS, ATTENTION = "mamba", "experts", "attention"
PATTERN_LETTERS = {"M": MAMBA, "E": EXPERTS, "*": ATTENTION}


def layer_types(pattern: str) -> Tuple[str, ...]:
    """The kinds of block a `hybrid_override_pattern` spells, in order."""
    return tuple(PATTERN_LETTERS[letter] for letter in pattern)


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    block_size: int = 262144
    n_embd: int = 2688
    layer_types: Tuple[str, ...] = layer_types("MEMEM*EME")
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8
    ssm_conv: int = 4
    ssm_chunk: int = 128
    expert_dim: int = 1856
    shared_dim: int = 3712
    num_experts: int = 128  # the router's width
    top_k: int = 6
    first_expert: int = 0
    num_held: Optional[int] = None  # experts computed here; None: all
    routed_scaling: float = 2.5
    gate_eps: float = 1e-20
    rms_eps: float = 1e-5
    rope_theta: float = 10000.0  # published, and used by no layer (as GraniteConfig's)
    dtype: Any = jnp.bfloat16
    use_flash_attention: bool = True
    attn_fn: Any = None  # as LlamaConfig.attn_fn
    lr_warmup_steps: int = 2000  # as MellumConfig.lr_warmup_steps

    family: ClassVar[Family]  # what TrainStep asks of it: set at the foot of this file

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def experts_held(self) -> int:
        return self.num_experts if self.num_held is None else self.num_held

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def mixer_params(self, kind: str, held_only: bool = False) -> int:
        """A block's matrices: a Mamba mixer's W_in and W_out; q, k, v, o; or
        the router, the shared expert whole and the routed experts' two
        matrices each (`held_only`: every expert held here; else what a token
        meets at even routing: top_k experts, of which held / num_experts are
        here)."""
        d = self.n_embd
        if kind == MAMBA:
            return d * (self.ssm_inner + self.ssm_conv_dim + self.ssm_heads) + self.ssm_inner * d
        if kind == ATTENTION:
            return 2 * d * self.head_dim * (self.n_head + self.n_kv_head)
        met = self.experts_held if held_only else self.top_k * self.experts_held / self.num_experts
        return int(d * self.num_experts + 2 * d * self.shared_dim + met * 2 * d * self.expert_dim)

    def matmul_params(self) -> int:
        """Each block's `mixer_params` and the untied head. The embedding is
        a look-up; the taps, the norms and the per-head vectors multiply
        element by element."""
        return (sum(self.mixer_params(kind) for kind in self.layer_types)
                + self.vocab_size * self.n_embd)

    def flops_per_token(self, seq_len: int) -> int:
        """GraniteConfig.flops_per_token's rules: 6 x matmul parameters, the
        causal term of the attention layers over their heads x head_dim, and
        the recurrence as it stands, 18 N (H P) a token and Mamba layer."""
        return (6 * self.matmul_params()
                + 6 * self.count(ATTENTION) * seq_len * self.n_head * self.head_dim
                + 18 * self.ssm_state * self.ssm_inner * self.count(MAMBA))

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, block_size=128, n_embd=64, layer_types=layer_types("ME*E"),
                    n_head=4, n_kv_head=2, head_dim=32, ssm_heads=4, ssm_head_dim=32,
                    ssm_state=16, ssm_groups=2, ssm_chunk=16, expert_dim=48, shared_dim=96,
                    num_experts=8, top_k=2)
        base.update(kw)
        return cls(**base)


class NemotronHBlock(nn.Module):
    """A block and the choices of its expert layer, (x, (B, T, top_k)); a
    block of another kind hands up None."""

    config: NemotronHConfig
    kind: str
    stream: Any = None  # the residual stream's sharding, or None (models/llama.py)
    # whether the blocks' remat plan keeps any of the expert layer's products
    # (ops/moe.py:ExpertShare.products_kept)
    products_kept: bool = True

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = pin(x, self.stream)
        u = RMSNorm(cfg.rms_eps, name="norm")(x)
        chosen = None
        if self.kind == MAMBA:
            mixed = Mamba2Mixer(cfg, norm_groups=cfg.ssm_groups, name="mamba")(u)
        elif self.kind == ATTENTION:
            mixed = LlamaAttention(cfg, rotary=False, name="attn")(u)
        else:
            mixed, chosen = ExpertShare(
                cfg.n_embd, cfg.expert_dim, cfg.num_experts, cfg.top_k, cfg.first_expert,
                cfg.num_held, cfg.dtype, router=SIGMOID, scaling=cfg.routed_scaling,
                hand_up_choices=True, gate_eps=cfg.gate_eps, products_kept=self.products_kept,
                form=RELU2, name="moe")(u)
            with jax.named_scope("moe.shared"):
                mixed = mixed + SharedExpert(cfg, RELU2, name="shared")(u)
        return pin(x + mixed, self.stream), chosen


# What a block's remat saves after the first rung (the flash kernel's output
# and logsumexp in the attention layer, the expert layers' choices and plans:
# `moe_plan`, integers), and the ms of a step each spared for a GiB held in
# the benchmark's cell on a v5e (my chip run, PR 47, call 3; one process a
# set of names, 8 steps by the host's clock; 481.68 ms a step with the first
# rung alone): the shared expert's up product spares that matmul in four
# layers (10.61 ms for 0.453 GiB); the flash kernel's operands the q, k, v
# projections and the repeat of two key-value heads to 32 in one layer (3.61
# ms for 0.375 GiB); the expert layer's two products together the forward
# grouped matmuls' second run (4.46 ms for 0.312 GiB; one at a time they
# spared 1.38 and 0.53 ms, and the layer reads kept products either way: one
# rung). The scan's output and chunk states are no rung here: at chunks of
# 128 the states are 0.25 GiB a layer, and with both kept the step took
# 481.70 ms, nothing spared for 1.50 GiB (`granite`'s cell, chunks of 256,
# spares 7.9 ms for 0.56 GiB).
REMAT_RUNGS = ((("shared_up",), 23.4), (("attn_q", "attn_k", "attn_v"), 9.6),
               (("moe_up", "moe_out"), 14.3))


def remat_plan(cfg: NemotronHConfig, shape: remat.StepShape, limit) -> remat.RematPlan:
    """What the blocks of a step of this shape save across remat, under a
    chip's `limit` of bytes: a pure function of its arguments. A name's
    bytes are one layer's, and `made_in` says which layers make it."""
    d, itemsize = cfg.n_embd, jnp.dtype(cfg.dtype).itemsize
    tokens = shape.rows * shape.seq_len
    name_bytes = remat.attention_bytes(shape, cfg.n_head, cfg.head_dim, itemsize)
    made_in = dict.fromkeys(name_bytes, remat.layers_of(cfg.layer_types, ATTENTION))
    name_bytes["shared_up"] = tokens * cfg.shared_dim * itemsize
    name_bytes.update(moe.named_bytes(tokens, cfg.top_k, cfg.experts_held, cfg.num_experts, d,
                                      cfg.expert_dim, itemsize, RELU2))
    made_in.update(dict.fromkeys(name_bytes.keys() - made_in.keys(),
                                 remat.layers_of(cfg.layer_types, EXPERTS)))
    held = remat.held_bytes(
        shape, params=count_params(cfg), width=d, vocab=cfg.vocab_size, n_layer=cfg.n_layer,
        itemsize=itemsize, block=_block_bytes(cfg, itemsize) * tokens)
    return remat.plan(REMAT_RUNGS, name_bytes, cfg.n_layer, held, limit,
                      remat.FIRST_RUNG + (moe.ROUTE_PLAN,), made_in)


def count_params(cfg: NemotronHConfig) -> int:
    """Every parameter held here: the blocks' matrices with every held expert
    whole, the embedding and the untied head, and the vectors (a norm a
    block and the final one; a Mamba block's taps and their bias, dt_bias,
    A_log, D and the gated norm's weight; an expert layer's selection bias)."""
    d = cfg.n_embd
    mamba = cfg.ssm_conv_dim * (cfg.ssm_conv + 1) + 3 * cfg.ssm_heads + cfg.ssm_inner
    vectors = {MAMBA: d + mamba, EXPERTS: d + cfg.num_experts, ATTENTION: d}
    return (sum(cfg.mixer_params(kind, held_only=True) + vectors[kind] for kind in cfg.layer_types)
            + 2 * cfg.vocab_size * d + d)


def _block_bytes(cfg: NemotronHConfig, itemsize: int) -> int:
    """What the largest block's backward works in, bytes a token, from its
    widths: a Mamba block's mixer (`layers.mixer_bytes`), with no MLP after
    it; an expert block's buffers of a row an assignment that are
    as wide as the stream (models/kanana.py:_block_bytes) and the shared
    expert's up product and what relu squared makes of it, each with its
    gradient; an attention block's four operands of the kernel and its
    output, each with its gradient."""
    blocks = {
        MAMBA: layers.mixer_bytes(cfg, itemsize),
        EXPERTS: cfg.top_k * 4 * cfg.n_embd * itemsize + 4 * cfg.shared_dim * itemsize,
        ATTENTION: 2 * 4 * cfg.n_head * cfg.head_dim * itemsize}
    return max(blocks[kind] for kind in set(cfg.layer_types))


class NemotronHGroup(nn.Module):
    """Every block of the model, each under nn.remat: the one parameter group."""

    config: NemotronHConfig
    keep: Any  # the blocks' checkpoint policies, one a layer
    stream: Any = None
    products_kept: Any = ()  # as the blocks', one a layer

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        choices = []
        for i, kind in enumerate(cfg.layer_types):
            x, chosen = nn.remat(NemotronHBlock, policy=self.keep[i])(
                cfg, kind, self.stream, self.products_kept[i], name=f"h_{i}")(x)
            choices.append(chosen)
        layers.sow_choices(self, choices)
        return x


class NemotronH(nn.Module):
    config: NemotronHConfig
    stream: Any = None  # parallel/mesh.py:stream_sharding of the step's mesh

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype, name="tok_emb",
                     embedding_init=nn.initializers.normal(0.02))(idx)
        keep = remat.block_policy(remat_plan, cfg, idx.shape, self.stream)
        products = remat.traced(cfg).saved_in(*RELU2.products)
        x = NemotronHGroup(cfg, keep, self.stream, products, name="p_0")(x)
        x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
        return layers.untied_head(self, cfg, x)


NEMOTRON_H_SHARDING_RULES = ShardingRules(
    layers.SHARED_EXPERT_SHARDING_PATTERNS + layers.UNTIED_HEAD_SHARDING_PATTERNS
    + layers.MAMBA_SHARDING_PATTERNS + EXPERT_SHARE_SHARDING_PATTERNS
    + layers.LLAMA_SHARDING_PATTERNS, default=P())


def step_metrics(cfg, sown, params, tokens):
    """`Family.metrics`: what the Mamba layers sowed and what the expert
    layers sowed, each by its own reducer."""
    return {**layers.ssm_step_metrics(cfg, sown, params, tokens),
            **moe.step_metrics(cfg, sown, params, tokens)}


NemotronHConfig.family = Family(
    module=NemotronH, rules=NEMOTRON_H_SHARDING_RULES,
    sown=("ssm_stats", "moe_load", "moe_router"), metrics=step_metrics,
    held_leaf=moe.SELECTION_BIAS_HELD)
