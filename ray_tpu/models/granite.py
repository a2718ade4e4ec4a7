"""Decoder whose layers are of two unlike kinds, a Mamba-2 mixer or attention
with no positional encoding, each followed by the same dense SwiGLU MLP
(Granite 4.0-H: `model_type` granitemoehybrid with no experts; nine Mamba
layers to one attention layer).

The equations, with d the hidden size, E the (tied) embedding and x the
residual stream:

    x0 = embedding_multiplier * E[idx]
    a layer:  x <- x + residual_multiplier * mixer(RMSNorm(x))
              x <- x + residual_multiplier * MLP(RMSNorm(x))
              MLP(u) = W_down (silu(W_gate u) * W_up u)       no bias
    logits = E RMSNorm(x) / logits_scaling                    float32
    loss   = mean cross-entropy of the next token             float32

`attention` layers: n_head query and n_kv_head key/value heads of
d / n_head, no bias, no rotary or any other position (`nope`), causal,
scores scaled by attention_multiplier in place of 1/sqrt(head_dim). The
flash kernel fixes 1/sqrt(head_dim) (ops/attention.py:_split_scale), so q is
scaled by attention_multiplier * sqrt(head_dim) before the call: 1/8 at the
published sizes, a power of two and so exact in bf16.

`mamba` layers: models/layers.py's `Mamba2Mixer`, which states the equations
and what computes them; here one group of B and C, and the gated norm over
all H P channels at once.
Departures from the published code, all under `assumed` in
bench/configs/granite4_h_micro_l10.json: the convolution's kernel is stored
(K, channels) and not (channels, 1, K); no clamp on Delta (the family's
`time_step_limit` is (0, inf)); Mamba-2's own initialisation of A_log,
dt_bias and D, which the published config does not carry.

Each block is under nn.remat with the plan of models/remat.py; the mixer's
named scopes (ssm.in_proj, ssm.conv, ssm.scan, ssm.gate, ssm.out_proj) reach
every op's metadata.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.models import Family, layers, remat
from ray_tpu.models.layers import LlamaAttention, LlamaMLP, Mamba2Mixer, RMSNorm
from ray_tpu.parallel.mesh import ShardingRules, pin

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteConfig:
    vocab_size: int = 100352
    block_size: int = 131072
    n_embd: int = 2048
    layer_types: Tuple[str, ...] = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    n_head: int = 32
    n_kv_head: int = 8
    intermediate: int = 8192
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 1.0 / 64
    logits_scaling: float = 8.0
    rms_eps: float = 1e-5
    rope_theta: float = 10000.0  # published, and used by no layer (`nope`)
    dtype: Any = jnp.bfloat16
    use_flash_attention: bool = True
    attn_fn: Any = None  # as LlamaConfig.attn_fn

    family: ClassVar[Family]  # what TrainStep asks of it: set at the foot of this file

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def period(self) -> int:
        """Layers after which `layer_types` repeats: the model's parameters
        are grouped by period (`p_<i>`, each with blocks `h_0` ..), so every
        group has one structure whatever kinds of layer a period mixes (what
        a pipeline stage holds, and what bench/worker.py asks of the groups
        it takes gradients by)."""
        kinds, n = self.layer_types, len(self.layer_types)
        return next(p for p in range(1, n + 1)
                    if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n)))

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @property
    def mlp_dim(self) -> int:
        return self.intermediate

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def mixer_matmul_params(self, kind: str) -> int:
        d = self.n_embd
        if kind == ATTENTION:
            return 2 * d * d + 2 * d * self.n_kv_head * self.head_dim
        return d * (self.ssm_inner + self.ssm_conv_dim + self.ssm_heads) + self.ssm_inner * d

    def matmul_params(self) -> int:
        """The mixer's projections and the MLP's three matrices of each
        layer, and the tied matrix once, as the head (the embedding is a
        look-up)."""
        d = self.n_embd
        return (sum(self.mixer_matmul_params(kind) + 3 * d * self.intermediate
                    for kind in self.layer_types) + self.vocab_size * d)

    def flops_per_token(self, seq_len: int) -> int:
        """6 x matmul parameters, the causal attention term of the attention
        layers (GPT2Config.flops_per_token's rule), and the recurrence as it
        stands, whatever computes it: 6 N P H a token and Mamba layer forward
        (update, decay, read-out), three times that with the backward."""
        kinds = self.layer_types
        scan = 18 * self.ssm_state * self.ssm_inner * kinds.count(MAMBA)
        return (6 * self.matmul_params() + 6 * kinds.count(ATTENTION) * seq_len * self.n_embd
                + scan)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, block_size=128, n_embd=64, layer_types=(MAMBA, ATTENTION),
                    n_head=4, n_kv_head=2, intermediate=128, ssm_heads=4, ssm_head_dim=32,
                    ssm_state=16, ssm_chunk=16, attention_multiplier=1.0 / 8)
        base.update(kw)
        return cls(**base)


def _add_scaled(x, factor, branch):
    """x + factor * branch, summed in float32 and rounded once. In bf16 the
    published 0.22 is 0.21973: every branch 0.12% short, which the loss of a
    tied model shows (found on the chip: 4.4e-4 of the loss, PERF.md section
    6, PR 36)."""
    return (x.astype(jnp.float32) + factor * branch.astype(jnp.float32)).astype(x.dtype)


class GraniteBlock(nn.Module):
    config: GraniteConfig
    kind: str
    stream: Any = None  # the residual stream's sharding, or None (models/llama.py)

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = pin(x, self.stream)
        u = RMSNorm(cfg.rms_eps, name="mixer_norm")(x)
        if self.kind == ATTENTION:
            mixed = LlamaAttention(
                cfg, rotary=False, q_scale=cfg.attention_multiplier * math.sqrt(cfg.head_dim),
                name="attn")(u)
        else:
            mixed = Mamba2Mixer(cfg, name="mamba")(u)
        x = pin(_add_scaled(x, cfg.residual_multiplier, mixed), self.stream)
        x = _add_scaled(x, cfg.residual_multiplier, LlamaMLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_eps, name="mlp_norm")(x)))
        return pin(x, self.stream)


# What a block's remat saves after the first rung (the flash kernel's output
# and logsumexp in the attention layers), and the ms of a step each spared
# for a GiB held in the benchmark's cell on a v5e (PERF.md section 6, PR 36):
# the scan's output and chunk states spare ssd_fwd's second run (7.9 ms for
# 0.56 GiB), the MLP's gate and up those two matmuls' (14.0 ms for 1.25 GiB).
# The input projection's and the convolution's outputs were tried as a third
# rung in PR 36, when the convolution was XLA's padded, shifted float32
# slices, and are not named: 3.2 ms for 0.88 GiB alone, and 0.9 ms *slower*
# beside the scan's; the convolution's second run is a kernel's 0.11 ms a
# layer since PR 48, less still to spare. At the cell's shape the rule's
# bookkeeping (16 bytes a parameter: 11.5 GiB) had no room for both whole
# under 13.5 (0.9 of a v5e's limit rounded down to 15 GiB): until PR 62 it
# took the MLP's whole and no scan's; from then (models/remat.py's depths)
# the scan's in the last eight Mamba layers of nine and the MLP's in the last
# nine layers of ten, which by the worths below spared more and on the chip
# cost 1.4 ms a step (-0.72%: the scan's worth dates from PR 36's program and
# spares nothing a step today; these worths are due a new reading, ROADMAP.md
# A7 b, PERF.md section 6, PR 62). Since PR 65 the limit is the chip's own to
# within 64 MiB (14.12 of room) and both rungs are whole (it reckons 13.68;
# the step compiled for a v5e holds 11.25): the worths decide nothing here.
REMAT_RUNGS = ((("ssm_y", "ssm_states"), 14.1), (("mlp_up",), 11.2))


def remat_plan(cfg: GraniteConfig, shape: remat.StepShape, limit) -> remat.RematPlan:
    """What the blocks of a step of this shape save across remat, under a
    chip's `limit` of bytes: a pure function of its arguments. A name's
    bytes are one layer's, and `made_in` says which layers make it."""
    d, itemsize = cfg.n_embd, jnp.dtype(cfg.dtype).itemsize
    tokens = shape.rows * shape.seq_len
    kinds = cfg.layer_types
    mamba = remat.layers_of(kinds, MAMBA)
    name_bytes = remat.attention_bytes(shape, cfg.n_head, cfg.head_dim, itemsize)
    made_in = dict.fromkeys(name_bytes, remat.layers_of(kinds, ATTENTION))
    made_in.update(ssm_y=mamba, ssm_states=mamba)
    chunks = -(-shape.seq_len // cfg.ssm_chunk)
    name_bytes.update(
        ssm_y=tokens * cfg.ssm_inner * itemsize,
        ssm_states=shape.rows * chunks * cfg.ssm_inner * cfg.ssm_state * 4,
        mlp_up=2 * tokens * cfg.intermediate * itemsize // shape.tp)
    vectors = sum(2 * d + (cfg.ssm_conv_dim * (cfg.ssm_conv + 1) + 3 * cfg.ssm_heads
                           + cfg.ssm_inner if kind == MAMBA else 0) for kind in kinds) + d
    held = remat.held_bytes(
        shape, params=cfg.matmul_params() + vectors, width=d, vocab=cfg.vocab_size,
        n_layer=cfg.n_layer, itemsize=itemsize,
        block=_block_bytes(cfg, itemsize) * tokens if mamba else 0)
    return remat.plan(REMAT_RUNGS, name_bytes, cfg.n_layer, held, limit, made_in=made_in)


def _block_bytes(cfg: GraniteConfig, itemsize: int) -> int:
    """What a Mamba block's backward works in, bytes a token: the mixer's
    (`mixer_bytes`) and the MLP's gate and up and their gradients (154 KB at
    the published widths in bf16)."""
    return layers.mixer_bytes(cfg, itemsize) + itemsize * 4 * cfg.intermediate


class GranitePeriod(nn.Module):
    """One period of the layer pattern, each block under nn.remat."""

    config: GraniteConfig
    keep: Any  # its blocks' checkpoint policies, one a layer
    stream: Any = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        for i, kind in enumerate(cfg.layer_types[:cfg.period]):
            x = nn.remat(GraniteBlock, policy=self.keep[i])(
                cfg, kind, self.stream, name=f"h_{i}")(x)
        return x


class Granite(nn.Module):
    config: GraniteConfig
    stream: Any = None  # parallel/mesh.py:stream_sharding of the step's mesh

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        # 1/embedding_multiplier an element, so the stream starts at unit
        # variance (models/mellum.py says why that matters)
        emb = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype, name="tok_emb",
                       embedding_init=nn.initializers.normal(1.0 / cfg.embedding_multiplier))
        x = emb(idx) * cfg.embedding_multiplier
        keep = remat.block_policy(remat_plan, cfg, idx.shape, self.stream)
        for i in range(cfg.n_layer // cfg.period):
            x = GranitePeriod(cfg, keep[i * cfg.period:(i + 1) * cfg.period], self.stream,
                              name=f"p_{i}")(x)
        x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
        # the tied head in float32, as models/llama.py's untied one, and under
        # that one's name: written at the model's top level it would carry no
        # scope, and the device profile (train/_device_profile.py) no head
        with jax.named_scope("lm_head"):
            logits = x.astype(jnp.float32) @ emb.embedding.astype(jnp.float32).T
            return logits / cfg.logits_scaling


GRANITE_SHARDING_RULES = ShardingRules(
    layers.MAMBA_SHARDING_PATTERNS + layers.LLAMA_SHARDING_PATTERNS, default=P())


GraniteConfig.family = Family(module=Granite, rules=GRANITE_SHARDING_RULES, sown=("ssm_stats",),
                              metrics=layers.ssm_step_metrics)
