"""Decoder with two kinds of mixer, three to one: Gated DeltaNet (a gated
delta rule whose decay is one number a value head and step, two value heads
on each key head) and gated softmax attention at heads of 256 with a rotary
over a quarter of each; every MLP routed, 10 of 512 experts beside a shared
one under a sigmoid gate of one number a token (Qwen3-Next-80B-A3B:
`model_type` qwen3_next; the equations are those of the checkpoint's
`modeling_qwen3_next.py` and of Gated Delta Networks, arXiv:2412.06464).

With d the hidden size, x the residual stream, h = RMSNorm(x) (eps 1e-6; the
source's norms of the stream and of q and k are "zero-centred", x * rsqrt(mean
x^2 + eps) * (1 + w) with w from zero: kept here as one weight from one, the
same values and the same gradients, and no decay touches a norm's weight):

    x0 = E[idx]
    a layer:  x <- x + mixer(RMSNorm(x));  x <- x + moe(RMSNorm(x))
    logits = W_head RMSNorm(x)       untied; operands in the compute dtype,
                                     float32 sums
    loss   = mean cross-entropy of the next token             float32

Gated DeltaNet mixer (`GatedDeltaNet`), Hk key heads and Hv value heads of 128:

    [q~ | k~ | v~] = silu(conv4([W_q h | W_k h | W_v h]))
                                     causal, depthwise, `gdn_conv` taps, no
                                     bias, one filter a channel
                                     (ops/short_conv.py's causal pair on a
                                     TPU); the three matrices lie side by
                                     side in one leaf, `qkv_proj`, the
                                     filters in `conv_kernel`
    z = W_z h (Hv x 128)    [b | a] = W_ba h (Hv + Hv)
    q = l2norm_head(q~) / sqrt(128)    k = l2norm_head(k~)
                                     u * rsqrt(sum of a head's squares +
                                     `L2_EPS`), made by ops/gdn.py, inside
                                     gdn_fwd and gdn_bwd on a TPU
    beta = sigmoid(b)       g = -exp(A_log) * softplus(a + dt_bias)
                                     one number a value head and token,
                                     float32, <= 0
    value head j reads key head j // (Hv / Hk)
    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                  ops/gdn.py: gdn_fwd and gdn_bwd on a
                                     TPU, the chunked form elsewhere
    y = W_o (RMSNorm_head(o) * w_norm * silu(z))
                                     the norm over a head's 128 alone, one
                                     weight of that width, before the gate
                                     (ops/kda_norm.py told `silu`, on o as
                                     gdn_fwd wrote it, (B, T, Hv x 128))

Gated attention: models/layers.py's `LlamaAttention(gate=True, qk_norm=True,
rotary_dim=64)` at `head_dim` 256: an RMSNorm over each head of q and k, the
rotary (halves turned, theta 1e7) on a head's first 64 entries and the other
192 as they are, softmax(q k^T / 16) v, times sigmoid of a fifth projection
of h as wide as the heads (the source's `q_proj` writes q and that gate side
by side a head; `wq` and `wg` here), then W_o.

moe: ops/moe.py's `ExpertShare` with the SOFTMAX router (a softmax over all
`num_experts` in float32, the `top_k` largest, gates renormalised over the
chosen), of which this program computes `num_held` experts from
`first_expert` on, plus models/layers.py's `SharedExpert(scalar_gate=True)`:
sigmoid(w_s . h) * SwiGLU_shared(h), whole on every chip and counted once when
shares are summed.

All blocks are one parameter group, `p_0`, which sows its blocks' choices
stacked, (blocks, B, T, top_k): `layers.sow_choices`. What the published keys
do not say (the convolution's form, l2norm's eps, A_log's and dt_bias's
initialisers, the head norm's weight, no multi-token head) is under
`assumed` in bench/configs/qwen3_next_80b_l5_ep32.json.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.models import Family, layers, remat
from ray_tpu.models.layers import LlamaAttention, NormWeight, RMSNorm, SharedExpert
from ray_tpu.ops import gdn, kda_norm, moe
from ray_tpu.ops.moe import EXPERT_SHARE_SHARDING_PATTERNS, ExpertShare
from ray_tpu.ops.short_conv import causal_conv_within
from ray_tpu.parallel.mesh import ShardingRules, pin

LINEAR, FULL = "linear_attention", "full_attention"  # the source's `layer_types`

# Room in the expert layers' buffers over the even-routing load
# (`ExpertShare.headroom`): the family's own number, from its own readings. A
# thirty-second's load (16 of 512 experts, 10 a token: 5,120 rows a layer at
# even routing) swings less than models/kimi_linear.py's 8 of 256 at 8 a
# token (which read up to 1.498 and takes 2.0): over 4 seeds x 40 steps of the
# benchmark's cell the rows routed to the 16 held experts read at most 1.133
# of the even load in any of the five layers (1.052, 1.074, 1.066 and 1.133
# by seed; a layer's mean over the steps 0.945-1.097), and no step took the
# buffer of every assignment (`moe_rows_summed_share` 0.0625 throughout, at
# the 2.0 those runs had). 1.5 leaves 0.37 over the largest reading, three
# times what the largest stood over the even load (my chip run, PR 64, call
# 2: bench/tests/qwen3_next_control.py --rows; PERF.md section 2).
EXPERT_HEADROOM = 1.5
L2_EPS = 1e-6  # under the square root of a head's q and k (fla's l2norm)


def layer_kinds(n_layer: int, full_every: int) -> Tuple[str, ...]:
    """The source's default `layer_types`: layer i is full attention where
    (i + 1) % `full_attention_interval` == 0, else linear."""
    return tuple(FULL if (i + 1) % full_every == 0 else LINEAR for i in range(n_layer))


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    block_size: int = 262144
    n_embd: int = 2048
    layer_types: Tuple[str, ...] = layer_kinds(48, 4)
    gdn_key_heads: int = 16
    gdn_value_heads: int = 32
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv: int = 4
    gdn_chunk: int = gdn.CHUNK
    n_head: int = 16  # of the full-attention layers
    n_kv_head: int = 2
    head_dim: int = 256
    rotary_dim: int = 64  # `partial_rotary_factor` x head_dim
    rope_theta: float = 1e7
    expert_dim: int = 512
    num_experts: int = 512  # the router's width
    top_k: int = 10
    first_expert: int = 0
    num_held: Optional[int] = None  # experts computed here; None: all
    shared_dim: int = 512  # `shared_expert_intermediate_size`
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    use_flash_attention: bool = True
    attn_fn: Any = None  # set under a mesh, which the delta rule has no form for yet
    lr_warmup_steps: int = 2000  # as MellumConfig.lr_warmup_steps

    family: ClassVar[Family]  # what TrainStep asks of it: set at the foot of this file

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def gdn_key_inner(self) -> int:
        return self.gdn_key_heads * self.gdn_key_dim

    @property
    def gdn_value_inner(self) -> int:
        return self.gdn_value_heads * self.gdn_value_dim

    @property
    def experts_held(self) -> int:
        return self.num_experts if self.num_held is None else self.num_held

    def gdn_params(self) -> int:
        """q, k, v and z, b and a's columns a value head, and o. The filters
        (taps x (2 Hk + Hv) x 128) are in no matmul."""
        d, keys, values = self.n_embd, self.gdn_key_inner, self.gdn_value_inner
        return d * (2 * keys + 2 * values) + d * 2 * self.gdn_value_heads + values * d

    def attention_params(self) -> int:
        """q and its gate, k, v, o."""
        d, hd = self.n_embd, self.head_dim
        return d * 2 * self.n_head * hd + 2 * d * self.n_kv_head * hd + self.n_head * hd * d

    def mixer_params(self) -> int:
        return (self.layer_types.count(LINEAR) * self.gdn_params()
                + self.layer_types.count(FULL) * self.attention_params())

    def matmul_params(self) -> int:
        """Each layer's mixer and its expert layer (KimiLinearConfig's rule:
        the router, the shared expert whole with its gate's row, and the
        expert matrices a token meets at even routing), and the untied head.
        The embedding is a look-up."""
        d = self.n_embd
        experts = self.top_k * self.experts_held / self.num_experts * 3 * d * self.expert_dim
        routed = d * self.num_experts + 3 * d * self.shared_dim + d + experts
        return int(self.mixer_params() + self.n_layer * routed + self.vocab_size * d)

    def flops_per_token(self, seq_len: int) -> int:
        """6 x matmul parameters; a full layer's causal term, 3 x T x heads x
        (256 + 256); and a DeltaNet layer's rule counted by the recurrence,
        whatever the chunk or the kernel (KimiLinearConfig.flops_per_token's):
        a token's three products with a value head's (128, 128) state
        forward and twice that backward."""
        full = 3 * seq_len * self.n_head * 2 * self.head_dim
        delta = 3 * 3 * 2 * self.gdn_value_heads * self.gdn_key_dim * self.gdn_value_dim
        return (6 * self.matmul_params() + self.layer_types.count(FULL) * full
                + self.layer_types.count(LINEAR) * delta)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, block_size=128, n_embd=64,
                    layer_types=(LINEAR, LINEAR, FULL), gdn_key_heads=2, gdn_value_heads=4,
                    gdn_key_dim=16, gdn_value_dim=16, gdn_chunk=16, n_head=4, n_kv_head=2,
                    head_dim=32, rotary_dim=8, expert_dim=32, num_experts=8, top_k=2,
                    shared_dim=32)
        base.update(kw)
        return cls(**base)


def a_log_init(key, shape, dtype=jnp.float32):
    """log(u), u uniform in (0, 16] (the checkpoint's module draws `A` so): a
    head with u near 16 forgets within a few steps."""
    return jnp.log(16.0 * (1.0 - jax.random.uniform(key, shape, dtype)))


class GatedDeltaNet(nn.Module):
    """(B, T, d) -> (B, T, d): the module docstring's DeltaNet mixer. Sows
    into "gdn_stats" the mean decay of a step, the mean beta, the RMS of the
    state after the last token and which path the delta rule took (1: the
    kernels)."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        if cfg.attn_fn is not None:
            raise NotImplementedError("the delta rule runs on one device")
        b, t, _ = x.shape
        hk, hv, dk, dv = (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
                          cfg.gdn_value_dim)
        keys, values = cfg.gdn_key_inner, cfg.gdn_value_inner
        f32 = jnp.float32
        dense = lambda width, name: nn.Dense(width, use_bias=False, dtype=cfg.dtype, name=name)
        with jax.named_scope("gdn.in_proj"):
            qkv = dense(2 * keys + values, "qkv_proj")(x)
            z = dense(values, "z_proj")(x)
            ba = dense(2 * hv, "ba_proj")(x).astype(f32)
        with jax.named_scope("gdn.conv"):
            w = self.param("conv_kernel", layers.conv_init, (cfg.gdn_conv, 2 * keys + values), f32)
            _, q, k, v, _ = causal_conv_within(qkv, w, jnp.zeros((2 * keys + values,), f32), 0,
                                               (keys, 2 * keys))
        with jax.named_scope("gdn.rule"):
            a_log = self.param("A_log", a_log_init, (hv,), f32)
            dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,), f32)
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = gdn.gate_log_decay(ba[..., hv:], a_log, dt_bias)
            # the l2 norms of a head's q and k: made inside the kernels where they run
            o, _, last = gdn.gdn(q.reshape(b, t, hk, dk), k.reshape(b, t, hk, dk),
                                 v.reshape(b, t, hv, dv), g, beta, cfg.gdn_chunk, l2_eps=L2_EPS)
            stat = lambda name, value: self.sow("gdn_stats", name, value)
            still = jax.lax.stop_gradient
            stat("decay_mean", jnp.exp(still(g)).mean())
            stat("beta_mean", still(beta).mean())
            stat("state_rms", jnp.sqrt(jnp.square(still(last)).mean()))
            stat("path", jnp.float32(gdn.gdn_path(t, dk, dv, cfg.gdn_chunk) == "pallas"))
        with jax.named_scope("gdn.norm"):
            # over a value head's 128, one weight; o stays (B, T, Hv x 128) from gdn_fwd to W_o
            y = kda_norm.kda_norm(o, z, NormWeight(name="o_norm")(dv), cfg.rms_eps,
                                  gate=kda_norm.SILU)
        with jax.named_scope("gdn.out_proj"):
            return dense(cfg.n_embd, "o_proj")(y)


def _mixer_half(block, x):
    cfg = block.config
    h = RMSNorm(cfg.rms_eps, name="attn_norm")(x)
    if block.kind == LINEAR:
        mixed = GatedDeltaNet(cfg, name="gdn")(h)
    else:
        mixed = LlamaAttention(cfg, gate=True, qk_norm=True, rotary_dim=cfg.rotary_dim,
                               name="attn")(h)
    return pin(x + mixed, block.stream)


def _moe_half(block, x):
    cfg = block.config
    h = RMSNorm(cfg.rms_eps, name="mlp_norm")(x)
    y, chosen = ExpertShare(
        cfg.n_embd, cfg.expert_dim, cfg.num_experts, cfg.top_k, cfg.first_expert,
        cfg.num_held, cfg.dtype, hand_up_choices=True,
        products_kept=False,  # no plan of this family keeps them: models/kanana.py says why
        headroom=EXPERT_HEADROOM, name="moe")(h)
    with jax.named_scope("moe.shared"):
        y = y + SharedExpert(cfg, scalar_gate=True, name="shared")(h)
    return pin(x + y, block.stream), chosen


class Qwen3NextBlock(nn.Module):
    """A block and the choices of its expert layer, (x, (B, T, top_k)). Each
    half is under `nn.remat` on its own, as models/kimi_linear.py's blocks
    and for its reason: a routed half's backward holds `ExpertShare`'s
    buffers of a row an assignment beside which a DeltaNet half's forward run
    again would stand. The leaves' paths: h_i/attn_norm, h_i/gdn or h_i/attn,
    h_i/mlp_norm, h_i/moe and h_i/shared."""

    config: Qwen3NextConfig
    kind: str
    keep: Any  # the halves' checkpoint policy
    stream: Any = None  # the residual stream's sharding, or None (models/llama.py)

    @nn.compact
    def __call__(self, x):
        x = nn.remat(_mixer_half, policy=self.keep)(self, pin(x, self.stream))
        return nn.remat(_moe_half, policy=self.keep)(self, x)


# What a block's remat saves after the first rung (the flash pair's output
# and logsumexp, and the expert layers' choices and plans, `moe_plan`): the
# delta rule's output and chunk states, which spare gdn_fwd's second run for
# 0.625 GiB a layer (the states 0.5 of it), worth what PERF.md section 6
# (PR 64) read of gdn_fwd in the benchmark's cell; and the attention layer's
# gate, one more array of the heads' width for a matmul of the stream's.
# The expert layer's three products are no rung, as in models/kanana.py.
REMAT_RUNGS = ((("gdn_out", "gdn_states"), 27.7), (("attn_gate",), 12.1))


def remat_plan(cfg: Qwen3NextConfig, shape: remat.StepShape, limit) -> remat.RematPlan:
    """What the blocks of a step of this shape save across remat, under a
    chip's `limit` of bytes: a pure function of its arguments. A name's
    bytes are one layer's, and `made_in` says which layers make it."""
    d, itemsize = cfg.n_embd, jnp.dtype(cfg.dtype).itemsize
    tokens = shape.rows * shape.seq_len
    linear, full = (remat.layers_of(cfg.layer_types, kind) for kind in (LINEAR, FULL))
    chunks = -(-shape.seq_len // cfg.gdn_chunk)
    name_bytes = remat.attention_bytes(shape, cfg.n_head, cfg.head_dim, itemsize)
    name_bytes.update(
        attn_gate=name_bytes["attn_out"],
        gdn_out=tokens * cfg.gdn_value_inner * itemsize,
        gdn_states=shape.rows * chunks * cfg.gdn_value_inner * cfg.gdn_key_dim * 4,
        moe_plan=moe.named_bytes(tokens, cfg.top_k, cfg.experts_held, cfg.num_experts, d,
                                 cfg.expert_dim, itemsize,
                                 headroom=EXPERT_HEADROOM)[moe.ROUTE_PLAN])
    made_in = dict(attn_out=full, attn_lse=full, attn_gate=full, gdn_out=linear,
                   gdn_states=linear)
    params = (cfg.mixer_params()
              + len(linear) * cfg.gdn_conv * (2 * cfg.gdn_key_inner + cfg.gdn_value_inner)
              + cfg.n_layer * (d * cfg.num_experts + 3 * d * cfg.shared_dim + d
                               + cfg.experts_held * 3 * d * cfg.expert_dim)
              + 2 * cfg.vocab_size * d)  # embedding and the untied head
    held = remat.held_bytes(
        shape, params=params, width=d, vocab=cfg.vocab_size,
        n_layer=2 * cfg.n_layer,  # a copy of the stream each half of a block
        itemsize=itemsize, block=_block_bytes(cfg, itemsize) * tokens)
    return remat.plan(REMAT_RUNGS, name_bytes, cfg.n_layer, held, limit,
                      remat.FIRST_RUNG + (moe.ROUTE_PLAN,), made_in)


def _block_bytes(cfg: Qwen3NextConfig, itemsize: int) -> int:
    """What the largest half of a block's backward works in, bytes a token,
    from its widths (models/kimi_linear.py's reckoning; a block's halves are
    rematerialised apart). A DeltaNet half's: the projection's q | k | v and
    z, the convolution's three, o and the gated o in the compute dtype, each
    with its gradient, and the chunk states (Hv x 128 x 128 float32 a
    chunk). A routed half's: the expert layer's buffers of a row an
    assignment that are as wide as the stream."""
    keys, values = cfg.gdn_key_inner, cfg.gdn_value_inner
    mixer = (2 * itemsize * (2 * (2 * keys + values) + 3 * values)
             + 4 * values * cfg.gdn_key_dim // cfg.gdn_chunk) if LINEAR in cfg.layer_types else 0
    return max(mixer, cfg.top_k * 4 * cfg.n_embd * itemsize)


class Qwen3NextGroup(nn.Module):
    """Every block of the model, each half of each under nn.remat: the one
    parameter group."""

    config: Qwen3NextConfig
    keep: Any  # the blocks' checkpoint policies, one a layer
    stream: Any = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        choices = []
        for i, kind in enumerate(cfg.layer_types):
            x, chosen = Qwen3NextBlock(cfg, kind, self.keep[i], self.stream, name=f"h_{i}")(x)
            choices.append(chosen)
        layers.sow_choices(self, choices)
        return x


class Qwen3Next(nn.Module):
    config: Qwen3NextConfig
    stream: Any = None  # parallel/mesh.py:stream_sharding of the step's mesh

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype, name="tok_emb",
                     embedding_init=nn.initializers.normal(0.02))(idx)
        keep = remat.block_policy(remat_plan, cfg, idx.shape, self.stream)
        x = Qwen3NextGroup(cfg, keep, self.stream, name="p_0")(x)
        x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
        return layers.untied_head(self, cfg, x)


QWEN3_NEXT_SHARDING_RULES = ShardingRules([
    (r"gdn/(qkv_proj|z_proj)/kernel", P("fsdp", "tp")),  # heads along the columns
    (r"gdn/ba_proj/kernel", P("fsdp", None)),
    (r"gdn/o_proj/kernel", P("tp", "fsdp")),
    (r"gdn/(conv_kernel|dt_bias|A_log)$", P()),
    (r"attn/wg/kernel", P("fsdp", "tp")),
] + layers.SHARED_EXPERT_SHARDING_PATTERNS + layers.UNTIED_HEAD_SHARDING_PATTERNS
    + EXPERT_SHARE_SHARDING_PATTERNS + layers.LLAMA_SHARDING_PATTERNS, default=P())


def step_metrics(cfg, sown, params, tokens):
    """`Family.metrics`: the expert layers' (ops/moe.py), and the means over
    layers of what the DeltaNet layers sowed (a step's decay exp(g), beta,
    the RMS of the state after a sequence's last token, the share of layers
    whose rule ran the kernels: `gdn_path`), of the attention layers' gate
    and of the shared experts' gate."""
    metrics = moe.step_metrics(cfg, sown, params, tokens)
    stats = [layer["gdn"] for period in sown.get("gdn_stats", {}).values()
             for layer in period.values()]  # the DeltaNet layers alone sow
    for name in ("decay_mean", "beta_mean", "state_rms", "path"):
        if stats:
            metrics[f"gdn_{name}"] = jnp.mean(jnp.stack([s[name][0] for s in stats]))
    for name in ("attn_gate", "shared_gate"):
        gates = jax.tree.leaves(sown.get(name, {}))
        if gates:
            metrics[f"{name}_mean"] = sum(gates) / len(gates)
    return metrics


Qwen3NextConfig.family = Family(
    module=Qwen3Next, rules=QWEN3_NEXT_SHARDING_RULES,
    sown=("moe_load", "gdn_stats", "attn_gate", "shared_gate"), metrics=step_metrics)
