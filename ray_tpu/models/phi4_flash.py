"""Decoder of two halves whose blocks are of five kinds and hand each other
more than the residual stream (Phi-4-mini-flash-reasoning: `model_type`
phi4flash; the SambaY architecture of Ren et al., "Decoder-Hybrid-Decoder
Architecture for Efficient Reasoning with Long Generation", with the
differential attention of Ye et al., "Differential Transformer").

The equations, with d the hidden size, E the tied embedding, x the residual
stream, L the published layer count and i a layer's published index from 0:

    x0 = E[idx]
    a layer:  x <- x + mixer_i(LN(x));  x <- x + MLP(LN(x))
              MLP(u) = W_down (silu(g) * h),  [g | h] = W_gate_up u    no bias
    logits = E LN(x)  (float32);  loss = mean cross-entropy of the next token

    i < L/2:    even i a Mamba-1 mixer, odd i window attention
    i = L/2:    Mamba-1, whose scan output y is also the *memory* m
    i = L/2+1:  full causal attention, whose K and V are also handed down
    i > L/2+1:  even i a gated memory unit, odd i cross attention

`mamba` (Gu & Dao 2023; C channels, N states, rank R, K taps):

    [u | z] = W_in x;  u <- silu(conv(u))      depthwise, causal, with bias
    [r | B_t | C_t] = W_x u;  Delta = softplus(W_dt r + b_dt);  A = -exp(A_log)
    h_t = exp(Delta_t A) * h_{t-1} + (Delta_t u_t) B_t^T;   y_t = h_t C_t + D u_t
    out = W_out (y * silu(z))

The recurrence is ops/selective_scan.py (pallas kernels sscan_fwd and
sscan_bwd on a TPU, the recurrence step by step elsewhere), the convolution
under its bias and silu ops/short_conv.py's causal pair, read where W_in
wrote u.

`gmu`: out = W_2 (m * silu(W_1 x)), no scan and no convolution: the memory
gated by the present stream.

Differential attention, `window`, `full` and `cross` alike, heads of 64: the
n_head query heads are n_head / 2 pairs (q1, q2) = heads (2j, 2j+1), the
n_kv_head key heads n_kv_head / 2 pairs (k1, k2), the value heads
n_kv_head / 2 values 128 wide; query pairs read key-value pair j // (n_head
/ n_kv_head);

    o_j = (softmax(q1 k1^T / 8) - lambda softmax(q2 k2^T / 8)) v
    out = W_o concat_j ((1 - lambda_init) RMSNorm_128(o_j) w) + b_o
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
    lambda_init = 0.8 - 0.6 exp(-0.3 i)

It runs on the flash kernels the repo has (ops/attention.py, untouched) as
**n_head heads of 128**: each of a pair's two maps is one head whose query
and key are its 64 lanes and 64 zeros and whose value is the pair's 128, so
a map is applied to the whole value by one call and the scores are made
once. The kernel divides the scores by sqrt(128), the model by 8: W_q's
columns and its bias are multiplied by sqrt(2) in float32 before they are
rounded to the compute dtype, so no operand is rounded twice. A 128-deep
pass of the MXU costs what a 64-deep one does; the other form on these
kernels, 2 n_head heads of 64 with each map applied to each half of the
value, makes the scores twice (a third more model FLOPs, and twice the
kernels' passes, since a 64-wide head's value pass is 128 lanes wide with
its neighbour's half discarded). The difference, its norm and the factor are
XLA's, float32, under the scope `attn.diff`. A cross layer has W_q, lambda,
norm and W_o of its own and takes K and V from the full layer as that layer's
W_qkv wrote them.

**What crosses blocks.** Every block takes and gives (x, m, (K, V)): m and
K, V are outputs of the blocks that make them and inputs of every block
that reads them, so `nn.remat` holds them through the step as it holds each
block's input, never makes them again, and sums the readers' cotangents into
them. Before their sources have run they are empty arrays (a configuration
that reads before its source is refused). The plan books their bytes beside
the layers' inputs (`remat_plan`).

Departures from the published code, all under `assumed` in
bench/configs/phi4_mini_flash_l5.json: Mamba-1's sizes by the family's
defaults; which of two adjacent heads is q1; the convolution's taps stored
(K, channels); no clamp on Delta; the scan's own initialisation.

Named scopes: ssm.in_proj, ssm.conv, ssm.x_proj, ssm.dt, ssm.scan, ssm.gate,
ssm.out_proj; gmu.in_proj, gmu.gate, gmu.out_proj; attn.window, attn.full,
attn.cross (the flash calls with the operands' layout round them) and
attn.diff; a cross layer's module is `cross`, a self layer's `attn`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, ClassVar, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.models import Family, layers, remat
from ray_tpu.ops.selective_scan import chunk_of, selective_scan
from ray_tpu.ops.short_conv import causal_conv_within
from ray_tpu.parallel.mesh import ShardingRules, pin

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    block_size: int = 262144
    n_embd: int = 2560
    n_layer_published: int = 32
    layers_kept: Tuple[int, ...] = tuple(range(32))  # published indices, from 0
    n_head: int = 40
    n_kv_head: int = 20
    intermediate: int = 10240
    window: int = 512
    ssm_inner: int = 5120
    ssm_state: int = 16
    ssm_rank: int = 160
    ssm_conv: int = 4
    ln_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    use_flash_attention: bool = True
    attn_fn: Any = None  # as LlamaConfig.attn_fn

    family: ClassVar[Family]  # what TrainStep asks of it: set at the foot of this file

    def __post_init__(self):
        kinds = self.layer_types
        for reader, source in ((GMU, self.memory_layer), (CROSS, self.kv_layer)):
            if reader in kinds and (source not in self.layers_kept or
                                    self.layers_kept.index(source) > kinds.index(reader)):
                raise ValueError(f"a {reader} layer is kept and layer {source}, which it "
                                 f"reads, is not kept before it: {self.layers_kept}")
        if self.n_head % self.n_kv_head or self.n_kv_head % 2:
            raise ValueError("differential attention pairs adjacent heads")

    @property
    def memory_layer(self) -> int:
        return self.n_layer_published // 2

    @property
    def kv_layer(self) -> int:
        return self.n_layer_published // 2 + 1

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """The kind of each kept layer, by its published index."""
        def kind(i):
            if i <= self.memory_layer:
                return MAMBA if i % 2 == 0 else WINDOW
            if i == self.kv_layer:
                return FULL
            return GMU if i % 2 == 0 else CROSS
        return tuple(kind(i) for i in self.layers_kept)

    @property
    def n_layer(self) -> int:
        return len(self.layers_kept)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @property
    def kv_dim(self) -> int:
        """The width of K, and of V, as the full layer hands them down."""
        return self.n_kv_head * self.head_dim

    def mixer_matmul_params(self, kind: str) -> int:
        d, c = self.n_embd, self.ssm_inner
        if kind == MAMBA:
            return d * 2 * c + c * (self.ssm_rank + 2 * self.ssm_state) + self.ssm_rank * c + c * d
        if kind == GMU:
            return 2 * d * c
        return 2 * d * d + (0 if kind == CROSS else 2 * d * self.kv_dim)

    def mixer_vector_params(self, kind: str) -> int:
        """The mixer's parameters in no matmul: taps, biases, A, D, lambda's
        four vectors and the difference's norm."""
        d, c, lam = self.n_embd, self.ssm_inner, 6 * self.head_dim
        if kind == MAMBA:
            return c * (self.ssm_conv + 1) + c + c * self.ssm_state + c
        if kind == GMU:
            return 0
        return 2 * d + lam + (0 if kind == CROSS else 2 * self.kv_dim)

    def matmul_params(self) -> int:
        """Each layer's mixer and MLP, and the tied matrix once, as the head
        (the embedding is a look-up)."""
        return (sum(self.mixer_matmul_params(kind) + 3 * self.n_embd * self.intermediate
                    for kind in self.layer_types) + self.vocab_size * self.n_embd)

    def params(self) -> int:
        """Every parameter: four LayerNorm vectors a layer and two before
        the head beside the matrices and the mixers' vectors."""
        return (self.matmul_params() + 2 * self.n_embd
                + sum(self.mixer_vector_params(kind) + 4 * self.n_embd
                      for kind in self.layer_types))

    def flops_per_token(self, seq_len: int) -> int:
        """6 x matmul parameters; an attention layer's two maps a pair by the
        model's shapes, whatever computes them (scores 64 deep over n_head
        heads and a value 128 wide under each map: 3 d multiply-adds a
        visible key forward, at a mean of T / 2 keys or the window's); the
        recurrence as it stands, 6 C N a token and Mamba layer forward;
        three times each with the backward."""
        kinds, t, w = self.layer_types, seq_len, min(self.window, seq_len)
        keys = ((kinds.count(FULL) + kinds.count(CROSS)) * t / 2
                + kinds.count(WINDOW) * (w - w * w / (2 * t)))
        return int(6 * self.matmul_params() + 18 * self.n_embd * keys
                   + 18 * self.ssm_inner * self.ssm_state * kinds.count(MAMBA))

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, block_size=256, n_embd=64, layers_kept=(15, 16, 17, 18, 19),
                    n_head=8, n_kv_head=4, intermediate=128, window=32, ssm_inner=128,
                    ssm_rank=4)
        base.update(kw)
        return cls(**base)


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def _a_log_init(key, shape, dtype=jnp.float32):
    """log(1..N) a channel."""
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape)


def _dt_kernel_init(key, shape, dtype=jnp.float32):
    """Uniform in +-rank^-1/2 (the family's `dt_init` "random")."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class Affine(nn.Module):
    """x W + b in float32 from operands in `dtype`: the bias is added before
    the result is rounded, by whoever rounds it. `columns` (width,) multiplies
    W's columns and b in float32 before W is rounded to `dtype`."""

    width: int
    dtype: Any
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros
    columns: Optional[Any] = None

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", self.kernel_init, (x.shape[-1], self.width), jnp.float32)
        bias = self.param("bias", self.bias_init, (self.width,), jnp.float32)
        if self.columns is not None:
            kernel, bias = kernel * self.columns, bias * self.columns
        return jnp.dot(x, kernel.astype(self.dtype), preferred_element_type=jnp.float32) + bias


class Mamba1Mixer(nn.Module):
    """(B, T, d) -> ((B, T, d), y (B, T, C)): the module docstring's `mamba`
    layer and its scan output before the gate. Sows into "ssm_stats" the most
    negative log-decay of a chunk and the largest entry of a carried state."""

    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        t = x.shape[1]
        c, n, r, f32 = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_rank, jnp.float32
        dense = lambda width, name: nn.Dense(width, use_bias=False, dtype=cfg.dtype, name=name)
        with jax.named_scope("ssm.in_proj"):
            uz = dense(2 * c, "in_proj")(x)
        with jax.named_scope("ssm.conv"):
            w = self.param("conv_kernel", layers.conv_init, (cfg.ssm_conv, c), f32)
            bias = self.param("conv_bias", nn.initializers.zeros, (c,), f32)
            _, u, z = causal_conv_within(uz, w, bias)
        with jax.named_scope("ssm.x_proj"):
            rank, bm, cm = jnp.split(dense(r + 2 * n, "x_proj")(u), [r, r + n], axis=-1)
        with jax.named_scope("ssm.dt"):  # the steps stay float32 into the scan
            delta = jax.nn.softplus(Affine(c, cfg.dtype, _dt_kernel_init, layers.dt_bias_init,
                                           name="dt_proj")(rank))
        with jax.named_scope("ssm.scan"):
            a = -jnp.exp(self.param("A_log", _a_log_init, (c, n), f32))
            skip = self.param("D", nn.initializers.ones, (c,), f32)
            y, states = selective_scan(u, delta, a, bm, cm, skip)
            chunk = chunk_of(t)
            steps = jax.lax.stop_gradient(delta).reshape(-1, t // chunk, chunk, c).sum(2)
            self.sow("ssm_stats", "chunk_log_decay_min",
                     (steps * jax.lax.stop_gradient(a).min(1)).min())
            self.sow("ssm_stats", "state_abs_max", jnp.abs(jax.lax.stop_gradient(states)).max())
        with jax.named_scope("ssm.gate"):
            gated = (y.astype(f32) * nn.silu(z.astype(f32))).astype(cfg.dtype)
        with jax.named_scope("ssm.out_proj"):
            return dense(cfg.n_embd, "out_proj")(gated), y


class GatedMemoryUnit(nn.Module):
    """(x (B, T, d), m (B, T, C)) -> W_2 (m * silu(W_1 x))."""

    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, x, memory):
        cfg = self.config
        dense = lambda width, name: nn.Dense(width, use_bias=False, dtype=cfg.dtype, name=name)
        with jax.named_scope("gmu.in_proj"):
            gate = checkpoint_name(dense(cfg.ssm_inner, "in_proj")(x), "gmu_gate")
        with jax.named_scope("gmu.gate"):
            gated = (memory.astype(jnp.float32) * nn.silu(gate.astype(jnp.float32))
                     ).astype(cfg.dtype)
        with jax.named_scope("gmu.out_proj"):
            return dense(cfg.n_embd, "out_proj")(gated)


class DiffAttention(nn.Module):
    """(x, (K, V) or None) -> (out, (K, V)): the module docstring's
    differential attention at published index `index`. With no K and V given
    it makes its own from x (a self layer, `window` keys a query sees or all
    before it) and hands them up as W_qkv wrote them, (B, T, kv heads x 64)
    each; given them it projects the queries alone (a cross layer). Sows
    lambda into "attn_stats"."""

    config: Phi4FlashConfig
    index: int
    window: Optional[int] = None

    @nn.compact
    def __call__(self, x, kv=None):
        cross = kv is not None
        cfg = self.config
        b, t, d = x.shape
        hd, heads, kv_dim, f32 = cfg.head_dim, cfg.n_head, cfg.kv_dim, jnp.float32
        pairs, reps = cfg.n_kv_head // 2, cfg.n_head // cfg.n_kv_head
        # the kernels divide by sqrt(2 hd), the model by sqrt(hd): the factor on
        # W_q's columns, in float32, before they are rounded
        up = jnp.full((d,), math.sqrt(2.0), f32)
        if not cross:
            columns = jnp.concatenate([up, jnp.ones((2 * kv_dim,), f32)])
            qkv = Affine(d + 2 * kv_dim, cfg.dtype, columns=columns, name="qkv")(x)
            q, k, v = jnp.split(qkv.astype(cfg.dtype), [d, d + kv_dim], axis=-1)
            kv = (k, v)
        else:
            q = Affine(d, cfg.dtype, columns=up, name="wq")(x).astype(cfg.dtype)
            k, v = kv
        kind = "attn.cross" if cross else (
            "attn.full" if self.window is None else "attn.window")
        with jax.named_scope(kind):
            # heads of 2 hd lanes: a map's query and key beside hd zeros, the
            # pair's whole value under each of its two maps
            wide = lambda a: jnp.pad(a, ((0, 0),) * 3 + ((0, hd),))
            q = wide(q.reshape(b, t, heads, hd))
            k = wide(jnp.broadcast_to(k.reshape(b, t, pairs, 1, 2, hd),
                                      (b, t, pairs, reps, 2, hd)).reshape(b, t, heads, hd))
            v = jnp.broadcast_to(v.reshape(b, t, pairs, 1, 2 * hd),
                                 (b, t, pairs, 2 * reps, 2 * hd)).reshape(b, t, heads, 2 * hd)
            window = self.window if self.window is not None and self.window < t else None
            if cfg.attn_fn is not None:
                o = cfg.attn_fn(q, k, v) if window is None else cfg.attn_fn(q, k, v, window=window)
            elif cfg.use_flash_attention:
                from ray_tpu.ops.attention import causal_attention

                o = causal_attention(q, k, v, window=window)
            else:
                from ray_tpu.ops.attention import xla_causal_attention

                o = xla_causal_attention(q, k, v, window)
        with jax.named_scope("attn.diff"):
            vec = lambda name: self.param(name, nn.initializers.normal(0.1), (hd,), f32)
            init = lambda_init(self.index)
            lam = (jnp.exp(jnp.dot(vec("lambda_q1"), vec("lambda_k1")))
                   - jnp.exp(jnp.dot(vec("lambda_q2"), vec("lambda_k2"))) + init)
            self.sow("attn_stats", "lambda", jax.lax.stop_gradient(lam))
            weight = self.param("subln_weight", nn.initializers.ones, (2 * hd,), f32)
            o = o.astype(f32).reshape(b, t, heads // 2, 2, 2 * hd)
            o = o[..., 0, :] - lam * o[..., 1, :]
            o = o * jax.lax.rsqrt(jnp.square(o).mean(-1, keepdims=True) + cfg.ln_eps)
            o = (o * (weight * (1.0 - init))).astype(cfg.dtype).reshape(b, t, d)
        return Affine(d, cfg.dtype, name="wo")(o).astype(cfg.dtype), kv


class Phi4FlashMLP(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=cfg.dtype, name=name)
        gate, up = jnp.split(checkpoint_name(dense(2 * cfg.intermediate, "gate_up")(x), "mlp_up"),
                             2, axis=-1)
        return dense(cfg.n_embd, "down")(nn.silu(gate) * up)


class Phi4FlashBlock(nn.Module):
    """(x, m, (K, V)) -> (x, m, (K, V)): one block of `kind` at published
    index `index`; m and K, V go through as they came unless this block is
    their source."""

    config: Phi4FlashConfig
    kind: str
    index: int
    stream: Any = None  # the residual stream's sharding, or None (models/llama.py)

    @nn.compact
    def __call__(self, x, memory, kv):
        cfg = self.config
        x = pin(x, self.stream)
        norm = lambda name: nn.LayerNorm(epsilon=cfg.ln_eps, dtype=cfg.dtype, name=name)
        h = norm("mixer_norm")(x)
        if self.kind == MAMBA:
            mixed, y = Mamba1Mixer(cfg, name="mamba")(h)
            if self.index == cfg.memory_layer:
                memory = y
        elif self.kind == GMU:
            mixed = GatedMemoryUnit(cfg, name="gmu")(h, memory)
        elif self.kind == CROSS:
            mixed, _ = DiffAttention(cfg, self.index, name=CROSS)(h, kv)
        else:
            mixed, own = DiffAttention(cfg, self.index, cfg.window if self.kind == WINDOW else None,
                                       name="attn")(h)
            if self.kind == FULL:
                kv = own
        x = pin(x + mixed, self.stream)
        x = x + Phi4FlashMLP(cfg, name="mlp")(norm("mlp_norm")(x))
        return pin(x, self.stream), memory, kv


# What a block's remat saves after the first rung (the flash calls' outputs
# and logsumexps in the three kinds of attention layer), and the ms of a step
# each spared for a GiB held. The scan's output and chunk states spare
# sscan_fwd's second run: 3.05 ms a call in the benchmark's cell on a v5e (my
# chip run, PR 57, call 1: the call's time in the traced step, not a step's
# difference) for 0.195 GiB (y 160 MiB, the states 40). The MLP's product
# spares its matmul's second run: 12.4 ms a layer in the same trace (`mlp
# remat` 61.9 ms over five layers) for 0.625 GiB, 19.8 ms a GiB; at this
# family's cell the plan has no room for all five layers' (3.1 GiB beside
# 8.60 GiB of state), and since PR 62, when the rule took to saving a rung
# in as many layers as there is room for (models/remat.py), it saved the
# last three's (1.875 GiB; it reckoned 13.30 of 13.5); since PR 65, held to
# the chip's own limit to within 64 MiB, the last four's (2.5 GiB; it
# reckons 13.92 of 14.12, the step compiled for a v5e holds 13.155). The memory
# layer's y is held as m whatever is saved: its name costs that layer
# nothing more.
REMAT_RUNGS = ((("sscan_y", "sscan_states"), 15.6), (("mlp_up",), 19.8))


def carried_bytes(cfg: Phi4FlashConfig, tokens: int, itemsize: int) -> int:
    """Bytes of what crosses blocks beside the stream, a step: m and K, V of
    the layers kept."""
    return tokens * itemsize * (
        (cfg.ssm_inner if cfg.memory_layer in cfg.layers_kept else 0)
        + (2 * cfg.kv_dim if cfg.kv_layer in cfg.layers_kept else 0))


def remat_plan(cfg: Phi4FlashConfig, shape: remat.StepShape, limit) -> remat.RematPlan:
    """What the blocks of a step of this shape save across remat, under a
    chip's `limit` of bytes: a pure function of its arguments. A name's
    bytes are one layer's, and `made_in` says which layers make it. m and K, V are booked with the layers'
    inputs, in `Held.always`."""
    d, itemsize = cfg.n_embd, jnp.dtype(cfg.dtype).itemsize
    tokens = shape.rows * shape.seq_len
    kinds = cfg.layer_types
    # the calls' heads are n_head of twice the model's width
    name_bytes = remat.attention_bytes(shape, cfg.n_head, 2 * cfg.head_dim, itemsize)
    made_in = dict.fromkeys(name_bytes, remat.layers_of(kinds, WINDOW, FULL, CROSS))
    made_in.update(dict.fromkeys(("sscan_y", "sscan_states"), remat.layers_of(kinds, MAMBA)))
    chunks = -(-shape.seq_len // chunk_of(shape.seq_len))
    name_bytes.update(
        sscan_y=tokens * cfg.ssm_inner * itemsize,
        sscan_states=shape.rows * chunks * cfg.ssm_inner * cfg.ssm_state * 4,
        mlp_up=2 * tokens * cfg.intermediate * itemsize // shape.tp)
    held = remat.held_bytes(shape, params=cfg.params(), width=d, vocab=cfg.vocab_size,
                            n_layer=cfg.n_layer, itemsize=itemsize,
                            block=_block_bytes(cfg, itemsize) * tokens)
    held = held._replace(always=held.always + carried_bytes(cfg, tokens, itemsize))
    return remat.plan(REMAT_RUNGS, name_bytes, cfg.n_layer, held, limit, made_in=made_in)


def _block_bytes(cfg: Phi4FlashConfig, itemsize: int) -> int:
    """What the largest block's backward works in, bytes a token, from its
    widths: a Mamba block's [u | z], the convolution's output, the scan's
    output and the gated y with their gradients in the compute dtype and the
    steps and their gradient in float32; an attention block's q, k, v and o
    as the calls take them (n_head heads of twice the width) with their
    gradients and the difference in float32; beside either the MLP's product
    and its gradient."""
    c, wide = cfg.ssm_inner, 2 * cfg.n_head * cfg.head_dim
    mamba = 2 * itemsize * 5 * c + 2 * 4 * c if MAMBA in cfg.layer_types else 0
    attention = 2 * itemsize * 4 * wide + 2 * 4 * wide
    return max(mamba, attention) + itemsize * 4 * cfg.intermediate


class Phi4FlashGroup(nn.Module):
    """Every block of the model, each under nn.remat: the one parameter
    group (the blocks' structures differ, and m and K, V cross them)."""

    config: Phi4FlashConfig
    keep: Any  # the blocks' checkpoint policies, one a layer
    stream: Any = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, t, _ = x.shape
        # nothing yet: a reader stands after its source (`__post_init__`)
        memory = jnp.zeros((b, t, 0), cfg.dtype)
        kv = (jnp.zeros((b, t, 0), cfg.dtype),) * 2
        for i, (kind, index) in enumerate(zip(cfg.layer_types, cfg.layers_kept)):
            x, memory, kv = nn.remat(Phi4FlashBlock, policy=self.keep[i])(
                cfg, kind, index, self.stream, name=f"h_{i}")(x, memory, kv)
        return x


class Phi4Flash(nn.Module):
    config: Phi4FlashConfig
    stream: Any = None  # parallel/mesh.py:stream_sharding of the step's mesh

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        emb = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype, name="tok_emb",
                       embedding_init=nn.initializers.normal(0.02))
        x = emb(idx)
        keep = remat.block_policy(remat_plan, cfg, idx.shape, self.stream)
        x = Phi4FlashGroup(cfg, keep, self.stream, name="p_0")(x)
        x = nn.LayerNorm(epsilon=cfg.ln_eps, dtype=cfg.dtype, name="final_norm")(x)
        # the tied head in float32 under the untied one's name (models/granite.py)
        with jax.named_scope("lm_head"):
            return x.astype(jnp.float32) @ emb.embedding.astype(jnp.float32).T


PHI4_FLASH_SHARDING_RULES = ShardingRules([
    (r"tok_emb/embedding", P("tp", "fsdp")),
    (r"(attn/qkv|cross/wq)/kernel", P("fsdp", "tp")),  # column parallel
    (r"(attn|cross)/wo/kernel", P("tp", "fsdp")),       # row parallel
    (r"mamba/in_proj/kernel", P("fsdp", None)),
    (r"mamba/out_proj/kernel", P(None, "fsdp")),
    (r"gmu/in_proj/kernel", P("fsdp", "tp")),
    (r"gmu/out_proj/kernel", P("tp", "fsdp")),
    (r"mlp/gate_up/kernel", P("fsdp", None)),  # gate and up side by side: not split by columns
    (r"mlp/down/kernel", P(None, "fsdp")),
], default=P())


def step_metrics(cfg, sown, params, tokens):
    """`Family.metrics`: of what the Mamba layers sowed the most negative
    log-decay of a chunk and the largest carried-state entry
    (`layers.ssm_step_metrics`, Mamba-2's two gauges); lambda's range over the
    attention layers; and the bytes that cross blocks beside the stream."""
    metrics = layers.ssm_step_metrics(cfg, sown, params, tokens)
    lams = [mixer["lambda"][0] for group in sown.get("attn_stats", {}).values()
            for layer in group.values() for mixer in layer.values()]
    if lams:
        metrics["attn_lambda_min"] = jnp.min(jnp.stack(lams))
        metrics["attn_lambda_max"] = jnp.max(jnp.stack(lams))
    metrics["carried_bytes"] = jnp.asarray(
        carried_bytes(cfg, tokens, jnp.dtype(cfg.dtype).itemsize), jnp.float32)
    return metrics


Phi4FlashConfig.family = Family(
    module=Phi4Flash, rules=PHI4_FLASH_SHARDING_RULES, sown=("ssm_stats", "attn_stats"),
    metrics=step_metrics)
