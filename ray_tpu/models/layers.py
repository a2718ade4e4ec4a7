"""The layers that several families run, each with what describes it: its
sharding patterns, what its backward works in, the reducer of what it sows.
A family's file (models/__init__.py states the rule) imports them from here
and from no other family's file.

TPU design notes of the attention and the MLP every family but GPT-2 builds on:
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
  per sublayer inserted by XLA from the shardings.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import moe
from ray_tpu.ops.short_conv import causal_conv_within
from ray_tpu.ops.ssd import ssd


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


class NormWeight(nn.Module):
    """An RMSNorm's leaf alone, `<name>/weight` (width,) float32: for a layer
    whose norm a kernel computes (`LlamaAttention._on_rows`,
    models/kimi_linear.py's `KimiDeltaAttention`)."""

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
    sigmoid's mean into "attn_gate": 0.5 at initialisation. `blocks`: the
    layer's input is the doubled stream [noised | clean] of a block-diffusion
    step (models/sdar.py), (B, 2T, C): both halves carry positions 0 .. T-1,
    and a query sees what ops/attention.py's `block_diffusion_mask` shows it
    with blocks of this length. `rotary_dim`: the rotary turns a head's first
    so many entries (their halves, positions from 0) and leaves the others as
    they are (models/qwen3_next.py: 64 of 256, `partial_rotary_factor`);
    None: the whole head."""

    config: Any
    window: Optional[int] = None
    inv_freq: Optional[tuple] = None
    rope_scale: float = 1.0
    qk_norm: bool = False
    select: Any = None
    rotary: bool = True
    q_scale: float = 1.0
    gate: bool = False
    blocks: Optional[int] = None
    rotary_dim: Optional[int] = None

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
                   and self.rotary_dim is None  # ops/qk_prep.py turns whole heads of 128 lanes
                   and (self.qk_norm or self.rotary) and attention_path(T, self.blocks) == "flash")
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

    @nn.nowrap
    def _positions(self, T, pos_offset):
        """The positions the rotary turns by; a doubled stream's halves carry
        the same ones."""
        at = jnp.arange(T)
        return (at if self.blocks is None else at % (T // 2)) + pos_offset

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
                turned = hd if self.rotary_dim is None else self.rotary_dim
                ang = rope_angles(turned, cfg.rope_theta, self._positions(T, pos_offset),
                                  self.inv_freq)
                if turned == hd:
                    q = apply_rope(q, ang, self.rope_scale)
                    k = apply_rope(k, ang, self.rope_scale)
                else:
                    q, k = (jnp.concatenate([apply_rope(a[..., :turned], ang, self.rope_scale),
                                             a[..., turned:]], axis=-1) for a in (q, k))
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
        elif self.blocks is not None:
            if cfg.attn_fn is not None:
                raise NotImplementedError("a doubled stream's attention runs on one device")
            from ray_tpu.ops.attention import causal_attention

            with jax.named_scope("attn.flash_bd"):
                y = causal_attention(q, k, v, blocks=self.blocks)
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
            w_q, w_k = (NormWeight(name=name)(hd) for name in ("q_norm", "k_norm"))
        if self.rotary:
            with jax.named_scope("attn.rope"):
                ang = rope_angles(hd, cfg.rope_theta, self._positions(T, pos_offset),
                                  self.inv_freq)
                tables = rope_tables(ang, self.rope_scale)
        with jax.named_scope("attn.qk_norm" if self.qk_norm else "attn.rope"):
            q = qk_prep(q, w_q, tables, eps=cfg.rms_eps, scale=self.q_scale)
            k = qk_prep(k, w_k, tables, rep=rep, eps=cfg.rms_eps)
        v = _as_rows(jnp.broadcast_to(v[:, :, :, None, :], (B, T, cfg.n_kv_head, rep, hd)
                                      ).reshape(B, T, cfg.n_head, hd))
        if self.blocks is not None:
            with jax.named_scope("attn.flash_bd"):
                return flash_attention_rows(q, k, v, cfg.n_head, blocks=self.blocks)
        if chosen is None:
            return flash_attention_rows(q, k, v, cfg.n_head, window=window)
        with jax.named_scope("attn.selected"):
            return flash_attention_rows(q, k, v, cfg.n_head, select=chosen)


class LlamaMLP(nn.Module):
    config: Any  # a LlamaConfig, or any config with `mlp_dim`, `n_embd` and `dtype`

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=cfg.dtype, name=name)
        gate, up = (checkpoint_name(dense(cfg.mlp_dim, name)(x), "mlp_up")
                    for name in ("gate", "up"))
        return dense(cfg.n_embd, "down")(nn.silu(gate) * up)


# Megatron-style TP layout + fsdp on the complementary dim. Paths are flax
# pytree paths like 'h_3/attn/wq/kernel'. `lm_head/kernel` is an `nn.Dense`
# head's (models/llama.py, models/mellum.py).
LLAMA_SHARDING_PATTERNS = [
    (r"tok_emb/embedding", P("tp", "fsdp")),
    (r"attn/w[qkv]/kernel", P("fsdp", "tp")),   # column parallel
    (r"attn/wo/kernel", P("tp", "fsdp")),       # row parallel
    (r"mlp/(gate|up)/kernel", P("fsdp", "tp")),
    (r"mlp/down/kernel", P("tp", "fsdp")),
    (r"lm_head/kernel", P("fsdp", "tp")),
    (r"norm", P()),
]


def a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step drawn log-uniformly from 1e-3 to 1e-1."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def conv_init(key, shape, dtype=jnp.float32):
    """torch's conv1d default: uniform in +-1/sqrt(fan_in), fan_in the K taps."""
    bound = 1 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class Mamba2Mixer(nn.Module):
    """(B, T, d) -> (B, T, d): a Mamba-2 mixer (Dao & Gu 2024), H heads of P,
    G groups of B and C, state N, inner width H P:

        [z | xBC | dt] = W_in u          d -> H P + (H P + 2 G N) + H, no bias
        xBC <- silu(conv(xBC))           depthwise, causal, K taps, with bias
        x (T, H, P), B (T, G, N), C (T, G, N) = split(xBC)
        head h reads B and C of group h // (H / G)
        Delta = softplus(dt + dt_bias)   (H);  A = -exp(A_log)  (H)
        S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T;   y_t = S_t C_t + D x_t
        y <- RMSNorm_g(y * silu(z))      the gate first, then an RMS norm over
                                         each of `norm_groups` equal parts of
                                         the H P channels on its own, one
                                         weight of H P
        out = W_out y                    H P -> d, no bias

    The recurrence is ops/ssd.py (its chunked form at `ssm_chunk`; pallas
    kernels ssd_fwd and ssd_bwd on a TPU); the convolution under its bias and
    silu ops/short_conv.py's (causal_conv_fwd and causal_conv_bwd on a TPU,
    reading xBC where W_in wrote it; the same lines in jax.numpy elsewhere);
    gate and norm over all channels at once (`norm_groups` 1) are XLA's,
    which fuses them into their neighbours, and by group one call of
    ops/gated_norm.py (gated_norm_fwd and gated_norm_bwd on a TPU: a pass of
    its own that reads z and each group where they lie). Sows into
    "ssm_stats" the most negative log-decay of a chunk and the largest entry
    of a carried state (TrainStep's telemetry). `config` is a GraniteConfig
    or any config with its `ssm_*` fields, `n_embd`, `rms_eps` and `dtype`."""

    config: Any
    norm_groups: int = 1

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        b, t, _ = u.shape
        h, p, g, n, k = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
                         cfg.ssm_conv)
        inner, conv_dim = cfg.ssm_inner, cfg.ssm_conv_dim
        dense = lambda width, name: nn.Dense(width, use_bias=False, dtype=cfg.dtype, name=name)
        with jax.named_scope("ssm.in_proj"):
            zxbcdt = dense(inner + conv_dim + h, "in_proj")(u)
        with jax.named_scope("ssm.conv"):
            w = self.param("conv_kernel", conv_init, (k, conv_dim), jnp.float32)
            bias = self.param("conv_bias", nn.initializers.zeros, (conv_dim,), jnp.float32)
            z, x, bm, cm, dt = causal_conv_within(zxbcdt, w, bias, inner,
                                                   (inner, inner + g * n))
        with jax.named_scope("ssm.scan"):
            dt_bias = self.param("dt_bias", dt_bias_init, (h,), jnp.float32)
            a_log = self.param("A_log", a_log_init, (h,), jnp.float32)
            skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
            delta = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            y, states = ssd(x.reshape(b, t, h, p), delta, -jnp.exp(a_log),
                            bm.reshape(b, t, g, n), cm.reshape(b, t, g, n), skip, cfg.ssm_chunk)
            chunk = min(cfg.ssm_chunk, t)
            log_decay = (delta * -jnp.exp(a_log)).reshape(b, t // chunk, chunk, h).sum(2)
            self.sow("ssm_stats", "chunk_log_decay_min", jax.lax.stop_gradient(log_decay.min()))
            self.sow("ssm_stats", "state_abs_max",
                     jax.lax.stop_gradient(jnp.abs(states).max()))
        with jax.named_scope("ssm.gate"):
            norm = RMSNorm(cfg.rms_eps, self.norm_groups, name="norm")
            y = y.reshape(b, t, inner)
            # one group: lines XLA fuses into their neighbours; more: a pass of its own
            y = (norm(y * nn.silu(z)) if self.norm_groups == 1 else
                 norm(y, gate=z, within=(zxbcdt, 0)))
        with jax.named_scope("ssm.out_proj"):
            return dense(cfg.n_embd, "out_proj")(y)


MAMBA_SHARDING_PATTERNS = [
    (r"mamba/in_proj/kernel", P("fsdp", None)),
    (r"mamba/out_proj/kernel", P(None, "fsdp")),
    (r"mamba/", P()),
]


def mixer_bytes(cfg, itemsize: int) -> int:
    """What a Mamba mixer's backward works in, bytes a token, from its
    widths: the input projection's output in the compute dtype; the
    convolution's output, the scan's output and the gated norm's input in
    that and in float32."""
    return (itemsize * (cfg.ssm_inner + cfg.ssm_conv_dim + cfg.ssm_heads)
            + (itemsize + 4) * (cfg.ssm_conv_dim + 2 * cfg.ssm_inner))


def ssm_step_metrics(cfg, sown, params, tokens):
    """`Family.metrics`, of what the Mamba layers sowed (`Mamba2Mixer`): the
    most negative log-decay of a chunk over layers and heads (how near a
    chunk's exp is to flushing to zero) and the largest carried-state entry
    (what a narrower state would have to hold); nothing of a tree in which
    no layer sowed. Any mixer named `mamba` that sows the two is read
    (models/phi4_flash.py's Mamba-1)."""
    stats = [layer["mamba"] for period in sown.get("ssm_stats", {}).values()
             for layer in period.values()]  # the mamba layers alone sow
    if not stats:
        return {}
    return {"ssm_chunk_log_decay_min": jnp.min(jnp.stack(
                [s["chunk_log_decay_min"][0] for s in stats])),
            "ssm_state_abs_max": jnp.max(jnp.stack(
                [s["state_abs_max"][0] for s in stats]))}


def pairs_apart(x):
    """(..., 2 n) read as n adjacent pairs -> (..., 2 n) with the pairs'
    first entries in the first half and their second in the second: the
    order in which the half-split rotation turns each pair (the source's
    `rope_interleave`). Queries and keys take the same order, so their
    products are those of the pairs where they lay."""
    *lead, width = x.shape
    return x.reshape(*lead, width // 2, 2).swapaxes(-1, -2).reshape(*lead, width)


class DenseParts(nn.Module):
    """`nn.Dense(heads * sum(widths), use_bias=False)`'s leaf under the same
    name (`kernel`: the shape, dtype, initialiser and key path are the
    Dense's, so a seed gives the same weights), applied a part at a time. A
    head's columns are its parts side by side, `widths` wide; part i of the
    result is x @ (that part's columns of every head), (..., heads *
    widths[i]). The cut is made on the weight, whose rows are the stream's
    width and not the tokens: each matmul writes the array its reader takes
    and nothing slices, pads or adds a (B, T, .) array, forward or backward.
    `order[i]`, where given, reorders part i's columns within a head (a
    function of (..., width) arrays: `pairs_apart`)."""

    heads: int
    widths: tuple
    dtype: Any

    @nn.compact
    def __call__(self, x, order=None):
        order, per_head = order or {}, sum(self.widths)
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.heads * per_head), jnp.float32)
        by_head = kernel.astype(self.dtype).reshape(-1, self.heads, per_head)
        x, parts, at = x.astype(self.dtype), [], 0
        for i, width in enumerate(self.widths):
            cut = order.get(i, lambda columns: columns)(by_head[..., at:at + width])
            parts.append(jax.lax.dot_general(x, cut.reshape(-1, self.heads * width),
                                             (((x.ndim - 1,), (0,)), ((), ()))))
            at += width
        return parts


class LatentAttention(nn.Module):
    """(B, T, d) -> (B, T, d): attention that reads its keys and values
    through a latent (MLA; HF `modeling_deepseek_v3.py`), H heads of widths
    `nope_dim`, `rope_dim` and `v_dim`, the latent `kv_latent` wide:

        q = W_q h                    d -> H x (nope + rope); a head's q is
                                     [q_nope ; q_pe]
        [c ; k_pe] = W_kva h         d -> latent + rope; k_pe is one key a
                                     token, for all heads, and is not normed
        c <- RMSNorm(c)
        [k_nope ; v] = W_kvb c       latent -> H x (nope + v), a head's
                                     [k_nope ; v]
        rotary on q_pe and k_pe alone, interleaved: the rope_dim entries are
        read as adjacent pairs (put apart, then the half-split rotation),
        theta `rope_theta`, no scaling
        scores of head h: (q_nope_h . k_nope_h + q_pe_h . k_pe) / sqrt(nope + rope),
        causal softmax; o_h = P_h v_h
        out = W_o o                  H x v -> d; no bias anywhere

    This is the expanded form, training's (ops/attention.py's latent pair,
    flash_mla_fwd and flash_mla_bwd_fused on a TPU); the absorbed form
    (scores against the latent itself) is decode's and is not here. No array
    of H keys nope + rope wide is made: the kernels add the two products
    tile by tile. `config` is a KananaConfig or any config with its
    attention's fields. With `rotary` off nothing turns q_pe and k_pe: the
    `rope_dim` entries are plain coordinates, k_pe still one key a token for
    all heads (models/kimi_linear.py, `mla_use_nope`); the kernels are the
    same."""

    config: Any
    rotary: bool = True

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        if cfg.attn_fn is not None:
            raise NotImplementedError("latent attention runs on one device")
        B, T, C = x.shape
        H, nope, rope = cfg.n_head, cfg.nope_dim, cfg.rope_dim
        parts = lambda heads, widths, name: DenseParts(heads, widths, cfg.dtype, name=name)
        # the 64 that the rotary turns leave their matmuls pairs apart: the
        # order is made on the weight's columns, and here alone
        apart = {1: pairs_apart} if self.rotary else {}
        with jax.named_scope("mla.q"):
            q, q_pe = parts(H, (nope, rope), "q_proj")(x, apart)
            q, q_pe = q.reshape(B, T, H, nope), q_pe.reshape(B, T, H, rope)
        with jax.named_scope("mla.kv_a"):
            latent, k_pe = parts(1, (cfg.kv_latent, rope), "kv_a_proj")(x, apart)
        with jax.named_scope("mla.kv_norm"):
            latent = RMSNorm(cfg.rms_eps, name="kv_a_norm")(latent)
        with jax.named_scope("mla.kv_b"):
            k, v = parts(H, (nope, cfg.v_dim), "kv_b_proj")(latent)
            k, v = k.reshape(B, T, H, nope), v.reshape(B, T, H, cfg.v_dim)
        if self.rotary:
            with jax.named_scope("mla.rope"):
                angles = rope_angles(rope, cfg.rope_theta, jnp.arange(T))
                q_pe = apply_rope(q_pe, angles)
                k_pe = apply_rope(k_pe[:, :, None], angles)[:, :, 0]
        with jax.named_scope("attn.core"):
            if cfg.use_flash_attention:
                from ray_tpu.ops.attention import latent_attention
            else:
                from ray_tpu.ops.attention import xla_latent_attention as latent_attention
            y = latent_attention(q, q_pe, k, k_pe, v)
        with jax.named_scope("mla.o"):
            return nn.Dense(C, use_bias=False, dtype=cfg.dtype, name="o_proj")(
                y.reshape(B, T, H * cfg.v_dim))


LATENT_SHARDING_PATTERNS = [
    (r"attn/q_proj/kernel", P("fsdp", "tp")),
    (r"attn/kv_a_proj/kernel", P("fsdp", None)),  # the latent and the shared key stay whole
    (r"attn/kv_b_proj/kernel", P(None, "tp")),
    (r"attn/o_proj/kernel", P("tp", "fsdp")),
]


class SharedExpert(nn.Module):
    """The expert every token passes through beside its routed ones, and of
    their form (ops/moe.py:ExpertForm): a matrix for each of `form.matrices`,
    `shared_dim` wide, and what `form.hidden` makes of their products into
    `down`. SWIGLU's leaves are gate, up and down; RELU2's up and down. With
    `scalar_gate` the result is times sigmoid(w_s . x), one number a token
    from one row of the stream's width (the leaf `token_gate`, (d, 1);
    models/qwen3_next.py's `shared_expert_gate`), the sigmoid and the product
    in float32 under the scope `moe.shared_gate`; its mean is sown into
    "shared_gate" (0.5 at initialisation)."""

    config: Any  # any config with `shared_dim`, `n_embd` and `dtype`
    form: moe.ExpertForm = moe.SWIGLU
    scalar_gate: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = lambda width, name: nn.Dense(width, use_bias=False, dtype=cfg.dtype, name=name)
        products = [checkpoint_name(dense(cfg.shared_dim, name)(x), "shared_up")
                    for name in self.form.matrices]
        y = dense(cfg.n_embd, "down")(self.form.hidden(*products))
        if not self.scalar_gate:
            return y
        with jax.named_scope("moe.shared_gate"):
            open_ = jax.nn.sigmoid(dense(1, "token_gate")(x).astype(jnp.float32))
            self.sow("shared_gate", "mean", open_.mean())
            return (y.astype(jnp.float32) * open_).astype(y.dtype)


SHARED_EXPERT_SHARDING_PATTERNS = [
    (r"shared/(gate|up)/kernel", P("fsdp", "tp")),
    (r"shared/down/kernel", P("tp", "fsdp")),
    (r"shared/token_gate/kernel", P()),
]


def untied_head(module, cfg, x):
    """The logits of the model `module`, whose leaf `lm_head` (d, vocab) this
    makes at its top level: operands in the compute dtype, float32 sums. A
    tied head and an `nn.Dense` head differ from it and stay their family's."""
    head = module.param("lm_head", nn.initializers.lecun_normal(),
                        (cfg.n_embd, cfg.vocab_size), jnp.float32)
    with jax.named_scope("lm_head"):
        return jnp.dot(x, head.astype(cfg.dtype), preferred_element_type=jnp.float32)


UNTIED_HEAD_SHARDING_PATTERNS = [(r"lm_head$", P("fsdp", "tp"))]


def sow_choices(module, choices):
    """What a parameter group hands the harness of its blocks' `choices`
    (each (B, T, top_k), or None of a block with no expert layer): one entry
    in "choices", (expert layers, B, T, top_k) in layer order, and nothing
    from a group with none. The comparison takes one entry a group, matched
    by the first key of its module path (bench/families/__init__.py), so the
    group sows and its layers are told `hand_up_choices`."""
    choices = [chosen for chosen in choices if chosen is not None]
    if choices:
        module.sow("choices", "experts", jnp.stack(choices))
