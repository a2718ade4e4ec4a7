"""Mellum 2 (JetBrains, 2026; the catalog row's config.json): a pre-norm
RMSNorm decoder whose layers come in periods of three `sliding_attention`
and one `full_attention`, grouped queries at 8 to 1 with heads of their own
size, and in every layer a top-8-of-64 SwiGLU expert layer in the MLP's
place, gates renormalised over the chosen, no shared expert, no biases.

What a configuration file may cut (bench/configs/mellum2_12b_l4_ep4.json):
the layers, the vocabulary, and the experts this program holds.
`num_experts` is the count held, experts `first_expert_held` onward of
`num_experts_published`, which is the router's width; a token's gates are
normalised over all its `num_experts_per_tok` choices, and what the experts
held elsewhere would add is left out, here as in the program.

The harness hands `layer` no index, and compiles it once for all layers. The
kind of a layer depends on its position, so the activations carry it:
`embed` returns (x, at) with `at` the position of the layer to come, each
layer returns (x, at + 1), and a layer reads its kind from
`layer_types[at]` as data: the window and the rotary table are selected,
not branched on, so the arithmetic is that of a reference written layer by
layer.
"""

import math

import jax
import jax.numpy as jnp

from bench.families._plain import QUERY_BLOCK, highest, next_token_loss


def build(sizes, compute_dtype):
    from ray_tpu.models.mellum import MellumConfig, YarnScaling

    if sizes["hidden_act"] != "silu" or sizes.get("attention_bias") or \
            sizes.get("tie_word_embeddings") or not sizes["norm_topk_prob"]:
        raise ValueError("models/mellum.py: SwiGLU experts, no biases, untied head, "
                         "gates normalised over the chosen")
    kinds = _kinds(sizes)
    if set(_mlp_kinds(sizes)) != {"sparse"}:
        raise ValueError("models/mellum.py: every MLP is an expert layer")
    rope = sizes["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    if (full["rope_type"], sliding["rope_type"]) != ("yarn", "default") or \
            full["rope_theta"] != sliding["rope_theta"]:
        raise ValueError("models/mellum.py: YaRN in full layers, plain rotary under "
                         "a window, one theta")
    return MellumConfig(
        vocab_size=sizes["vocab_size"], block_size=sizes["max_position_embeddings"],
        n_head=sizes["num_attention_heads"], n_kv_head=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], n_embd=sizes["hidden_size"], layer_types=tuple(kinds),
        sliding_window=sizes["sliding_window"], rope_theta=float(full["rope_theta"]),
        yarn=YarnScaling(
            factor=float(full["factor"]),
            original_max_position_embeddings=full["original_max_position_embeddings"],
            attention_factor=float(full["attention_factor"]),
            beta_fast=float(full["beta_fast"]), beta_slow=float(full["beta_slow"])),
        rms_eps=sizes["rms_norm_eps"], expert_dim=sizes["moe_intermediate_size"],
        num_experts=sizes["num_experts_published"], top_k=sizes["num_experts_per_tok"],
        first_expert=sizes["first_expert_held"], num_held=sizes["num_experts"],
        dtype=jnp.dtype(compute_dtype))


def _kinds(sizes):
    return sizes["layer_types"][: sizes["num_hidden_layers"]]


def _mlp_kinds(sizes):
    return sizes["mlp_layer_types"][: sizes["num_hidden_layers"]]


def matmul_params(sizes):
    """A layer: q and o (d x heads x head_dim), k and v (d x kv heads x
    head_dim), the router (d x experts published), and of the expert
    matrices (3 x d x width each) what a token meets at even routing:
    experts-per-token x held / published of them. Then the untied head. The
    embedding table multiplies nothing. The expert term is the even-routing
    load, not a run's: the rows a run routes here are decided on the device
    (`telemetry/moe_held_share` says what they were)."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    attn = 2 * d * sizes["num_attention_heads"] * hd + 2 * d * sizes["num_key_value_heads"] * hd
    router = d * sizes["num_experts_published"]
    experts = (sizes["num_experts_per_tok"] * sizes["num_experts"]
               / sizes["num_experts_published"] * 3 * d * sizes["moe_intermediate_size"])
    return int(sizes["num_hidden_layers"] * (attn + router + experts)
               + sizes["vocab_size"] * d)


def flops_per_token(sizes, seq_len):
    """6 x matmul parameters + attention 12 x heads x head_dim x the keys a
    query sees on average: T/2 in a full layer, w - w^2/(2T) under a window
    w < T (families/gpt2.py's rule, 6*T*width a causal layer, with a window
    counted for what it needs). At the published widths, 16 of 64 experts, 4
    layers, V = 24,576 and T = 8,192: 6 x (4 x (21.23 M + 0.147 M + 8 x 16/64
    x 6.193 M) + 56.6 M) = 1.150 G, + 12 x 4096 x (4096 + 3 x 960) = 0.343 G."""
    keys = 0.0
    for kind in _kinds(sizes):
        w = sizes["sliding_window"] if kind == "sliding_attention" else seq_len
        keys += seq_len / 2 if w >= seq_len else w - w * w / (2 * seq_len)
    width = sizes["num_attention_heads"] * sizes["head_dim"]
    return int(6 * matmul_params(sizes) + 12 * width * keys)


def layer_names(sizes):
    return [f"h_{i}" for i in range(sizes["num_hidden_layers"])]


# What each matmul does to an operand before it multiplies: nothing. The
# control of bench/tests/mellum_control.py puts a rounding to a lower
# precision here, to show that the comparison refuses it.
OPERAND = None


def _mm(a, b):
    return a @ b if OPERAND is None else OPERAND(a) @ OPERAND(b)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * weight


def _yarn_inv_freq(dim, rope):
    """transformers' `_compute_yarn_parameters`, `truncate` at its default."""
    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    pos_freq = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    extrapolation, interpolation = 1.0 / pos_freq, 1.0 / (factor * pos_freq)

    def correction(rotations):
        return (dim * math.log(rope["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(correction(rope["beta_fast"])), 0)
    high = min(math.ceil(correction(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return interpolation * ramp + extrapolation * (1 - ramp)


def _kind(at, sizes):
    """(is this a full_attention layer, its window, its rotary frequencies
    and the factor on cos and sin), selected by the position `at` carries."""
    full = jnp.asarray([k == "full_attention" for k in _kinds(sizes)])[
        at.reshape(-1)[0].astype(jnp.int32)]
    rope, dim = sizes["rope_parameters"], sizes["head_dim"]
    plain = 1.0 / (float(rope["sliding_attention"]["rope_theta"])
                   ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    inv_freq = jnp.where(full, _yarn_inv_freq(dim, rope["full_attention"]), plain)
    factor = jnp.where(full, float(rope["full_attention"]["attention_factor"]), 1.0)
    window = jnp.where(full, jnp.iinfo(jnp.int32).max, sizes["sliding_window"])
    return window, inv_freq, factor


def _rope(x, inv_freq, factor):
    """x (B, T, H, D): rotate pairs (i, i + D/2) by pos * inv_freq_i; cos and
    sin both times `factor`."""
    T, D = x.shape[1], x.shape[-1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def windowed_attention(q, k, v, window):
    """q (B, T, H, D), k and v (B, T, G, D), head h reads key-value head
    h // (H/G); query i sees keys j <= i with i - j < window. In blocks of
    queries as _plain.causal_attention: a block sees every key, so its
    softmax is whole and the blocks change no arithmetic."""
    B, T, H, D = q.shape
    G = k.shape[2]
    if OPERAND is not None:
        q, k, v = OPERAND(q), OPERAND(k), OPERAND(v)
    q = q.reshape(B, T, G, H // G, D)
    key_pos = jnp.arange(T)

    def block(q_blk, start):
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q_blk, k) / math.sqrt(D)
        ahead = (start + jnp.arange(q_blk.shape[1]))[:, None] - key_pos[None, :]
        s = jnp.where((ahead >= 0) & (ahead < window), s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p if OPERAND is None else OPERAND(p), v)

    if T <= QUERY_BLOCK:
        out = block(q, 0)
    else:
        n = T // QUERY_BLOCK
        if n * QUERY_BLOCK != T:
            raise ValueError(f"sequence {T} is not a multiple of {QUERY_BLOCK}")
        blocks = q.reshape(B, n, QUERY_BLOCK, G, H // G, D).swapaxes(0, 1)
        out = jax.lax.map(
            lambda xs: jax.checkpoint(block)(xs[0], xs[1]),
            (blocks, jnp.arange(n) * QUERY_BLOCK))
        out = out.swapaxes(0, 1).reshape(B, T, G, H // G, D)
    return out.reshape(B, T, H, D)


def _attend(x, blk, sizes, at):
    B, T, _ = x.shape
    H, G, D = (sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"])
    window, inv_freq, factor = _kind(at, sizes)
    h = _rms_norm(x, blk["attn_norm"]["weight"], sizes["rms_norm_eps"])
    q = _rope(_mm(h, blk["attn"]["wq"]["kernel"]).reshape(B, T, H, D), inv_freq, factor)
    k = _rope(_mm(h, blk["attn"]["wk"]["kernel"]).reshape(B, T, G, D), inv_freq, factor)
    v = _mm(h, blk["attn"]["wv"]["kernel"]).reshape(B, T, G, D)
    return x + _mm(windowed_attention(q, k, v, window).reshape(B, T, H * D),
                   blk["attn"]["wo"]["kernel"])


def _route(x, blk, sizes):
    """(the expert layer's input, every expert's probability) of a token."""
    h = _rms_norm(x, blk["moe_norm"]["weight"], sizes["rms_norm_eps"])
    return h, jax.nn.softmax(_mm(h, blk["moe"]["router"]["kernel"]), axis=-1)


@highest
def choice(x, blk, sizes):
    x, at = x
    _, probs = _route(_attend(x, blk, sizes, at), blk, sizes)
    return jax.lax.top_k(probs, sizes["num_experts_per_tok"])[1]


def layer(x, blk, sizes, choice=None):
    return _layer(x, blk, sizes, choice)


@highest
def _layer(x, blk, sizes, choice):
    x, at = x
    x = _attend(x, blk, sizes, at)
    h, probs = _route(x, blk, sizes)
    if choice is None:
        choice = jax.lax.top_k(probs, sizes["num_experts_per_tok"])[1]
    chosen = jnp.take_along_axis(probs, choice, axis=-1)
    gates = chosen / chosen.sum(-1, keepdims=True)  # over all chosen, held or not
    moe = blk["moe"]

    def one_expert(y, e):
        # every token through expert e, weighted by the gate of the tokens
        # that chose it and by zero for the rest
        weight = jnp.where(choice == sizes["first_expert_held"] + e, gates, 0.0).sum(-1)
        out = _mm(jax.nn.silu(_mm(h, moe["gate"][e])) * _mm(h, moe["up"][e]), moe["down"][e])
        return y + weight[..., None] * out, None

    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                        jnp.arange(sizes["num_experts"]))
    return (x + y, at + 1), 0.0  # (what goes on to the next layer, no loss of its own)


@highest
def embed(outer, idx, sizes):
    # with the position of the layer to come, one a row so that it is split
    # over the batch as the activations are
    return outer["tok_emb"]["embedding"][idx], jnp.zeros((idx.shape[0], 1, 1), jnp.float32)


@highest
def head_loss(outer, x, targets, sizes):
    x = _rms_norm(x[0], outer["final_norm"]["weight"], sizes["rms_norm_eps"])
    return next_token_loss(_mm(x, outer["lm_head"]["kernel"]), targets)
