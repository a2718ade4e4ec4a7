"""LFM2-8B-A1B (LiquidAI; `model_type` lfm2_moe, HF `modeling_lfm2_moe.py`): a
pre-norm RMSNorm decoder whose layers mix along time with a gated short
convolution or with attention, three to one, whose first `num_dense_layers`
MLPs are dense and the others routed, with a tied head. The equations, d the
hidden size, E the embedding, x the stream:

    x0 = E[idx]
    a layer:  x <- x + op(RMSNorm(x));  x <- x + ffn(RMSNorm(x))
    logits = E RMSNorm(x);  loss = mean cross-entropy

    conv:       [B | C | u] = W_in h
                y_t = C_t * sum_{j=0..k-1} w_j * (B * u)_{t-(k-1)+j}    k taps,
                depthwise, causal, zeros before a row's first token, no bias
                out = W_out y
    attention:  q, k, v, o without bias; RMSNorm over the 64 of each q and k
                head; rotary over the whole head, half-split, theta 1e6;
                causal; 32 query heads on 8 key-value heads
    dense ffn:  W_down (silu(W_gate h) * W_up h)
    routed ffn: s = sigmoid(W_r h); the 4 experts of a token are the top 4 of
                s + b; its gates s at those four, over their sum + 1e-6, times
                routed_scaling_factor; each expert a SwiGLU

What a configuration file may cut (bench/configs/lfm2_8b_a1b_l5_ep4.json): the
layers (`layer_types` as run, the leading dense layers counted once), the
vocabulary, and the experts this program holds: `num_experts` is the count
held, experts `first_expert_held` onward of `num_experts_published`, which is
the router's width; a token's gates are normalised over all its choices, and
what the experts held elsewhere would add is left out, here as in the program.

A *layer* of this family, as the harness takes gradients, is all the blocks
(the program groups them so: `p_0` with `h_0` ..): the harness asks every layer
for one structure of parameters and for one choice, and this model has three
unlike blocks and four routed ones. Inside, each block is under
jax.checkpoint. The group's choice is its routed blocks' stacked, (routed
blocks, rows, T, k): `choice` runs the blocks by the reference's own choices,
so each block's choice is made on the stream the reference's own side made.
"""

import math

import jax
import jax.numpy as jnp

from bench.families._plain import QUERY_BLOCK, highest, next_token_loss

# The control of bench/tests/lfm2_control.py puts a rounding to a lower
# precision here: every matmul's operands and the convolution's products go
# through it. None in every other use.
OPERAND = None


def _mm(a, b):
    return a @ b if OPERAND is None else OPERAND(a) @ OPERAND(b)


def _times(a, b):
    return a * b if OPERAND is None else OPERAND(a) * OPERAND(b)


def build(sizes, compute_dtype):
    from ray_tpu.models.lfm2 import Lfm2Config

    if (sizes["conv_bias"] or not sizes["norm_topk_prob"] or not sizes["use_expert_bias"]
            or not sizes["tie_word_embeddings"]
            or len(sizes["layer_types"]) != sizes["num_hidden_layers"]
            or sizes["hidden_size"] % sizes["num_attention_heads"]):
        raise ValueError("models/lfm2.py: no bias in the convolution, gates normalised over "
                         "the chosen, selection under a bias, a tied head")
    return Lfm2Config(
        vocab_size=sizes["vocab_size"], block_size=sizes["max_position_embeddings"],
        n_embd=sizes["hidden_size"], layer_types=tuple(sizes["layer_types"]),
        num_dense_layers=sizes["num_dense_layers"], n_head=sizes["num_attention_heads"],
        n_kv_head=sizes["num_key_value_heads"], intermediate=sizes["intermediate_size"],
        conv_taps=sizes["conv_L_cache"], expert_dim=sizes["moe_intermediate_size"],
        num_experts=sizes["num_experts_published"], top_k=sizes["num_experts_per_tok"],
        first_expert=sizes["first_expert_held"], num_held=sizes["num_experts"],
        routed_scaling=float(sizes["routed_scaling_factor"]),
        rope_theta=float(sizes["rope_theta"]), rms_eps=sizes["norm_eps"],
        dtype=jnp.dtype(compute_dtype))


def _blocks(sizes):
    """(kind, whether its MLP is routed) of each block."""
    return [(kind, i >= sizes["num_dense_layers"]) for i, kind in enumerate(sizes["layer_types"])]


def _head_width(sizes):
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def matmul_params(sizes):
    """A block's operator: W_in (d x 3 d) and W_out (d x d), or q and o (d x
    d), k and v (d x kv heads x head width). Its MLP: three matrices d x
    intermediate_size, or the router (d x experts published) and of the
    expert matrices (3 x d x width each) what a token meets at even routing:
    experts-per-token x held / published of them (families/mellum.py's rule).
    The tied matrix once, as the head: the embedding is a look-up. The taps
    and the norms multiply element by element and are left out."""
    d = sizes["hidden_size"]
    kv = sizes["num_key_value_heads"] * _head_width(sizes)
    operator = {"conv": 4 * d * d, "full_attention": 2 * d * d + 2 * d * kv}
    experts = (sizes["num_experts_per_tok"] * sizes["num_experts"]
               / sizes["num_experts_published"] * 3 * d * sizes["moe_intermediate_size"])
    routed = d * sizes["num_experts_published"] + experts
    return int(sum(operator[kind] + (routed if is_routed else 3 * d * sizes["intermediate_size"])
                   for kind, is_routed in _blocks(sizes)) + sizes["vocab_size"] * d)


def flops_per_token(sizes, seq_len):
    """6 x matmul parameters + the causal term of the attention layers, 12 x
    heads x head width x T/2 each (families/gpt2.py's rule). At the published
    widths, 8 of 32 experts, the five layers and V = 16,384: 6 x (60,817,408 +
    21,561,344 + 3 x 27,852,800 + 33,554,432) = 1.197 G, + 12 x 2,048 x 4,096
    = 0.101 G at T = 8,192. The convolution's 2 k + 2 operations a channel are
    not counted: its work is its bytes."""
    attention = sizes["layer_types"].count("full_attention")
    return int(6 * matmul_params(sizes) + 6 * attention * seq_len * sizes["hidden_size"])


def layer_names(sizes):
    return ["p_0"]


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * weight


def _rope(x, theta):
    """x (B, T, H, D): rotate pairs (i, i + D/2) by pos * theta^(-2i/D)."""
    T, D = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_attention(q, k, v):
    """_plain.causal_attention with the control's rounding on the operands
    of its two matmuls: q (B, T, H, D), k and v (B, T, G, D), head h reads
    key-value head h // (H/G). In blocks of queries: a block sees every key,
    so its softmax is whole and the blocks change no arithmetic."""
    B, T, H, D = q.shape
    G = k.shape[2]
    if OPERAND is not None:
        q, k, v = OPERAND(q), OPERAND(k), OPERAND(v)
    q = q.reshape(B, T, G, H // G, D)
    key_pos = jnp.arange(T)

    def block(q_blk, start):
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q_blk, k) / math.sqrt(D)
        q_pos = start + jnp.arange(q_blk.shape[1])
        s = jnp.where(q_pos[:, None] >= key_pos[None, :], s, -jnp.inf)
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


def _attention(h, a, sizes):
    B, T, d = h.shape
    H, G, D = sizes["num_attention_heads"], sizes["num_key_value_heads"], _head_width(sizes)
    eps, theta = sizes["norm_eps"], float(sizes["rope_theta"])
    q = _rms_norm(_mm(h, a["wq"]["kernel"]).reshape(B, T, H, D), a["q_norm"]["weight"], eps)
    k = _rms_norm(_mm(h, a["wk"]["kernel"]).reshape(B, T, G, D), a["k_norm"]["weight"], eps)
    v = _mm(h, a["wv"]["kernel"]).reshape(B, T, G, D)
    return _mm(causal_attention(_rope(q, theta), _rope(k, theta), v).reshape(B, T, H * D),
               a["wo"]["kernel"])


def gated_conv(b, c, u, taps):
    """y_t = C_t * sum_j w_j (B u)_{t-(k-1)+j}: one shifted slice a tap, zeros
    before the first token. b, c, u (B, T, d), taps (k, d)."""
    k, T = taps.shape[0], b.shape[1]
    z = jnp.pad(_times(b, u), ((0, 0), (k - 1, 0), (0, 0)))
    return _times(c, sum(_times(taps[j], z[:, j:j + T]) for j in range(k)))


def _conv(h, m, sizes):
    b, c, u = jnp.split(_mm(h, m["in_proj"]["kernel"]), 3, axis=-1)
    return _mm(gated_conv(b, c, u, m["conv_kernel"]), m["out_proj"]["kernel"])


def _dense_mlp(h, mlp):
    return _mm(jax.nn.silu(_mm(h, mlp["gate"]["kernel"])) * _mm(h, mlp["up"]["kernel"]),
               mlp["down"]["kernel"])


def _routed_mlp(h, moe, sizes, choice):
    """(the held experts' part of the layer, the choice it used)."""
    scores = jax.nn.sigmoid(_mm(h, moe["router"]["kernel"]))
    if choice is None:
        choice = jax.lax.top_k(scores + moe["expert_bias"], sizes["num_experts_per_tok"])[1]
    chosen = jnp.take_along_axis(scores, choice, axis=-1)
    # over all chosen, held or not
    gates = chosen / (chosen.sum(-1, keepdims=True) + 1e-6) * sizes["routed_scaling_factor"]

    def one_expert(y, e):
        # every token through expert e, weighted by the gate of the tokens
        # that chose it and by zero for the rest
        weight = jnp.where(choice == sizes["first_expert_held"] + e, gates, 0.0).sum(-1)
        out = _mm(jax.nn.silu(_mm(h, moe["gate"][e])) * _mm(h, moe["up"][e]), moe["down"][e])
        return y + weight[..., None] * out, None

    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(h),
                        jnp.arange(sizes["num_experts"]))
    return y, choice


def block(x, blk, sizes, choice=None):
    """One block, its kinds told by the parameters it is handed: (x, the
    choice its expert layer used, or None where its MLP is dense)."""
    eps = sizes["norm_eps"]
    h = _rms_norm(x, blk["operator_norm"]["weight"], eps)
    x = x + (_conv(h, blk["conv"], sizes) if "conv" in blk else _attention(h, blk["attn"], sizes))
    h = _rms_norm(x, blk["ffn_norm"]["weight"], eps)
    if "mlp" in blk:
        return x + _dense_mlp(h, blk["mlp"]), None
    y, choice = _routed_mlp(h, blk["moe"], sizes, choice)
    return x + y, choice


@highest
def _run(x, group, sizes, choice):
    """The blocks in order; (x, the routed blocks' choices stacked). With a
    `choice` given, its i-th entry is the i-th routed block's."""
    used = []
    for i in range(len(group)):
        blk = group[f"h_{i}"]
        given = None if choice is None or "moe" not in blk else choice[len(used)]
        x, chosen = jax.checkpoint(lambda x, blk, given: block(x, blk, sizes, given))(
            x, blk, given)
        if chosen is not None:
            used.append(chosen)
    return x, jnp.stack(used)


def layer(x, group, sizes, choice=None):
    return _run(x, group, sizes, choice)[0]


def choice(x, group, sizes):
    return _run(x, group, sizes, None)[1]


@highest
def embed(outer, idx, sizes):
    return outer["tok_emb"]["embedding"][idx]


@highest
def head_loss(outer, x, targets, sizes):
    x = _rms_norm(x, outer["final_norm"]["weight"], sizes["norm_eps"])
    return next_token_loss(_mm(x, outer["tok_emb"]["embedding"].T), targets)
