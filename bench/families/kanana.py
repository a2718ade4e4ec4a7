"""kanana-2-30b-a3b-instruct-2601 (kakaocorp; `model_type` deepseek_v3, HF
`modeling_deepseek_v3.py`): a pre-norm RMSNorm decoder whose every attention
layer is latent (MLA), whose first `first_k_dense_replace` MLPs are dense and
the others routed with a shared expert beside the routed ones, with an untied
head. The equations, d the hidden size, H heads, x the stream:

    x0 = E[idx]
    a layer:  x <- x + attn(RMSNorm(x));  x <- x + ffn(RMSNorm(x))
    logits = W_head RMSNorm(x);  loss = mean cross-entropy

    attention:  q = W_q h, a head's 192 split [q_nope (128) ; q_pe (64)]
                [c ; k_pe] = W_kva h (512 + 64); c <- RMSNorm(c); k_pe is one
                key a token for all heads and is not normed
                [k_nope ; v] = W_kvb c, a head's 256 split (128, 128)
                rotary on q_pe and k_pe alone, interleaved (adjacent pairs),
                theta 1e6, no scaling
                k = [k_nope ; k_pe repeated to every head]: the published
                expanded form; scores q . k / sqrt(192), causal softmax,
                o = P v (128 wide), out = W_o o; no bias
    dense ffn:  W_down (silu(W_gate h) * W_up h)
    routed ffn: s = sigmoid(W_r h); the 6 experts of a token are the top 6 of
                s + b; its gates s at those six, over their sum + 1e-20, times
                routed_scaling_factor; each expert a SwiGLU;
                + the shared expert, one SwiGLU n_shared_experts x
                moe_intermediate_size wide, for every token

What a configuration file may cut (bench/configs/kanana2_30b_l5_ep8.json): the
layers, the vocabulary, and the experts this program holds: `n_routed_experts`
is the count held, experts `first_expert_held` onward of
`n_routed_experts_published`, which is the router's width; a token's gates are
normalised over all its choices, and what the experts held elsewhere would add
is left out, here as in the program. The shared expert is whole on every chip.

A *layer* of this family, as the harness takes gradients, is all the blocks
(`p_0` with `h_0` ..), as families/lfm2.py says; the group's choice is its
routed blocks' stacked, (routed blocks, rows, T, k).
"""

import math

import jax
import jax.numpy as jnp

from bench.families._plain import QUERY_BLOCK, highest, next_token_loss

# The control of bench/tests/kanana_control.py puts a rounding to a lower
# precision here: every matmul's operands go through it. None in every other
# use.
OPERAND = None


def _mm(a, b):
    return a @ b if OPERAND is None else OPERAND(a) @ OPERAND(b)


def build(sizes, compute_dtype):
    from ray_tpu.models.kanana import KananaConfig

    if (sizes["attention_bias"] or sizes["q_lora_rank"] is not None
            or sizes["rope_scaling"] is not None or not sizes["rope_interleave"]
            or sizes["scoring_func"] != "sigmoid" or sizes["topk_method"] != "noaux_tc"
            or sizes["n_group"] != 1 or sizes["topk_group"] != 1 or not sizes["norm_topk_prob"]
            or sizes["moe_layer_freq"] != 1 or sizes["tie_word_embeddings"]
            or sizes["qk_head_dim"] != sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]):
        raise ValueError(
            "models/kanana.py: no bias, no query latent, no rotary scaling, interleaved rotary, "
            "sigmoid scores under a selection bias in one group, gates normalised over the "
            "chosen, every layer after the dense ones routed, an untied head")
    return KananaConfig(
        vocab_size=sizes["vocab_size"], block_size=sizes["max_position_embeddings"],
        n_embd=sizes["hidden_size"], n_layer=sizes["num_hidden_layers"],
        num_dense_layers=sizes["first_k_dense_replace"], n_head=sizes["num_attention_heads"],
        kv_latent=sizes["kv_lora_rank"], nope_dim=sizes["qk_nope_head_dim"],
        rope_dim=sizes["qk_rope_head_dim"], v_dim=sizes["v_head_dim"],
        intermediate=sizes["intermediate_size"], expert_dim=sizes["moe_intermediate_size"],
        num_experts=sizes["n_routed_experts_published"], top_k=sizes["num_experts_per_tok"],
        first_expert=sizes["first_expert_held"], num_held=sizes["n_routed_experts"],
        shared_experts=sizes["n_shared_experts"],
        routed_scaling=float(sizes["routed_scaling_factor"]),
        rope_theta=float(sizes["rope_theta"]), rms_eps=sizes["rms_norm_eps"],
        dtype=jnp.dtype(compute_dtype))


def _routed_layers(sizes):
    return sizes["num_hidden_layers"] - sizes["first_k_dense_replace"]


def matmul_params(sizes):
    """A block's attention: W_q (d x H x 192), W_kva (d x 576), W_kvb (512 x
    H x 256), W_o (H x 128 x d). Its MLP: three matrices d x
    intermediate_size, or the router (d x experts published), the shared
    expert whole (3 x d x n_shared x width) and of the routed experts'
    matrices what a token meets at even routing: experts-per-token x held /
    published of them (families/mellum.py's rule). The head once: the
    embedding is a look-up."""
    d, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope, v = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    latent, width = sizes["kv_lora_rank"], sizes["moe_intermediate_size"]
    attention = (d * heads * (nope + rope) + d * (latent + rope)
                 + latent * heads * (nope + v) + heads * v * d)
    experts = (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
               / sizes["n_routed_experts_published"] * 3 * d * width)
    routed = (d * sizes["n_routed_experts_published"]
              + 3 * d * sizes["n_shared_experts"] * width + experts)
    return int(sizes["num_hidden_layers"] * attention
               + sizes["first_k_dense_replace"] * 3 * d * sizes["intermediate_size"]
               + _routed_layers(sizes) * routed + sizes["vocab_size"] * d)


def flops_per_token(sizes, seq_len):
    """6 x matmul parameters + the causal term, which for a score 192 deep
    and a value 128 wide is 6 x T x H x (192 + 128) / 2 a layer
    (families/gpt2.py's rule, where both widths are the head's one). At the
    published widths, 16 of 128 experts, five layers and V = 16,032: 6 x
    255.26 M = 1.532 G, + 5 x 251.7 M = 1.258 G at T = 8,192."""
    core = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"] + sizes["v_head_dim"]
    return int(6 * matmul_params(sizes)
               + 3 * sizes["num_hidden_layers"] * seq_len * sizes["num_attention_heads"] * core)


def layer_names(sizes):
    return ["p_0"]


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * weight


def _rope_pairs(x, theta):
    """x (B, T, H, D): pair i is entries (2 i, 2 i + 1), rotated by
    pos * theta^(-2i/D); the turned pairs come out first entries, then second
    entries (the source's order after `rope_interleave`: queries and keys
    alike, so their products are the pairs')."""
    T, D = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_attention(q, k, v):
    """q, k (B, T, H, Dk), v (B, T, H, Dv) -> (B, T, H, Dv), scores over
    sqrt(Dk), with the control's rounding on the operands of its two matmuls.
    In blocks of queries: a block sees every key, so its softmax is whole
    and the blocks change no arithmetic (_plain.causal_attention's, which
    takes one width)."""
    B, T, H, D = q.shape
    if OPERAND is not None:
        q, k, v = OPERAND(q), OPERAND(k), OPERAND(v)
    key_pos = jnp.arange(T)

    def block(q_blk, start):
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / math.sqrt(D)
        q_pos = start + jnp.arange(q_blk.shape[1])
        s = jnp.where(q_pos[:, None] >= key_pos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p if OPERAND is None else OPERAND(p), v)

    if T <= QUERY_BLOCK:
        return block(q, 0)
    n = T // QUERY_BLOCK
    if n * QUERY_BLOCK != T:
        raise ValueError(f"sequence {T} is not a multiple of {QUERY_BLOCK}")
    blocks = q.reshape(B, n, QUERY_BLOCK, H, D).swapaxes(0, 1)
    out = jax.lax.map(lambda xs: jax.checkpoint(block)(xs[0], xs[1]),
                      (blocks, jnp.arange(n) * QUERY_BLOCK))
    return out.swapaxes(0, 1).reshape(B, T, H, v.shape[-1])


def _attention(h, a, sizes):
    B, T, _ = h.shape
    H, nope, rope = (sizes["num_attention_heads"], sizes["qk_nope_head_dim"],
                     sizes["qk_rope_head_dim"])
    latent, theta = sizes["kv_lora_rank"], float(sizes["rope_theta"])
    q = _mm(h, a["q_proj"]["kernel"]).reshape(B, T, H, nope + rope)
    ckv = _mm(h, a["kv_a_proj"]["kernel"])
    c = _rms_norm(ckv[..., :latent], a["kv_a_norm"]["weight"], sizes["rms_norm_eps"])
    kv = _mm(c, a["kv_b_proj"]["kernel"]).reshape(B, T, H, nope + sizes["v_head_dim"])
    q_pe = _rope_pairs(q[..., nope:], theta)
    k_pe = _rope_pairs(ckv[..., None, latent:], theta)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (B, T, H, rope))], -1)
    out = causal_attention(q, k, kv[..., nope:])
    return _mm(out.reshape(B, T, -1), a["o_proj"]["kernel"])


def _swiglu(h, mlp):
    return _mm(jax.nn.silu(_mm(h, mlp["gate"]["kernel"])) * _mm(h, mlp["up"]["kernel"]),
               mlp["down"]["kernel"])


def _routed_mlp(h, moe, sizes, choice):
    """(the held experts' part of the layer, the choice it used)."""
    scores = jax.nn.sigmoid(_mm(h, moe["router"]["kernel"]))
    if choice is None:
        choice = jax.lax.top_k(scores + moe["expert_bias"], sizes["num_experts_per_tok"])[1]
    chosen = jnp.take_along_axis(scores, choice, axis=-1)
    # over all chosen, held or not
    gates = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * sizes["routed_scaling_factor"]

    def one_expert(y, e):
        # every token through expert e, weighted by the gate of the tokens
        # that chose it and by zero for the rest
        weight = jnp.where(choice == sizes["first_expert_held"] + e, gates, 0.0).sum(-1)
        out = _mm(jax.nn.silu(_mm(h, moe["gate"][e])) * _mm(h, moe["up"][e]), moe["down"][e])
        return y + weight[..., None] * out, None

    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(h),
                        jnp.arange(sizes["n_routed_experts"]))
    return y, choice


def block(x, blk, sizes, choice=None):
    """One block, its MLP's kind told by the parameters it is handed: (x, the
    choice its expert layer used, or None where its MLP is dense)."""
    eps = sizes["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, blk["attn_norm"]["weight"], eps), blk["attn"], sizes)
    h = _rms_norm(x, blk["mlp_norm"]["weight"], eps)
    if "mlp" in blk:
        return x + _swiglu(h, blk["mlp"]), None
    y, choice = _routed_mlp(h, blk["moe"], sizes, choice)
    return x + y + _swiglu(h, blk["shared"]), choice


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
    x = _rms_norm(x, outer["final_norm"]["weight"], sizes["rms_norm_eps"])
    return next_token_loss(_mm(x, outer["lm_head"]), targets)
