"""The RMSNorm / rotary / grouped-query / SwiGLU decoder (Touvron et al.
2023; Mistral 7B, Jiang et al. 2023): no biases, untied head, rotate-half
rotary embedding as in the published reference code."""

import jax
import jax.numpy as jnp

from bench.families._plain import causal_attention, highest, next_token_loss


def build(sizes, compute_dtype):
    from ray_tpu.models.llama import LlamaConfig

    if sizes["head_dim"] * sizes["num_attention_heads"] != sizes["hidden_size"]:
        raise ValueError("models/llama.py takes head_dim = hidden_size / heads only")
    if sizes.get("sliding_window") is not None or sizes.get("tie_word_embeddings"):
        raise ValueError("models/llama.py has no sliding window and no tied head")
    return LlamaConfig(
        vocab_size=sizes["vocab_size"], block_size=sizes["max_position_embeddings"],
        n_layer=sizes["num_hidden_layers"], n_head=sizes["num_attention_heads"],
        n_kv_head=sizes["num_key_value_heads"], n_embd=sizes["hidden_size"],
        intermediate=sizes["intermediate_size"], rope_theta=sizes["rope_theta"],
        rms_eps=sizes["rms_norm_eps"], dtype=jnp.dtype(compute_dtype))


def matmul_params(sizes):
    """q and o (d x d), k and v (d x kv heads x head_dim), gate, up and down
    (d x intermediate), and the untied head. The embedding table multiplies
    nothing."""
    d, L, V = sizes["hidden_size"], sizes["num_hidden_layers"], sizes["vocab_size"]
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]
    return L * (2 * d * d + 2 * d * kv + 3 * d * sizes["intermediate_size"]) + V * d


def flops_per_token(sizes, seq_len):
    """6 x matmul parameters + causal attention 6*L*T*n_head*head_dim (see
    families/gpt2.py); grouped queries save memory, not operations."""
    attn_width = sizes["num_attention_heads"] * sizes["head_dim"]
    return (6 * matmul_params(sizes)
            + 6 * sizes["num_hidden_layers"] * seq_len * attn_width)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * weight


def _rope(x, theta):
    """x (B, T, H, D): rotate pairs (i, i + D/2) by pos * theta^(-2i/D)."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@highest
def layer(x, blk, sizes):
    B, T, _ = x.shape
    H, G, D = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
               sizes["head_dim"])
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    h = _rms_norm(x, blk["attn_norm"]["weight"], eps)
    q = _rope((h @ blk["attn"]["wq"]["kernel"]).reshape(B, T, H, D), theta)
    k = _rope((h @ blk["attn"]["wk"]["kernel"]).reshape(B, T, G, D), theta)
    v = (h @ blk["attn"]["wv"]["kernel"]).reshape(B, T, G, D)
    x = x + causal_attention(q, k, v).reshape(B, T, H * D) @ blk["attn"]["wo"]["kernel"]
    h = _rms_norm(x, blk["mlp_norm"]["weight"], eps)
    mlp = blk["mlp"]
    return x + (jax.nn.silu(h @ mlp["gate"]["kernel"]) * (h @ mlp["up"]["kernel"])
                ) @ mlp["down"]["kernel"]


def layer_names(sizes):
    return [f"h_{i}" for i in range(sizes["num_hidden_layers"])]


@highest
def embed(outer, idx, sizes):
    return outer["tok_emb"]["embedding"][idx]


@highest
def head_loss(outer, x, targets, sizes):
    x = _rms_norm(x, outer["final_norm"]["weight"], sizes["rms_norm_eps"])
    return next_token_loss(x @ outer["lm_head"]["kernel"], targets)
