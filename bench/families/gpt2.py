"""GPT-2 (Radford et al. 2019; openai-community/gpt2): learned positions,
pre-LayerNorm blocks, fused qkv with biases, tanh-GELU MLP of 4d, tied head."""

import math

import jax
import jax.numpy as jnp

from bench.families._plain import causal_attention, highest, next_token_loss


def build(sizes, compute_dtype):
    from ray_tpu.models.gpt2 import GPT2Config

    if sizes.get("n_inner") not in (None, 4 * sizes["n_embd"]):
        raise ValueError("models/gpt2.py has no setting for n_inner != 4*n_embd")
    return GPT2Config(
        vocab_size=sizes["vocab_size"], block_size=sizes["n_positions"],
        n_layer=sizes["n_layer"], n_head=sizes["n_head"], n_embd=sizes["n_embd"],
        dtype=jnp.dtype(compute_dtype))


def matmul_params(sizes):
    """qkv, attention projection, the two MLP matrices, and the head. The
    embedding look-ups (wte as a table, wpe) multiply nothing; the tied head
    does, once."""
    d, L, V = sizes["n_embd"], sizes["n_layer"], sizes["vocab_size"]
    return L * (3 * d * d + d * d + 4 * d * d + 4 * d * d) + V * d


def flops_per_token(sizes, seq_len):
    """6 x matmul parameters (2 forward, 4 backward) + causal attention:
    QK^T and PV are 2*T*d each a token over all heads, half of it under the
    causal mask, three times for forward and backward: 6*L*T*d. Recomputed
    operations (remat, the kernels' own recompute) are not counted."""
    d, L = sizes["n_embd"], sizes["n_layer"]
    return 6 * matmul_params(sizes) + 6 * L * seq_len * d


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def layer_names(sizes):
    return [f"h_{i}" for i in range(sizes["n_layer"])]


@highest
def embed(outer, idx, sizes):
    return outer["wte"]["embedding"][idx] + outer["wpe"]["embedding"][: idx.shape[1]]


@highest
def layer(x, blk, sizes):
    eps, H = sizes["layer_norm_epsilon"], sizes["n_head"]
    B, T, _ = x.shape
    q, k, v = jnp.split(
        _dense(_layer_norm(x, blk["ln_1"], eps), blk["attn"]["c_attn"]), 3, -1)
    heads = lambda a: a.reshape(B, T, H, -1)
    y = causal_attention(heads(q), heads(k), heads(v)).reshape(B, T, -1)
    x = x + _dense(y, blk["attn"]["c_proj"])
    h = _gelu_new(_dense(_layer_norm(x, blk["ln_2"], eps), blk["mlp"]["c_fc"]))
    return x + _dense(h, blk["mlp"]["c_proj"])


@highest
def head_loss(outer, x, targets, sizes):
    x = _layer_norm(x, outer["ln_f"], sizes["layer_norm_epsilon"])
    return next_token_loss(x @ outer["wte"]["embedding"].T, targets)
