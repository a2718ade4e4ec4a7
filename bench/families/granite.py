"""Granite 4.0-H (ibm-granite/granite-4.0-h-micro; `model_type`
granitemoehybrid with no experts): a decoder whose layers are Mamba-2 mixers
(Dao & Gu 2024) or attention without any positional encoding, nine to one,
each followed by the same SwiGLU MLP, with four scalar multipliers and a tied
head. The equations, d the hidden size, E the embedding, x the stream:

    x0 = embedding_multiplier * E[idx]
    a layer:  x <- x + residual_multiplier * mixer(RMSNorm(x))
              x <- x + residual_multiplier * MLP(RMSNorm(x))
              MLP(u) = W_down (silu(W_gate u) * W_up u)
    logits = E RMSNorm(x) / logits_scaling;  loss = mean cross-entropy

    attention:  32 query and 8 key/value heads of d / 32, no bias, no rotary
                (`position_embedding_type` nope), causal,
                softmax(attention_multiplier * q k^T) v
    mamba:      [z | xBC | dt] = W_in u
                xBC <- silu(conv(xBC))   depthwise, causal, K taps, with bias:
                                         out_t = bias + sum_i w_i in_{t-(K-1)+i}
                x (T, H, P), B (T, G, N), C (T, G, N) = split(xBC)
                Delta = softplus(dt + dt_bias);  A = -exp(A_log)
                S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T   (P x N a head)
                y_t = S_t C_t + D x_t
                out = W_out RMSNorm(y * silu(z))     the gate before the norm,
                                                     one norm over all H P

**The recurrence is computed as written**, one time step after another
(`_recurrence`: a lax.scan over t), and not in the chunked or the quadratic
form: the program computes the chunked form, and a reference that shared
its algebra would share its mistakes (a wrong carry between chunks, a
decay taken from the wrong end of a chunk). The scan is nested, an outer one
over blocks of _STEPS steps under jax.checkpoint, so that the harness's vjp
keeps T / _STEPS + _STEPS states of (H, P, N) float32 and not T of them (8.6 GB
at T = 4,096): the same values, bounded memory. The whole-sequence quadratic
form ((C B^T) * L) (Delta x) was the other candidate; it needs a (T, T)
float32 array a head and shares the program's L.

Departures from the published code, as the configuration's file lists them
under `assumed`: the convolution's taps are stored (K, channels); Delta is
not clamped; the heads' width is d / heads.

A *layer* of this family, as the harness takes gradients, is one period of
`layer_types` (the program groups its blocks so: `p_0` with `h_0` ..), since
the harness asks every layer for one structure of parameters and a Mamba
block's differs from an attention block's. Inside, each block is under
jax.checkpoint, so a period's vjp keeps a block's input each and works in
one block at a time."""

import math

import jax
import jax.numpy as jnp

from bench.families._plain import causal_attention, highest, next_token_loss

_STEPS = 64  # time steps a checkpointed block of the recurrence

# The controls of the comparison (bench/tests/granite_control.py) put a known
# fault into this reference and see whether the comparison refuses it. Each is
# None in every other use.
OPERAND = None      # f(array): every matmul operand goes through it
RESET_EVERY = None  # the carried state is dropped at every such time step
LOG_DECAY = None    # (f(array), block): the cumulative log-decay within blocks of
                    # `block` steps goes through f, and a step's decay is its difference


def _mm(a, b):
    return a @ b if OPERAND is None else OPERAND(a) @ OPERAND(b)


def _period(kinds):
    n = len(kinds)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n)))


def build(sizes, compute_dtype):
    from ray_tpu.models.granite import GraniteConfig

    if sizes["mamba_expand"] * sizes["hidden_size"] != sizes["mamba_n_heads"] * sizes["mamba_d_head"]:
        raise ValueError("mamba_expand x hidden_size is not heads x head width")
    if (sizes["position_embedding_type"] != "nope" or not sizes["tie_word_embeddings"]
            or sizes["num_local_experts"] or sizes["attention_bias"] or sizes["mamba_proj_bias"]
            or not sizes["mamba_conv_bias"] or sizes["hidden_act"] != "silu"
            or sizes["normalization_function"] != "rmsnorm"
            or sizes["shared_intermediate_size"] != sizes["intermediate_size"]
            or len(sizes["layer_types"]) != sizes["num_hidden_layers"]):
        raise ValueError("models/granite.py runs the dense, tied, position-free form alone")
    return GraniteConfig(
        vocab_size=sizes["vocab_size"], block_size=sizes["max_position_embeddings"],
        n_embd=sizes["hidden_size"], layer_types=tuple(sizes["layer_types"]),
        n_head=sizes["num_attention_heads"], n_kv_head=sizes["num_key_value_heads"],
        intermediate=sizes["shared_intermediate_size"], ssm_heads=sizes["mamba_n_heads"],
        ssm_head_dim=sizes["mamba_d_head"], ssm_state=sizes["mamba_d_state"],
        ssm_groups=sizes["mamba_n_groups"], ssm_conv=sizes["mamba_d_conv"],
        ssm_chunk=sizes["mamba_chunk_size"],
        embedding_multiplier=sizes["embedding_multiplier"],
        residual_multiplier=sizes["residual_multiplier"],
        attention_multiplier=sizes["attention_multiplier"],
        logits_scaling=sizes["logits_scaling"], rms_eps=sizes["rms_norm_eps"],
        rope_theta=sizes["rope_theta"], dtype=jnp.dtype(compute_dtype))


def _widths(sizes):
    h, p = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    gn = sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    return h, p, h * p, gn


def matmul_params(sizes):
    """A Mamba mixer's W_in (d x (2 H P + 2 G N + H)) and W_out (H P x d), an
    attention layer's q and o (d x d), k and v (d x kv heads x head width),
    the MLP's three matrices after either, and the tied matrix once, as the
    head: the embedding is a look-up. The convolution's taps, the norms and
    the per-head vectors multiply element by element and are left out, as
    every family leaves out its norms."""
    d, ff = sizes["hidden_size"], sizes["shared_intermediate_size"]
    h, _, inner, gn = _widths(sizes)
    kv = sizes["num_key_value_heads"] * (d // sizes["num_attention_heads"])
    mixer = {"mamba": d * (2 * inner + 2 * gn + h) + inner * d,
             "attention": 2 * d * d + 2 * d * kv}
    return (sum(mixer[kind] + 3 * d * ff for kind in sizes["layer_types"])
            + sizes["vocab_size"] * d)


def vector_params(sizes):
    """The parameters `matmul_params` leaves out: the convolution's taps and
    bias, dt_bias, A_log and D, the gated norm, two norms a layer and the
    final one."""
    d = sizes["hidden_size"]
    h, _, inner, gn = _widths(sizes)
    mamba = (inner + 2 * gn) * (sizes["mamba_d_conv"] + 1) + 3 * h + inner
    return sum(2 * d + (mamba if kind == "mamba" else 0) for kind in sizes["layer_types"]) + d


def flops_per_token(sizes, seq_len):
    """6 x matmul parameters + the causal attention term of the attention
    layers, 6 T d each (families/gpt2.py) + the recurrence as the equations
    need it, whatever computes it: a token and Mamba layer forward, 2 N P H
    each for the update (Delta x B^T onto the state), the decay and the
    read-out (S C), three times that with the backward. No chunk-quadratic
    term: a kernel that does more arithmetic earns no MFU for it."""
    kinds = sizes["layer_types"]
    _, _, inner, _ = _widths(sizes)
    return (6 * matmul_params(sizes)
            + 6 * kinds.count("attention") * seq_len * sizes["hidden_size"]
            + 18 * sizes["mamba_d_state"] * inner * kinds.count("mamba"))


def layer_names(sizes):
    kinds = sizes["layer_types"]
    return [f"p_{i}" for i in range(len(kinds) // _period(kinds))]


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * weight


def _recurrence(x, delta, a, bm, cm):
    """x (B, T, H, P), delta (B, T, H), a (H,), bm and cm (B, T, G, N) ->
    S_t C_t for every t, (B, T, H, P), S_{-1} = 0."""
    b, t, h, p = x.shape
    g, n = bm.shape[2:]
    steps = math.gcd(t, _STEPS)
    log_decay = delta * a  # (B, T, H), negative
    if LOG_DECAY is not None:
        rounded, size = LOG_DECAY
        c = rounded(jnp.cumsum(log_decay.reshape(b, t // size, size, h), axis=2))
        log_decay = jnp.diff(c, axis=2, prepend=0.0).reshape(b, t, h)
    if OPERAND is not None:
        x, bm, cm = OPERAND(x), OPERAND(bm), OPERAND(cm)

    def step(state, now):  # state (B, G, H/G, P, N)
        x_t, d_t, l_t, b_t, c_t, at = now
        if RESET_EVERY is not None:
            state = jnp.where(at % RESET_EVERY == 0, 0.0, state)
        state = (jnp.exp(l_t)[..., None, None] * state
                 + (d_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :])
        return state, jnp.einsum("bgrpn,bgn->bgrp", state, c_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    # time first, in blocks; heads by group
    by_block = lambda v: jnp.moveaxis(v, 1, 0).reshape(t // steps, steps, *v.shape[:1], *v.shape[2:])
    heads = lambda v: by_block(v.reshape(b, t, g, h // g, *v.shape[3:]))
    xs = (heads(x), heads(delta), heads(log_decay), by_block(bm), by_block(cm),
          jnp.arange(t).reshape(t // steps, steps))
    _, y = jax.lax.scan(block, jnp.zeros((b, g, h // g, p, n), jnp.float32), xs)
    return jnp.moveaxis(y.reshape(t, b, h, p), 0, 1)


def _scan_inputs(u, m, sizes):
    """(z, x, Delta, A, B, C) of a Mamba mixer from its normed input: the
    input projection, the convolution and the split."""
    b, t, _ = u.shape
    h, p, inner, gn = _widths(sizes)
    g, k = sizes["mamba_n_groups"], sizes["mamba_d_conv"]
    z, xbc, dt = jnp.split(_mm(u, m["in_proj"]["kernel"]), [inner, 2 * inner + 2 * gn], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(m["conv_bias"] + sum(
        padded[:, i:i + t] * m["conv_kernel"][i] for i in range(k)))
    x, bm, cm = jnp.split(xbc, [inner, inner + gn], axis=-1)
    return (z, x.reshape(b, t, h, p), jax.nn.softplus(dt + m["dt_bias"]), -jnp.exp(m["A_log"]),
            bm.reshape(b, t, g, gn // g), cm.reshape(b, t, g, gn // g))


def _mamba(u, m, sizes):
    z, x, delta, a, bm, cm = _scan_inputs(u, m, sizes)
    y = _recurrence(x, delta, a, bm, cm) + m["D"][:, None] * x
    y = y.reshape(*z.shape)
    return _mm(_rms_norm(y * jax.nn.silu(z), m["norm"]["weight"], sizes["rms_norm_eps"]),
               m["out_proj"]["kernel"])


def _attention(u, a, sizes):
    b, t, d = u.shape
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    width = d // heads
    q = _mm(u, a["wq"]["kernel"]).reshape(b, t, heads, width)
    k = _mm(u, a["wk"]["kernel"]).reshape(b, t, kv, width)
    v = _mm(u, a["wv"]["kernel"]).reshape(b, t, kv, width)
    # causal_attention divides the scores by sqrt(width); the family scales
    # them by attention_multiplier instead
    q = q * (sizes["attention_multiplier"] * math.sqrt(width))
    if OPERAND is not None:
        q, k, v = OPERAND(q), OPERAND(k), OPERAND(v)
    return _mm(causal_attention(q, k, v).reshape(b, t, d), a["wo"]["kernel"])


def block(x, blk, sizes):
    """One block: a Mamba block or an attention block, told apart by the
    parameters it is handed."""
    eps, res = sizes["rms_norm_eps"], sizes["residual_multiplier"]
    u = _rms_norm(x, blk["mixer_norm"]["weight"], eps)
    mixed = _mamba(u, blk["mamba"], sizes) if "mamba" in blk else _attention(u, blk["attn"], sizes)
    x = x + res * mixed
    u = _rms_norm(x, blk["mlp_norm"]["weight"], eps)
    mlp = blk["mlp"]
    return x + res * _mm(jax.nn.silu(_mm(u, mlp["gate"]["kernel"])) * _mm(u, mlp["up"]["kernel"]),
                         mlp["down"]["kernel"])


@highest
def layer(x, period, sizes):
    for i in range(len(period)):
        x = jax.checkpoint(lambda x, blk: block(x, blk, sizes))(x, period[f"h_{i}"])
    return x


@highest
def embed(outer, idx, sizes):
    return sizes["embedding_multiplier"] * outer["tok_emb"]["embedding"][idx]


@highest
def head_loss(outer, x, targets, sizes):
    x = _rms_norm(x, outer["final_norm"]["weight"], sizes["rms_norm_eps"])
    return next_token_loss(
        _mm(x, outer["tok_emb"]["embedding"].T) / sizes["logits_scaling"], targets)
