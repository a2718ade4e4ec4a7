"""SDAR-30B-A3B-Chat (JetLM, 2025; the catalog row's config.json, `model_type`
`sdar_moe`): a Qwen3-MoE decoder (pre-norm RMSNorm, grouped queries 8 to 1
with heads of their own size and an RMSNorm over each head of q and k, in
every layer a top-8-of-128 SwiGLU expert layer, gates renormalised over the
chosen, no shared expert, no biases, untied head) that is *trained by block
diffusion*: the row's mechanism is the objective.

A sequence x0 of T tokens is cut into blocks of L = `block_length`,
blk(i) = i // L. One step, for every block at once:

    u_b ~ U[0, 1)  a (sequence, block);  t_b = eps + (1 - eps) u_b
    r_i ~ U[0, 1)  a token;  m_i = [r_i < t_blk(i)] and [blk(i) >= 1]
    xt_i = MASK if m_i else x0_i
    stream = [xt | x0]                      2T positions, both halves at
                                            rotary positions 0 .. T-1

and the layers run on the stream under this mask (q a query, k a key, i and j
their positions within their halves):

    noised q i, noised k j : seen iff blk(j) == blk(i)
    noised q i, clean  k j : seen iff blk(j) <  blk(i)
    clean  q i, clean  k j : seen iff blk(j) <= blk(i)
    clean  q i, noised k j : never

One layer, for its normed input h_p = rmsnorm(x_p) at stream position p whose
position within its half is i:

    q_{p,h} = rope_i(rmsnorm(W_q h_p)_h)      32 heads of 128
    k_{p,g} = rope_i(rmsnorm(W_k h_p)_g)      4 key-value heads, g(h) = h // 8
    v_{p,g} = (W_v h_p)_g
    o_{p,h} = sum_{s seen by p} softmax_s(q_{p,h} . k_{s,g(h)} / sqrt(128)) v_{s,g(h)}
    x'_p = x_p + W_o o_p
    x''_p = x'_p + sum_{e in top8(p_p)} p_{p,e} / (sum over the 8) expert_e(rmsnorm(x'_p)),
            p_p = softmax(W_r rmsnorm(x'_p)) over all 128 experts, in float32

and the loss reads the noised half alone, each masked token at its own
position (no shift):

    loss = sum_i m_i CE(W_head rmsnorm(x_i), x0_i) / t_blk(i)  /  (B (T - L))

Block 0 of every sequence is never masked and carries no loss (the prompt's
stand-in). What the configuration's file lists under `assumed`: the per-head
q/k RMSNorm, the block length, the mask token, the schedule, the loss, the
clean block 0 and the noise's recipe, which `_noise` states again: the
harness hands the reference `idx` and next-token `targets` alone
(bench/worker.py), so the noise is a function of the step's count (0: the
compared step is the first) and the shapes, and both sides draw it by the same
lines of jax.random. `head_loss` is handed no `idx`: it takes x0_i =
targets_(i-1) for i >= 1, which the uniform generator's shift makes true, and
position 0 lies in block 0.

What a configuration file may cut (bench/configs/sdar_30b_a3b_l5_ep8.json):
the layers, the vocabulary, and the experts this program holds, as
bench/families/mellum.py has it.

At T = 8,192 the stream's scores of 32 heads are 34 GB, so a layer works a
block of queries at a time over `jax.checkpoint`ed blocks; a block sees every
key, so the blocks bound memory and change no arithmetic.
"""

import math

import jax
import jax.numpy as jnp

from bench.families._plain import highest

# Queries a block: 32 heads x 256 x 16,384 float32 scores are 0.5 GiB.
QUERY_BLOCK = 256


def build(sizes, compute_dtype):
    from ray_tpu.models.sdar import SDARConfig

    if sizes["hidden_act"] != "silu" or sizes.get("attention_bias") or \
            sizes.get("tie_word_embeddings") or not sizes["norm_topk_prob"]:
        raise ValueError("models/sdar.py: SwiGLU experts, no biases, untied head, "
                         "gates normalised over the chosen")
    if sizes["decoder_sparse_step"] != 1 or sizes["mlp_only_layers"] or \
            sizes.get("use_sliding_window") or sizes["rope_scaling"] is not None:
        raise ValueError("models/sdar.py: every MLP an expert layer, no window, plain rotary")
    return SDARConfig(
        vocab_size=sizes["vocab_size"], block_size=sizes["max_position_embeddings"],
        n_layer=sizes["num_hidden_layers"], n_head=sizes["num_attention_heads"],
        n_kv_head=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        n_embd=sizes["hidden_size"], rope_theta=float(sizes["rope_theta"]),
        rms_eps=sizes["rms_norm_eps"], expert_dim=sizes["moe_intermediate_size"],
        num_experts=sizes["num_experts_published"], top_k=sizes["num_experts_per_tok"],
        first_expert=sizes["first_expert_held"], num_held=sizes["num_experts"],
        block_length=sizes["block_length"], mask_token_id=sizes["mask_token_id"],
        noise_eps=sizes["noise_eps"], noise_seed=sizes["noise_seed"],
        dtype=jnp.dtype(compute_dtype))


def matmul_params(sizes):
    """The matrices a data token meets: q, o, k, v, the router and the expert
    matrices at even routing (experts-per-token x held / published of them)
    of every layer twice, once for each of its two positions of the stream,
    and the untied head once: it reads the noised half alone."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    attn = 2 * d * sizes["num_attention_heads"] * hd + 2 * d * sizes["num_key_value_heads"] * hd
    router = d * sizes["num_experts_published"]
    experts = (sizes["num_experts_per_tok"] * sizes["num_experts"]
               / sizes["num_experts_published"] * 3 * d * sizes["moe_intermediate_size"])
    return int(2 * sizes["num_hidden_layers"] * (attn + router + experts)
               + sizes["vocab_size"] * d)


def flops_per_token(sizes, seq_len):
    """The model's own work for a data token, forward and backward: 6 x
    `matmul_params`; attention 12 x heads x head_dim x the keys its two
    queries see. The mask shows a noised query its block and the clean
    blocks before it, L (b + 1) keys in block b, and a clean query as many:
    T^2 + T L pairs a sequence of the stream's (2T)^2, T + L a token. At the
    published widths, 16 of 128 experts, 5 layers, V = 18,992, T = 8,192,
    L = 4: 6 x (2 x 5 x (18.87 M + 0.26 M + 8 x 16/128 x 4.72 M) + 38.9 M)
    = 1.665 G, + 5 x 12 x 4096 x 8196 = 2.014 G: 3.68 GF a token, 30.1 TF a
    step, of which the attention core is 55%."""
    width = sizes["num_attention_heads"] * sizes["head_dim"]
    keys = seq_len + sizes["block_length"]
    return int(6 * matmul_params(sizes) + sizes["num_hidden_layers"] * 12 * width * keys)


def layer_names(sizes):
    return [f"h_{i}" for i in range(sizes["num_hidden_layers"])]


# What each matmul does to an operand before it multiplies: nothing. The
# control of bench/tests/sdar_control.py puts a rounding to a lower precision
# here, to show what the comparison refuses.
OPERAND = None


def _mm(a, b):
    return a @ b if OPERAND is None else OPERAND(a) @ OPERAND(b)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * weight


def _noise(sizes, rows, T):
    """(m (rows, T) bool, t (rows, T) float32) of step 0, by the recipe the
    configuration's file states: key = fold_in(PRNGKey(noise_seed), 0), split
    in two; u (rows, ceil(T / L)) uniform from the first, r (rows, T) uniform
    from the second."""
    L, eps = sizes["block_length"], sizes["noise_eps"]
    levels, tokens = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(sizes["noise_seed"]), 0))
    t = eps + (1 - eps) * jax.random.uniform(levels, (rows, -(-T // L)), jnp.float32)
    t = jnp.repeat(t, L, axis=1)[:, :T]
    r = jax.random.uniform(tokens, (rows, T), jnp.float32)
    return (r < t) & (jnp.arange(T) // L >= 1), t


def seen(q_clean, i, k_clean, j, L):
    """The mask's four defining lines: whether a query (its half, its
    position i within it) sees a key (its half, its position j), blk(i) =
    i // L. bench/tests/sdar_control.py puts a wrong mask in its place,
    `SEEN`, to show what notices."""
    q_blk, k_blk = i // L, j // L
    return jnp.where(
        ~q_clean & ~k_clean, k_blk == q_blk,       # noised q, noised k: the block itself
        jnp.where(~q_clean & k_clean, k_blk < q_blk,   # noised q, clean k: the clean past, strictly
                  jnp.where(q_clean & k_clean, k_blk <= q_blk,  # clean q, clean k: block-causal
                            False)))                   # clean q, noised k: never


SEEN = seen


def _rope(x, theta):
    """x (B, 2T, H, D): rotate pairs (i, i + D/2) by pos * theta^(-2i/D),
    pos the position within its half."""
    S, D = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = (jnp.arange(S) % (S // 2)).astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def stream_attention(q, k, v, L):
    """q (B, 2T, H, D), k and v (B, 2T, G, D) over the stream [noised |
    clean], head h reads key-value head h // (H/G); a query sees the keys
    `SEEN` shows it. In blocks of queries, each against every key."""
    B, S, H, D = q.shape
    G, T = k.shape[2], S // 2
    if OPERAND is not None:
        q, k, v = OPERAND(q), OPERAND(k), OPERAND(v)
    q = q.reshape(B, S, G, H // G, D)
    at = jnp.arange(S)

    def block(q_blk, start):
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q_blk, k) / math.sqrt(D)
        p = start + jnp.arange(q_blk.shape[1])
        shown = SEEN((p >= T)[:, None], (p % T)[:, None], (at >= T)[None, :], (at % T)[None, :], L)
        w = jax.nn.softmax(jnp.where(shown, s, -jnp.inf), axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", w if OPERAND is None else OPERAND(w), v)

    if S <= QUERY_BLOCK:
        out = block(q, 0)
    else:
        n = S // QUERY_BLOCK
        if n * QUERY_BLOCK != S:
            raise ValueError(f"a stream of {S} is not a multiple of {QUERY_BLOCK}")
        blocks = q.reshape(B, n, QUERY_BLOCK, G, H // G, D).swapaxes(0, 1)
        out = jax.lax.map(lambda xs: jax.checkpoint(block)(xs[0], xs[1]),
                          (blocks, jnp.arange(n) * QUERY_BLOCK))
        out = out.swapaxes(0, 1).reshape(B, S, G, H // G, D)
    return out.reshape(B, S, H, D)


def _attend(x, blk, sizes):
    B, S, _ = x.shape
    H, G, D = (sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"])
    eps, theta, attn = sizes["rms_norm_eps"], float(sizes["rope_theta"]), blk["attn"]
    h = _rms_norm(x, blk["attn_norm"]["weight"], eps)
    q = _rms_norm(_mm(h, attn["wq"]["kernel"]).reshape(B, S, H, D), attn["q_norm"]["weight"], eps)
    k = _rms_norm(_mm(h, attn["wk"]["kernel"]).reshape(B, S, G, D), attn["k_norm"]["weight"], eps)
    v = _mm(h, attn["wv"]["kernel"]).reshape(B, S, G, D)
    out = stream_attention(_rope(q, theta), _rope(k, theta), v, sizes["block_length"])
    return x + _mm(out.reshape(B, S, -1), attn["wo"]["kernel"])


@highest
def attend(x, blk, sizes):
    """The stream after a layer's attention, before its experts: what the
    control and the tests compare a mask by."""
    return _attend(x, blk, sizes)


def _route(x, blk, sizes):
    """(the expert layer's input, every expert's probability) of a position."""
    h = _rms_norm(x, blk["moe_norm"]["weight"], sizes["rms_norm_eps"])
    return h, jax.nn.softmax(_mm(h, blk["moe"]["router"]["kernel"]), axis=-1)


@highest
def choice(x, blk, sizes):
    """(B, 2T, 8): the experts of every position, in the stream's order."""
    _, probs = _route(_attend(x, blk, sizes), blk, sizes)
    return jax.lax.top_k(probs, sizes["num_experts_per_tok"])[1]


def layer(x, blk, sizes, choice=None):
    return _layer(x, blk, sizes, choice)


@highest
def _layer(x, blk, sizes, choice):
    x = _attend(x, blk, sizes)
    h, probs = _route(x, blk, sizes)
    if choice is None:
        choice = jax.lax.top_k(probs, sizes["num_experts_per_tok"])[1]
    chosen = jnp.take_along_axis(probs, choice, axis=-1)
    gates = chosen / chosen.sum(-1, keepdims=True)  # over all chosen, held or not
    moe = blk["moe"]

    def one_expert(y, e):
        # every position through expert e, weighted by the gate of those
        # that chose it and by zero for the rest
        weight = jnp.where(choice == sizes["first_expert_held"] + e, gates, 0.0).sum(-1)
        out = _mm(jax.nn.silu(_mm(h, moe["gate"][e])) * _mm(h, moe["up"][e]), moe["down"][e])
        return y + weight[..., None] * out, None

    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                        jnp.arange(sizes["num_experts"]))
    return x + y, 0.0


@highest
def embed(outer, idx, sizes):
    """(rows, 2T, d): the stream [x_t | x_0] of step 0's noise."""
    masked, _ = _noise(sizes, *idx.shape)
    stream = jnp.concatenate([jnp.where(masked, sizes["mask_token_id"], idx), idx], axis=1)
    return outer["tok_emb"]["embedding"][stream]


@highest
def head_loss(outer, x, targets, sizes):
    rows, T = targets.shape
    # x0_i = targets_(i-1); position 0 is in block 0, which carries no loss
    x0 = jnp.concatenate([jnp.zeros((rows, 1), targets.dtype), targets[:, :-1]], axis=1)
    masked, t = _noise(sizes, rows, T)
    h = _rms_norm(x[:, :T], outer["final_norm"]["weight"], sizes["rms_norm_eps"])
    logp = jax.nn.log_softmax(_mm(h, outer["lm_head"]), axis=-1)
    ce = -jnp.take_along_axis(logp, x0[..., None], axis=-1)[..., 0]
    return jnp.where(masked, ce / t, 0.0).sum() / max(1, rows * (T - sizes["block_length"]))
