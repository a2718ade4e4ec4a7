"""NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (nvidia; `model_type` nemotron_h, HF
`modeling_nemotron_h.py`): a decoder whose every block is a norm and one
mixer, the kind told by a letter of `hybrid_override_pattern`: `M` a Mamba-2
mixer (Dao & Gu 2024), `E` an expert layer, `*` attention with no position.
The equations, d the hidden size, x the stream:

    x0 = E[idx]
    a block:  x <- x + mixer(RMSNorm(x))
    logits = W_head RMSNorm(x);  loss = mean cross-entropy

    M:  [z | xBC | dt] = W_in u      d -> H P + (H P + 2 G N) + H, H P =
                                     mamba_num_heads x mamba_head_dim
        xBC <- silu(conv(xBC))       depthwise, causal, K taps, with bias:
                                     out_t = bias + sum_i w_i in_{t-(K-1)+i}
        x (T, H, P), B (T, G, N), C (T, G, N) = split(xBC);  head h reads B
        and C of group h // (H / G)
        Delta = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T   (P x N a head)
        y_t = S_t C_t + D x_t
        out = W_out RMSNorm_g(y * silu(z))   the gate first, then an RMS norm
                                     over each group's H P / G channels on
                                     its own, one weight of H P
    *:  q = W_q u (heads x head_dim), k, v = W_k u, W_v u (kv heads x
        head_dim), causal softmax(q k^T / sqrt(head_dim)) v, head h reads
        key-value head h // (heads / kv heads), out = W_o o; no bias, no
        rotary, no other position
    E:  s = sigmoid(W_r u); the k experts of a token are the top k of s + b;
        its gates s at those, over their sum + 1e-20, times
        routed_scaling_factor; an expert is W_down relu(W_up u)^2, two
        matrices; + the shared expert, the same form
        moe_shared_expert_intermediate_size wide, for every token

**The recurrence is computed as written**, one time step after another, in
checkpointed blocks of time (families/granite.py says why, and why not the
chunked or the quadratic form). The harness takes this family's gradient
beside 9.9 GiB of training state and 5 GiB of the reference's own gradients,
so the pieces work in bounded memory: the Mamba mixer a group of heads at a
time, the attention in blocks of 128 queries, each under jax.checkpoint: the
same values.

What a configuration file may cut (bench/configs/nemotron3_nano_l9_ep16.json):
the layers (`hybrid_override_pattern` spells the kept ones), the vocabulary,
and the experts this program holds: `n_routed_experts` is the count held,
experts `first_expert_held` onward of `n_routed_experts_published`, which is
the router's width; a token's gates are normalised over all its choices, and
what the experts held elsewhere would add is left out, here as in the
program. The shared expert is whole on every chip.

A *layer* of this family, as the harness takes gradients, is all the blocks
(`p_0` with `h_0` ..), as families/lfm2.py says; the group's choice is its
expert blocks' stacked, (expert blocks, rows, T, k).
"""

import math

import jax
import jax.numpy as jnp

from bench.families._plain import causal_attention, highest, next_token_loss

_STEPS = 64  # time steps a checkpointed block of the recurrence
_QUERIES = 128  # queries a checkpointed block of the attention

# The controls of the comparison (bench/tests/nemotron_h_control.py) put a
# known fault into this reference and see whether the comparison refuses it.
# Each is None (or False) in every other use.
OPERAND = None      # f(array): every matmul operand goes through it
RESET_EVERY = None  # the carried state is dropped at every such time step
LOG_DECAY = None    # (f(array), block): as families/granite.py's
ONE_GROUP = False   # every head reads group 0's B and C


def _mm(a, b):
    return a @ b if OPERAND is None else OPERAND(a) @ OPERAND(b)


def build(sizes, compute_dtype):
    from ray_tpu.models.nemotron_h import NemotronHConfig, layer_types

    pattern = sizes["hybrid_override_pattern"]
    if (sizes["attention_bias"] or sizes["mamba_proj_bias"] or sizes["mlp_bias"]
            or sizes["use_bias"] or not sizes["use_conv_bias"] or sizes["tie_word_embeddings"]
            or sizes["mamba_hidden_act"] != "silu" or sizes["mlp_hidden_act"] != "relu2"
            or sizes["n_group"] != 1 or sizes["topk_group"] != 1 or not sizes["norm_topk_prob"]
            or sizes["n_shared_experts"] != 1 or sizes["residual_in_fp32"]
            or sizes["sliding_window"] is not None
            or sizes["moe_intermediate_size"] != sizes["intermediate_size"]
            or sizes["norm_eps"] != sizes["layer_norm_epsilon"]
            or len(pattern) != sizes["num_hidden_layers"] or set(pattern) - set("ME*")):
        raise ValueError(
            "models/nemotron_h.py: no bias but the convolution's, silu in the Mamba mixer, "
            "relu squared in the experts, sigmoid scores under a selection bias in one group, "
            "gates normalised over the chosen, one shared expert, an untied head, a pattern "
            "of M, E and * as long as the layers")
    return NemotronHConfig(
        vocab_size=sizes["vocab_size"], block_size=sizes["max_position_embeddings"],
        n_embd=sizes["hidden_size"], layer_types=layer_types(pattern),
        n_head=sizes["num_attention_heads"], n_kv_head=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], ssm_heads=sizes["mamba_num_heads"],
        ssm_head_dim=sizes["mamba_head_dim"], ssm_state=sizes["ssm_state_size"],
        ssm_groups=sizes["n_groups"], ssm_conv=sizes["conv_kernel"],
        ssm_chunk=sizes["chunk_size"], expert_dim=sizes["moe_intermediate_size"],
        shared_dim=sizes["moe_shared_expert_intermediate_size"],
        num_experts=sizes["n_routed_experts_published"], top_k=sizes["num_experts_per_tok"],
        first_expert=sizes["first_expert_held"], num_held=sizes["n_routed_experts"],
        routed_scaling=float(sizes["routed_scaling_factor"]), rms_eps=sizes["layer_norm_epsilon"],
        rope_theta=float(sizes["rope_theta"]), dtype=jnp.dtype(compute_dtype))


def _widths(sizes):
    h, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    return h, p, h * p, sizes["n_groups"] * sizes["ssm_state_size"]


def _count(sizes, letter):
    return sizes["hybrid_override_pattern"].count(letter)


def matmul_params(sizes):
    """An `M` block's W_in (d x (2 H P + 2 G N + H)) and W_out (H P x d); a
    `*` block's q and o (d x heads x head_dim) and k and v (d x kv heads x
    head_dim); an `E` block's router (d x experts published), the shared
    expert whole (2 x d x its width) and of the routed experts' two matrices
    each what a token meets at even routing: experts-per-token x held /
    published of them (families/mellum.py's rule). The head once: the
    embedding is a look-up. The taps, the norms and the per-head vectors
    multiply element by element and are left out."""
    d = sizes["hidden_size"]
    h, _, inner, gn = _widths(sizes)
    hd = sizes["head_dim"]
    met = (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
           / sizes["n_routed_experts_published"])
    block = {"M": d * (2 * inner + 2 * gn + h) + inner * d,
             "*": 2 * d * hd * (sizes["num_attention_heads"] + sizes["num_key_value_heads"]),
             "E": int(d * sizes["n_routed_experts_published"]
                      + 2 * d * sizes["moe_shared_expert_intermediate_size"]
                      + met * 2 * d * sizes["moe_intermediate_size"])}
    return sum(block[letter] for letter in sizes["hybrid_override_pattern"]) \
        + sizes["vocab_size"] * d


def vector_params(sizes):
    """The parameters `matmul_params` leaves out, with every held expert's
    matrices whole in place of what a token meets, and the embedding: a norm
    a block and the final one, an `M` block's taps and bias, dt_bias, A_log,
    D and the gated norm, an `E` block's selection bias. With
    `matmul_params`'s: every parameter held here."""
    d = sizes["hidden_size"]
    h, _, inner, gn = _widths(sizes)
    mamba = (inner + 2 * gn) * (sizes["conv_kernel"] + 1) + 3 * h + inner
    held, k = sizes["n_routed_experts"], sizes["num_experts_per_tok"]
    unmet = (held - k * held / sizes["n_routed_experts_published"]) \
        * 2 * d * sizes["moe_intermediate_size"]
    return int(len(sizes["hybrid_override_pattern"]) * d + d + _count(sizes, "M") * mamba
               + _count(sizes, "E") * (sizes["n_routed_experts_published"] + unmet)
               + sizes["vocab_size"] * d)


def flops_per_token(sizes, seq_len):
    """6 x matmul parameters + the causal term of the `*` blocks, 6 T x heads
    x head_dim each (families/gpt2.py's rule over the width the scores and
    values have) + the recurrence as the equations need it, 18 N H P a token
    and `M` block (families/granite.py's rule). At the published widths, 8 of
    128 experts, `MEMEM*EME` and V = 16,384: 6 x 318.43 M + 201.3 M at T =
    8,192 + 37.7 M = 2.150 G."""
    _, _, inner, _ = _widths(sizes)
    return (6 * matmul_params(sizes)
            + 6 * _count(sizes, "*") * seq_len * sizes["num_attention_heads"] * sizes["head_dim"]
            + 18 * sizes["ssm_state_size"] * inner * _count(sizes, "M"))


def layer_names(sizes):
    return ["p_0"]


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * weight


def _recurrence(x, delta, a, bm, cm):
    """One group's heads: x (B, T, R, P), delta (B, T, R), a (R,), bm and cm
    (B, T, N) -> S_t C_t for every t, (B, T, R, P), S_{-1} = 0."""
    b, t, r, p = x.shape
    n = bm.shape[-1]
    steps = math.gcd(t, _STEPS)
    log_decay = delta * a  # (B, T, R), negative
    if LOG_DECAY is not None:
        rounded, size = LOG_DECAY
        c = rounded(jnp.cumsum(log_decay.reshape(b, t // size, size, r), axis=2))
        log_decay = jnp.diff(c, axis=2, prepend=0.0).reshape(b, t, r)
    if OPERAND is not None:
        x, bm, cm = OPERAND(x), OPERAND(bm), OPERAND(cm)

    def step(state, now):  # state (B, R, P, N)
        x_t, d_t, l_t, b_t, c_t, at = now
        if RESET_EVERY is not None:
            state = jnp.where(at % RESET_EVERY == 0, 0.0, state)
        state = (jnp.exp(l_t)[..., None, None] * state
                 + (d_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return state, jnp.einsum("brpn,bn->brp", state, c_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    # time first, in blocks
    by_block = lambda v: jnp.moveaxis(v, 1, 0).reshape(t // steps, steps, *v.shape[:1], *v.shape[2:])
    xs = (*map(by_block, (x, delta, log_decay, bm, cm)), jnp.arange(t).reshape(t // steps, steps))
    _, y = jax.lax.scan(block, jnp.zeros((b, r, p, n), jnp.float32), xs)
    return jnp.moveaxis(y.reshape(t, b, r, p), 0, 1)


def _by_group(v, segments, g):
    """v (..., sum of `segments`): every segment's columns in g equal parts
    -> (g, ..., a part of each segment side by side)."""
    parts, at = [], 0
    for width in segments:
        parts.append(v[..., at:at + width].reshape(*v.shape[:-1], g, width // g))
        at += width
    return jnp.moveaxis(jnp.concatenate(parts, -1), -2, 0)


def _group_mixer(u, m, p, n, eps):
    """The `M` mixer of one group's R heads up to the gated norm, (B, T, R P):
    the module docstring's equations with G = 1, `m` holding the group's
    columns of every parameter ([z | x B C | dt] of W_in, [x B C] of the
    taps)."""
    b, t, _ = u.shape
    k, r = m["conv_kernel"].shape[0], m["dt_bias"].shape[0]
    inner = r * p
    z, xbc, dt = jnp.split(_mm(u, m["in_proj"]), [inner, 2 * inner + 2 * n], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(m["conv_bias"] + sum(
        padded[:, i:i + t] * m["conv_kernel"][i] for i in range(k)))
    x, bm, cm = jnp.split(xbc, [inner, inner + n], axis=-1)
    x = x.reshape(b, t, r, p)
    y = _recurrence(x, jax.nn.softplus(dt + m["dt_bias"]), -jnp.exp(m["A_log"]), bm, cm)
    y = (y + m["D"][:, None] * x).reshape(b, t, inner)
    return _rms_norm(y * jax.nn.silu(z), m["norm"], eps)  # the gate first, the group's own norm


def _mamba(u, m, sizes):
    """A group at a time under jax.checkpoint: the groups share the input
    and the output's projection and nothing else (each has its heads' z, x
    and dt, its B and C, its taps, its part of the gated norm)."""
    h, p, inner, gn = _widths(sizes)
    g = sizes["n_groups"]
    n = gn // g
    groups = {"in_proj": _by_group(m["in_proj"]["kernel"], (inner, inner, gn, gn, h), g),
              "conv_kernel": _by_group(m["conv_kernel"], (inner, gn, gn), g),
              "conv_bias": _by_group(m["conv_bias"], (inner, gn, gn), g),
              "norm": _by_group(m["norm"]["weight"], (inner,), g),
              **{name: _by_group(m[name], (h,), g) for name in ("dt_bias", "A_log", "D")}}
    if ONE_GROUP:  # group 0's B and C columns for every group
        for name, at in (("in_proj", 2 * inner // g), ("conv_kernel", inner // g),
                         ("conv_bias", inner // g)):
            v = groups[name]
            groups[name] = v.at[..., at:at + 2 * n].set(v[:1, ..., at:at + 2 * n])
    y = jax.lax.map(jax.checkpoint(
        lambda of: _group_mixer(u, of, p, n, sizes["layer_norm_epsilon"])), groups)
    b, t, _ = u.shape
    return _mm(jnp.moveaxis(y, 0, 2).reshape(b, t, inner), m["out_proj"]["kernel"])


def _causal_attention(q, k, v):
    """`_plain.causal_attention` (grouped queries: head h reads key-value head
    h // (H / G)) in query blocks of _QUERIES: at 16 query heads to a
    key-value head a block's scores are 16 x _QUERIES x T a key-value head,
    and the harness takes this family's gradient beside 9.9 GiB of state."""
    B, T, H, D = q.shape
    G = k.shape[2]
    if T <= _QUERIES:
        return causal_attention(q, k, v)
    n = T // _QUERIES
    if n * _QUERIES != T:
        raise ValueError(f"sequence {T} is not a multiple of {_QUERIES}")
    key_pos = jnp.arange(T)

    def block(q_blk, start):
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q_blk, k) / math.sqrt(D)
        q_pos = start + jnp.arange(_QUERIES)
        s = jnp.where(q_pos[:, None] >= key_pos[None, :], s, -jnp.inf)
        return jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(s, axis=-1), v)

    blocks = q.reshape(B, n, _QUERIES, G, H // G, D).swapaxes(0, 1)
    out = jax.lax.map(lambda xs: jax.checkpoint(block)(xs[0], xs[1]),
                      (blocks, jnp.arange(n) * _QUERIES))
    return out.swapaxes(0, 1).reshape(B, T, H, D)


def _attention(u, a, sizes):
    b, t, _ = u.shape
    heads, kv, width = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                        sizes["head_dim"])
    q = _mm(u, a["wq"]["kernel"]).reshape(b, t, heads, width)
    k = _mm(u, a["wk"]["kernel"]).reshape(b, t, kv, width)
    v = _mm(u, a["wv"]["kernel"]).reshape(b, t, kv, width)
    if OPERAND is not None:
        q, k, v = OPERAND(q), OPERAND(k), OPERAND(v)
    return _mm(_causal_attention(q, k, v).reshape(b, t, heads * width), a["wo"]["kernel"])


def _relu2(u, up, down):
    return _mm(jnp.square(jax.nn.relu(_mm(u, up))), down)


def _routed(u, moe, sizes, choice):
    """(the held experts' part of the layer, the choice it used)."""
    scores = jax.nn.sigmoid(_mm(u, moe["router"]["kernel"]))
    if choice is None:
        choice = jax.lax.top_k(scores + moe["expert_bias"], sizes["num_experts_per_tok"])[1]
    chosen = jnp.take_along_axis(scores, choice, axis=-1)
    # over all chosen, held or not
    gates = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * sizes["routed_scaling_factor"]

    def one_expert(y, e):
        # every token through expert e, weighted by the gate of the tokens
        # that chose it and by zero for the rest
        weight = jnp.where(choice == sizes["first_expert_held"] + e, gates, 0.0).sum(-1)
        return y + weight[..., None] * _relu2(u, moe["up"][e], moe["down"][e]), None

    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(u),
                        jnp.arange(sizes["n_routed_experts"]))
    return y, choice


def block(x, blk, sizes, choice=None):
    """One block, its kind told by the parameters it is handed: (x, the
    choice its expert layer used, or None where it has none)."""
    u = _rms_norm(x, blk["norm"]["weight"], sizes["layer_norm_epsilon"])
    if "mamba" in blk:
        return x + _mamba(u, blk["mamba"], sizes), None
    if "attn" in blk:
        return x + _attention(u, blk["attn"], sizes), None
    y, choice = _routed(u, blk["moe"], sizes, choice)
    shared = blk["shared"]
    return x + y + _relu2(u, shared["up"]["kernel"], shared["down"]["kernel"]), choice


@highest
def _run(x, group, sizes, choice):
    """The blocks in order; (x, the expert blocks' choices stacked). With a
    `choice` given, its i-th entry is the i-th expert block's."""
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
    x = _rms_norm(x, outer["final_norm"]["weight"], sizes["layer_norm_epsilon"])
    return next_token_loss(_mm(x, outer["lm_head"]), targets)
