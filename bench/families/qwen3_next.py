"""Qwen3-Next-80B-A3B-Instruct (Qwen; `model_type` qwen3_next; the equations
are those of the checkpoint's `modeling_qwen3_next.py` and of Gated Delta
Networks, arXiv:2412.06464): a pre-norm RMSNorm decoder whose mixers are Gated
DeltaNet (three layers of four) or gated softmax attention (the fourth),
every MLP routed with a shared expert beside the routed ones, an untied head.
The equations, d the hidden size, x the stream, h = RMSNorm(x) (eps 1e-6; the
source's "zero-centred" weight 1 + w from zero is one weight from one here):

    x0 = E[idx]
    a layer:  x <- x + mixer(RMSNorm(x));  x <- x + moe(RMSNorm(x))
    logits = W_head RMSNorm(x);  loss = mean cross-entropy

    Gated DeltaNet, Hk = 16 key heads, Hv = 32 value heads, all 128 wide:
        [q~ | k~ | v~] = silu(conv4([W_q h | W_k h | W_v h]))
                                    conv4: causal depthwise, 4 taps, no bias,
                                    one filter a channel
        z = W_z h (Hv x 128)   b = W_b h (Hv)   a = W_a h (Hv)
        q = l2norm_head(q~) / sqrt(128)    k = l2norm_head(k~)
                                    u * rsqrt(sum of a head's squares + 1e-6)
        beta = sigmoid(b)      g = -exp(A_log) * softplus(a + dt_bias)
                                    one number a value head and token, <= 0
        value head j reads key head j // 2
        S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
                                    S a value head's (128, 128), S_0 = 0
        o_t = S_t^T q_t
        y = W_o (RMSNorm_head(o) * w_norm * silu(z))
                                    the norm over a head's 128 alone
    gated attention, 16 query heads on 2 key-value heads of 256:
        q, gate = W_q h, W_g h (a head's 256 each)   k = W_k h   v = W_v h
        q, k <- RMSNorm_256(q), RMSNorm_256(k);  rotary (halves turned, theta
        1e7, positions from 0) on a head's first 64 entries, the other 192
        left as they are
        y = W_o (softmax(q k^T / 16, causal) v * sigmoid(gate))
    moe: p = softmax(W_r h) over all 512; the 10 largest; gates p_i over the
        sum of the chosen; y = sum_i gate_i SwiGLU_i(h)
        + sigmoid(w_s . h) * SwiGLU_shared(h)

**The delta rule is computed as written**, one time step after another
(`_recurrence`: a lax.scan over t), and not in the chunked form: the program
computes the chunked form (ops/gdn.py), and a reference that shared its
algebra would share its mistakes. The scan is nested, an outer one over
blocks of _STEPS steps under jax.checkpoint (families/kimi_linear.py's), so
that the harness's vjp keeps T / _STEPS + _STEPS states and not T of them.

The program keeps W_q, W_k, W_v side by side in one leaf (`qkv_proj`), the
three filters in one (`conv_kernel`, taps first), W_b and W_a in one
(`ba_proj`), and the source's q | gate projection as two (`wq`, `wg`); this
file cuts them apart.

What a configuration file may cut (bench/configs/qwen3_next_80b_l5_ep32.json):
the layers (`layers_kept`, published numbering from 0, whose kinds
`full_attention_interval` gives), the vocabulary, and the experts this
program holds: `num_experts` is the count held, experts `first_expert_held`
onward of `num_experts_published`, which is the router's width; a token's
gates are normalised over all its choices, and what the experts held
elsewhere would add is left out, here as in the program. The shared expert
is whole on every chip.

A *layer* of this family, as the harness takes gradients, is all the blocks
(`p_0` with `h_0` ..), as families/kimi_linear.py says; the group's choice
is its blocks' stacked, (blocks, rows, T, k).
"""

import math

import jax
import jax.numpy as jnp

from bench.families._plain import QUERY_BLOCK, highest, next_token_loss

_STEPS = 64  # time steps a checkpointed block of the recurrence
_HEADS = 4   # key heads of a DeltaNet mixer worked at a time

# The controls of bench/tests/qwen3_next_control.py put a known fault into
# this reference and see whether the comparison refuses it. Each is None or
# False in every other use.
OPERAND = None          # f(array): every matmul operand goes through it
RESET_EVERY = None      # the carried state is dropped at every such time step
NO_DELTA_TERM = False   # beta k k^T S is left out: S_t = exp(g) S + beta k v^T
DECAY_AFTER = False     # the decay applied after the update and not before it
KEY_HEAD_J = False      # value head j reads key head j (mod Hk) in place of j // 2
ROTARY_ALL = False      # the rotary over all 256 of a head


def _mm(a, b):
    return a @ b if OPERAND is None else OPERAND(a) @ OPERAND(b)


def _kinds(sizes):
    every = sizes["full_attention_interval"]
    return ["full" if (n + 1) % every == 0 else "linear" for n in sizes["layers_kept"]]


def build(sizes, compute_dtype):
    from ray_tpu.models.qwen3_next import FULL, LINEAR, Qwen3NextConfig

    if (sizes["rope_scaling"] is not None or not sizes["norm_topk_prob"]
            or sizes["decoder_sparse_step"] != 1 or sizes["mlp_only_layers"]
            or sizes["tie_word_embeddings"] or sizes["hidden_act"] != "silu"
            or sizes["use_sliding_window"]
            or len(sizes["layers_kept"]) != sizes["num_hidden_layers"]
            or sizes["layers_kept"] != list(range(sizes["num_hidden_layers"]))):
        raise ValueError(
            "models/qwen3_next.py: no rope scaling, gates normalised over the chosen, every "
            "layer routed, an untied head, no window, the layers kept the first of the "
            "published ones in order")
    rotary = sizes["partial_rotary_factor"] * sizes["head_dim"]
    if rotary != int(rotary) or int(rotary) % 2:
        raise ValueError(f"the rotary turns {rotary} entries of a head")
    return Qwen3NextConfig(
        vocab_size=sizes["vocab_size"], block_size=sizes["max_position_embeddings"],
        n_embd=sizes["hidden_size"],
        layer_types=tuple(FULL if kind == "full" else LINEAR for kind in _kinds(sizes)),
        gdn_key_heads=sizes["linear_num_key_heads"],
        gdn_value_heads=sizes["linear_num_value_heads"],
        gdn_key_dim=sizes["linear_key_head_dim"], gdn_value_dim=sizes["linear_value_head_dim"],
        gdn_conv=sizes["linear_conv_kernel_dim"], n_head=sizes["num_attention_heads"],
        n_kv_head=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        rotary_dim=int(rotary), rope_theta=float(sizes["rope_theta"]),
        expert_dim=sizes["moe_intermediate_size"], num_experts=sizes["num_experts_published"],
        top_k=sizes["num_experts_per_tok"], first_expert=sizes["first_expert_held"],
        num_held=sizes["num_experts"], shared_dim=sizes["shared_expert_intermediate_size"],
        rms_eps=sizes["rms_norm_eps"], dtype=jnp.dtype(compute_dtype))


def _gdn_widths(sizes):
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    return hk, hv, sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]


def matmul_params(sizes):
    """A DeltaNet block's mixer: W_q, W_k (d x Hk x 128 each), W_v, W_z and
    W_o (d x Hv x 128 each) and W_b, W_a (d x Hv each); the filters (4 x (2 Hk
    + Hv) x 128) are in no matmul. The attention block's: W_q and W_g (d x 16
    x 256 each), W_k, W_v (d x 2 x 256), W_o. Every MLP: the router (d x
    experts published), the shared expert whole with its gate's row (d), and
    of the routed experts' matrices what a token meets at even routing:
    experts-per-token x held / published of them (families/mellum.py's rule).
    The head once: the embedding is a look-up."""
    d = sizes["hidden_size"]
    hk, hv, dk, dv = _gdn_widths(sizes)
    linear = d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d
    heads, kv, hd = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    full = d * 2 * heads * hd + 2 * d * kv * hd + heads * hd * d
    kinds = _kinds(sizes)
    experts = (sizes["num_experts_per_tok"] * sizes["num_experts"]
               / sizes["num_experts_published"] * 3 * d * sizes["moe_intermediate_size"])
    routed = (d * sizes["num_experts_published"]
              + 3 * d * sizes["shared_expert_intermediate_size"] + d + experts)
    return int(kinds.count("linear") * linear + kinds.count("full") * full
               + len(kinds) * routed + sizes["vocab_size"] * d)


def flops_per_token(sizes, seq_len):
    """6 x matmul parameters; the attention layer's causal term, 6 x T x 16 x
    (256 + 256) / 2; and a DeltaNet layer's rule **by the recurrence**, the
    same whatever the chunk or the kernel (families/kimi_linear.py's): a
    token's three products with a value head's (128, 128) state forward and
    twice that backward, 18 x Hv x 128^2 = 9.44 M a layer and token."""
    _, hv, dk, dv = _gdn_widths(sizes)
    kinds = _kinds(sizes)
    return int(6 * matmul_params(sizes)
               + kinds.count("full") * 3 * seq_len * sizes["num_attention_heads"]
               * 2 * sizes["head_dim"]
               + kinds.count("linear") * 18 * hv * dk * dv)


def layer_names(sizes):
    return ["p_0"]


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * weight


def _recurrence(q, k, v, g, beta):
    """q, k (B, T, Hk, K), v (B, T, Hv, V), g and beta (B, T, Hv) -> o_t =
    S_t^T q_t / sqrt(K) for every t, (B, T, Hv, V), S_0 = 0; value head j
    reads key head j // (Hv / Hk)."""
    b, t, hk, kd = q.shape
    hv = v.shape[2]
    steps = math.gcd(t, _STEPS)
    scale = 1.0 / math.sqrt(kd)
    if KEY_HEAD_J:
        q, k = (jnp.tile(a, (1, 1, hv // hk, 1)) for a in (q, k))
    else:
        q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
    if OPERAND is not None:
        q, k, v = OPERAND(q), OPERAND(k), OPERAND(v)

    def step(state, now):  # state (B, Hv, K, V)
        q_t, k_t, v_t, g_t, b_t, at = now
        if RESET_EVERY is not None:
            state = jnp.where(at % RESET_EVERY == 0, 0.0, state)
        if not DECAY_AFTER:
            state = jnp.exp(g_t)[..., None, None] * state
        seen = 0.0 if NO_DELTA_TERM else jnp.einsum("bhk,bhkv->bhv", k_t, state)
        u = b_t[..., None] * (v_t - seen)
        state = state + k_t[..., None] * u[..., None, :]
        if DECAY_AFTER:
            state = jnp.exp(g_t)[..., None, None] * state
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state) * scale

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    by_block = lambda a: jnp.moveaxis(a, 1, 0).reshape(t // steps, steps, *a.shape[:1],
                                                       *a.shape[2:])
    xs = tuple(by_block(a) for a in (q, k, v, g, beta)) + (
        jnp.arange(t).reshape(t // steps, steps),)
    _, o = jax.lax.scan(block, jnp.zeros((b, hv, kd, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o.reshape(t, b, hv, v.shape[-1]), 0, 1)


def _delta_net(x, m, sizes):
    """The mixer a group of _HEADS key heads (and their value heads) at a
    time, each group under jax.checkpoint and its part of W_o's product added
    to the sum, as families/kimi_linear.py's `_kda` and for its reason: the
    heads are independent of one another from the projections to W_o."""
    b, t, d = x.shape
    hk, hv, dk, dv = _gdn_widths(sizes)
    rep, taps = hv // hk, sizes["linear_conv_kernel_dim"]
    per = hk if KEY_HEAD_J else math.gcd(hk, _HEADS)  # the fault crosses the groups
    groups = hk // per
    eps = sizes["rms_norm_eps"]
    keys, values = hk * dk, hv * dv

    def columns(w, width):  # (..., groups * width) -> (groups, ..., width)
        return jnp.moveaxis(w.reshape(*w.shape[:-1], groups, width), -2, 0)

    cut = lambda w: jnp.split(w, [keys, 2 * keys], axis=-1)
    (wq, wk, wv), (cq, ck, cv) = cut(m["qkv_proj"]["kernel"]), cut(m["conv_kernel"])
    w_b, w_a = jnp.split(m["ba_proj"]["kernel"], 2, axis=-1)
    l2norm = lambda a: a * jax.lax.rsqrt(jnp.square(a).sum(-1, keepdims=True) + 1e-6)

    def conv(u, w):  # causal, depthwise, silu after it
        padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(padded[:, i:i + t] * w[i] for i in range(taps)))

    def group(y, ws):
        wq, wk, wv, cq, ck, cv, w_z, w_b, w_a, a_log, dt_bias, w_o = ws
        q, k = (conv(_mm(x, w), c).reshape(b, t, per, dk) for w, c in ((wq, cq), (wk, ck)))
        v = conv(_mm(x, wv), cv).reshape(b, t, per * rep, dv)
        g = -jnp.exp(a_log) * jax.nn.softplus(_mm(x, w_a) + dt_bias)
        beta = jax.nn.sigmoid(_mm(x, w_b))
        o = _recurrence(l2norm(q), l2norm(k), v, g, beta)
        o = _rms_norm(o, m["o_norm"]["weight"], eps).reshape(b, t, per * rep * dv)
        return y + _mm(o * jax.nn.silu(_mm(x, w_z)), w_o), None

    wide = per * rep
    ws = (columns(wq, per * dk), columns(wk, per * dk), columns(wv, wide * dv),
          columns(cq, per * dk), columns(ck, per * dk), columns(cv, wide * dv),
          columns(m["z_proj"]["kernel"], wide * dv), columns(w_b, wide), columns(w_a, wide),
          m["A_log"].reshape(groups, wide), m["dt_bias"].reshape(groups, wide),
          m["o_proj"]["kernel"].reshape(groups, wide * dv, d))
    y, _ = jax.lax.scan(jax.checkpoint(group), jnp.zeros_like(x), ws)
    return y


def _rotary(x, turned, theta):
    """x (B, T, H, D): the halves of a head's first `turned` entries turned
    by the position, from 0; the rest as they are."""
    t = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, turned, 2, dtype=jnp.float32) / turned))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., :turned // 2], x[..., turned // 2:turned], x[..., turned:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], axis=-1)


def causal_attention(q, k, v):
    """q (B, T, H, D), k and v (B, T, G, D), head h on key-value head h //
    (H / G) -> (B, T, H, D), scores over sqrt(D), in blocks of queries
    (families/_plain.py's, with the control's rounding on the operands of its
    two matmuls)."""
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
        return block(q, 0).reshape(B, T, H, D)
    n = T // QUERY_BLOCK
    if n * QUERY_BLOCK != T:
        raise ValueError(f"sequence {T} is not a multiple of {QUERY_BLOCK}")
    blocks = q.reshape(B, n, QUERY_BLOCK, G, H // G, D).swapaxes(0, 1)
    out = jax.lax.map(lambda xs: jax.checkpoint(block)(xs[0], xs[1]),
                      (blocks, jnp.arange(n) * QUERY_BLOCK))
    return out.swapaxes(0, 1).reshape(B, T, H, D)


def _gated_attention(h, a, sizes):
    B, T, _ = h.shape
    H, G, D = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    turned = D if ROTARY_ALL else int(sizes["partial_rotary_factor"] * D)
    q = _rms_norm(_mm(h, a["wq"]["kernel"]).reshape(B, T, H, D), a["q_norm"]["weight"], eps)
    k = _rms_norm(_mm(h, a["wk"]["kernel"]).reshape(B, T, G, D), a["k_norm"]["weight"], eps)
    v = _mm(h, a["wv"]["kernel"]).reshape(B, T, G, D)
    out = causal_attention(_rotary(q, turned, theta), _rotary(k, turned, theta), v)
    gate = jax.nn.sigmoid(_mm(h, a["wg"]["kernel"]))
    return _mm(out.reshape(B, T, H * D) * gate, a["wo"]["kernel"])


def _swiglu(h, mlp):
    return _mm(jax.nn.silu(_mm(h, mlp["gate"]["kernel"])) * _mm(h, mlp["up"]["kernel"]),
               mlp["down"]["kernel"])


def _routed_mlp(h, moe, sizes, choice):
    """(the held experts' part of the layer, the choice it used)."""
    probs = jax.nn.softmax(_mm(h, moe["router"]["kernel"]), axis=-1)
    if choice is None:
        choice = jax.lax.top_k(probs, sizes["num_experts_per_tok"])[1]
    chosen = jnp.take_along_axis(probs, choice, axis=-1)
    gates = chosen / chosen.sum(-1, keepdims=True)  # over all chosen, held or not

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
    """One block, its mixer's kind told by the parameters it is handed: (x,
    the choice its expert layer used)."""
    eps = sizes["rms_norm_eps"]
    h = _rms_norm(x, blk["attn_norm"]["weight"], eps)
    x = x + (_delta_net(h, blk["gdn"], sizes) if "gdn" in blk
             else _gated_attention(h, blk["attn"], sizes))
    h = _rms_norm(x, blk["mlp_norm"]["weight"], eps)
    y, choice = _routed_mlp(h, blk["moe"], sizes, choice)
    shared = blk["shared"]
    return x + y + jax.nn.sigmoid(_mm(h, shared["token_gate"]["kernel"])) * _swiglu(h, shared), choice


@highest
def _run(x, group, sizes, choice):
    """The blocks in order; (x, the blocks' choices stacked). With a `choice`
    given, its i-th entry is the i-th block's."""
    used = []
    for i in range(len(group)):
        given = None if choice is None else choice[i]
        x, chosen = jax.checkpoint(lambda x, blk, given: block(x, blk, sizes, given))(
            x, group[f"h_{i}"], given)
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
