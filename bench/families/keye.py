"""Keye-VL 2.0's language model (Kwai-Keye, 2026; the catalog row's
config.json): a Qwen3-MoE decoder (pre-norm RMSNorm, grouped queries 8 to 1
with heads of their own size and an RMSNorm over each head of q and k, in
every layer a top-8-of-128 SwiGLU expert layer, gates renormalised over the
chosen, no shared expert, no biases) whose every attention layer picks its
keys with a learned indexer (`sa_config`: DeepSeek-V3.2's sparse attention,
which the row's `described_as` names). No vision tower: the row's `config` is
the language model's, and the traffic is text.

One layer, for its normed input h_t = rmsnorm(x_t) at position t. Text only,
so the three `mrope_section` components carry the same position and the
rotary is the plain rotate-half one at `rope_theta`:

    q_{t,h} = rope(rmsnorm(W_q h_t)_h)        32 heads of 128
    k_{t,g} = rope(rmsnorm(W_k h_t)_g)        4 key-value heads, g(h) = h // 8
    v_{t,g} = (W_v h_t)_g
    qI_{t,j} = rope(W_qI h_t)_j               the indexer: 16 heads of 64,
    kI_t = rope(layernorm(W_kI h_t))          one key head of 64,
    w_t = W_w h_t                             a weight a head, in R^16
    I_{t,s} = sum_j w_{t,j} relu(qI_{t,j} . kI_s)
    S_t = the min(2048, t + 1) positions s <= t of largest I_{t,s}, ties to
          the lower position
    o_{t,h} = sum_{s in S_t} softmax_{s in S_t}(q_{t,h} . k_{s,g(h)} / sqrt(128)) v_{s,g(h)}
    x'_t = x_t + W_o o_t
    x''_t = x'_t + sum_{e in top8(p_t)} p_{t,e} / (sum over the 8) expert_e(rmsnorm(x'_t)),
            p_t = softmax(W_r rmsnorm(x'_t)) over all 128 experts, in float32

S_t is a set of integers, so no gradient reaches W_qI, W_kI, W_w: the indexer
reads h under `stop_gradient`, here and in the program. Positive constant
factors on I (DeepSeek scales by the heads' count and width) change no
selection and are left out. What the configuration's file lists under
`assumed`: the per-head q/k RMSNorm (Qwen3-MoE's, whose keys this config
carries); rotary over all 64 of the indexer's dimensions at the layer's
theta; LayerNorm (eps 1e-6, scale and bias) on kI; `q_chunk_size` and
`kv_chunk_size` are the tiling of the indexer's computation and change no
result; no objective of the indexer's own (the config has no key for one).

What a configuration file may cut (bench/configs/keye_vl2_30b_l4_ep8.json):
the layers, the vocabulary, and the experts this program holds, as
bench/families/mellum.py has it: `num_experts` is the count held, experts
`first_expert_held` onward of `num_experts_published`, the router's width.

The choice the harness holds (bench/families/__init__.py) is the experts'.
The selection of keys is each side's own: `selected_keys` gives the
reference's, for the tests and bench/tests/keye_keys.py.

At T = 16,384 the scores of 32 heads are 34 GB, so a layer works a block of
queries at a time. A first pass makes S as a (T, T) array of booleans (each
row's k-th largest value by `lax.top_k`, then the entries above it and the
first of those equal to it); a second, over `jax.checkpoint`ed blocks, takes
the softmax over what S allows. The backward pass reads the same S: a
selection computed twice could differ in a last bit and leave a one-key row
with none. A block sees every key: the blocks bound memory and change no
arithmetic.
"""

import math

import jax
import jax.numpy as jnp

from bench.families._plain import highest, next_token_loss

# Queries a block: 32 heads x 256 x 16,384 float32 scores are 0.5 GiB.
QUERY_BLOCK = 256


def build(sizes, compute_dtype):
    from ray_tpu.models.mellum import INDEXED, MellumConfig

    if sizes["hidden_act"] != "silu" or sizes.get("attention_bias") or \
            sizes.get("tie_word_embeddings") or not sizes["norm_topk_prob"]:
        raise ValueError("models/mellum.py: SwiGLU experts, no biases, untied head, "
                         "gates normalised over the chosen")
    if sizes["decoder_sparse_step"] != 1 or sizes["mlp_only_layers"] or \
            sizes.get("use_sliding_window") or sizes["rope_scaling"]["rope_type"] != "default":
        raise ValueError("models/mellum.py: every MLP an expert layer, no window, plain rotary")
    sa = sizes["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("ops/indexer.py: the indexer has one key head")
    return MellumConfig(
        vocab_size=sizes["vocab_size"], block_size=sizes["max_position_embeddings"],
        n_head=sizes["num_attention_heads"], n_kv_head=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], n_embd=sizes["hidden_size"],
        layer_types=(INDEXED,) * sizes["num_hidden_layers"],
        rope_theta=float(sizes["rope_theta"]), yarn=None, rms_eps=sizes["rms_norm_eps"],
        qk_norm=True, index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        index_top_k=sa["topk"], expert_dim=sizes["moe_intermediate_size"],
        num_experts=sizes["num_experts_published"], top_k=sizes["num_experts_per_tok"],
        first_expert=sizes["first_expert_held"], num_held=sizes["num_experts"],
        dtype=jnp.dtype(compute_dtype))


def index_params(sizes):
    """The indexer's three matrices of one layer; they take no gradient."""
    sa = sizes["sa_config"]
    return sizes["hidden_size"] * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                                   + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def matmul_params(sizes):
    """The matrices on the gradient's path, as bench/families/mellum.py
    counts them: q, o, k, v, the router, the expert matrices a token meets
    at even routing (experts-per-token x held / published of them), the
    untied head. The indexer's are counted apart (`flops_per_token`)."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    attn = 2 * d * sizes["num_attention_heads"] * hd + 2 * d * sizes["num_key_value_heads"] * hd
    router = d * sizes["num_experts_published"]
    experts = (sizes["num_experts_per_tok"] * sizes["num_experts"]
               / sizes["num_experts_published"] * 3 * d * sizes["moe_intermediate_size"])
    return int(sizes["num_hidden_layers"] * (attn + router + experts)
               + sizes["vocab_size"] * d)


def flops_per_token(sizes, seq_len):
    """The model's own work, not an implementation's: 6 x matmul parameters
    on the gradient's path; attention over the selected keys, 12 x heads x
    head_dim x the keys a query sees on average, k - k^2/(2T) where k < T
    (T/2 else); the indexer forward only, 2 x its matrices + 2 x heads x
    width x T/2 of scores. What a kernel computes of pairs the selection
    leaves out counts for nothing. At the published widths, 16 of 128
    experts, 4 layers, V = 18,992, T = 16,384: 6 x (4 x (18.87 M + 0.26 M +
    8 x 16/128 x 4.72 M) + 38.9 M) = 0.806 G, + 4 x 12 x 4096 x 1920 = 0.377
    G, + 4 x (2 x 2.26 M + 16.8 M) = 0.085 G."""
    k, layers = sizes["sa_config"]["topk"], sizes["num_hidden_layers"]
    keys = seq_len / 2 if k >= seq_len else k - k * k / (2 * seq_len)
    width = sizes["num_attention_heads"] * sizes["head_dim"]
    sa = sizes["sa_config"]
    index = 2 * index_params(sizes) + sa["indexer_num_heads"] * sa["indexer_head_dim"] * seq_len
    return int(6 * matmul_params(sizes) + layers * (12 * width * keys + index))


def layer_names(sizes):
    return [f"h_{i}" for i in range(sizes["num_hidden_layers"])]


# What each matmul does to an operand before it multiplies: nothing. The
# control of bench/tests/keye_control.py puts a rounding to a lower precision
# here (OPERAND: every matmul; INDEX_OPERAND: the indexer's alone), to show
# what the comparison refuses.
OPERAND = None
INDEX_OPERAND = None


def _mm(a, b, operand=None):
    operand = operand or OPERAND
    return a @ b if operand is None else operand(a) @ operand(b)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * weight


def _layer_norm(x, scale, bias, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x, theta):
    """x (B, T, H, D): rotate pairs (i, i + D/2) by pos * theta^(-2i/D)."""
    T, D = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _index_operands(h, blk, sizes):
    """(qI (B, T, J, E), kI (B, T, E), w (B, T, J)) from the normed input,
    which takes no gradient from here."""
    sa, theta = sizes["sa_config"], float(sizes["rope_theta"])
    B, T, _ = h.shape
    J, E = sa["indexer_num_heads"], sa["indexer_head_dim"]
    h, ix = jax.lax.stop_gradient(h), blk["indexer"]
    mm = lambda a, b: _mm(a, b, INDEX_OPERAND)  # or OPERAND, where that is set
    q = _rope(mm(h, ix["wq"]["kernel"]).reshape(B, T, J, E), theta)
    k = _layer_norm(mm(h, ix["wk"]["kernel"]), ix["k_norm"]["scale"], ix["k_norm"]["bias"])
    k = _rope(k[:, :, None, :], theta)[:, :, 0, :]
    return q, k, mm(h, ix["ww"]["kernel"])


def _index_scores(q_blk, w_blk, k, start):
    """I of a block of queries against every key, (B, Q, T); -inf where the
    key lies after the query."""
    operand = INDEX_OPERAND or OPERAND
    if operand is not None:
        q_blk, k = operand(q_blk), operand(k)
    s = jnp.einsum("bqje,bse->bqjs", q_blk, k)
    scores = (w_blk[..., None] * jax.nn.relu(s)).sum(2)
    ahead = (start + jnp.arange(q_blk.shape[1]))[:, None] - jnp.arange(k.shape[1])[None, :]
    return jnp.where(ahead >= 0, scores, -jnp.inf)


def _in_blocks(fn, arrays, T):
    """fn(block of each array along axis 1, the block's first position) over
    blocks of QUERY_BLOCK queries, results joined along axis 1."""
    if T <= QUERY_BLOCK:
        return fn(arrays, 0)
    n = T // QUERY_BLOCK
    if n * QUERY_BLOCK != T:
        raise ValueError(f"sequence {T} is not a multiple of {QUERY_BLOCK}")
    split = lambda a: a.reshape(a.shape[0], n, QUERY_BLOCK, *a.shape[2:]).swapaxes(0, 1)
    join = lambda a: a.swapaxes(0, 1).reshape(a.shape[1], T, *a.shape[3:])
    out = jax.lax.map(lambda xs: fn(xs[0], xs[1]),
                      (jax.tree.map(split, arrays), jnp.arange(n) * QUERY_BLOCK))
    return jax.tree.map(join, out)


def _selected(index, top_k):
    """(B, T, T) bool, entry [t, s]: s is in S_t. Of each query's row of I:
    its k-th largest value, k = min(top_k, t + 1), by `lax.top_k` of the
    masked row; the entries above it, and of those equal to it (the k-th
    itself, and any tie) the first by position until the row has k."""
    q, k, w = index
    T = k.shape[1]

    def block(xs, start):
        scores = _index_scores(xs[0], xs[1], k, start)
        want = jnp.minimum(start + jnp.arange(scores.shape[1]) + 1, top_k)
        kth = jnp.take_along_axis(
            jax.lax.top_k(scores, min(top_k, T))[0],
            jnp.broadcast_to((want - 1)[None, :, None], scores.shape[:2] + (1,)), 2)
        above, tied = scores > kth, scores == kth
        short = want[None, :, None] - above.sum(-1, keepdims=True)
        return above | (tied & (jnp.cumsum(tied, -1) <= short))

    return _in_blocks(block, (q, w), T)


def selected_attention(q, k, v, seen):
    """q (B, T, H, D), k and v (B, T, G, D), head h reads key-value head
    h // (H/G); query t attends to the keys s with seen[t, s] alone."""
    B, T, H, D = q.shape
    G = k.shape[2]
    if OPERAND is not None:
        q, k, v = OPERAND(q), OPERAND(k), OPERAND(v)
    q = q.reshape(B, T, G, H // G, D)

    def block(xs, start):
        q_blk, seen_blk = xs
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q_blk, k) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(seen_blk[:, None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p if OPERAND is None else OPERAND(p), v)

    return _in_blocks(jax.checkpoint(block), (q, seen), T).reshape(B, T, H, D)


def _qkv(x, blk, sizes):
    B, T, _ = x.shape
    H, G, D = (sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"])
    eps, theta, attn = sizes["rms_norm_eps"], float(sizes["rope_theta"]), blk["attn"]
    h = _rms_norm(x, blk["attn_norm"]["weight"], eps)
    q = _rms_norm(_mm(h, attn["wq"]["kernel"]).reshape(B, T, H, D), attn["q_norm"]["weight"], eps)
    k = _rms_norm(_mm(h, attn["wk"]["kernel"]).reshape(B, T, G, D), attn["k_norm"]["weight"], eps)
    v = _mm(h, attn["wv"]["kernel"]).reshape(B, T, G, D)
    return h, _rope(q, theta), _rope(k, theta), v


def _attend(x, blk, sizes):
    B, T, _ = x.shape
    h, q, k, v = _qkv(x, blk, sizes)
    seen = _selected(_index_operands(h, blk, sizes), sizes["sa_config"]["topk"])
    out = selected_attention(q, k, v, jax.lax.stop_gradient(seen))
    return x + _mm(out.reshape(B, T, -1), blk["attn"]["wo"]["kernel"])


@highest
def selected_keys(x, blk, sizes):
    """(B, T, T) bool: entry [t, s], query t of this layer attends to key s."""
    h = _rms_norm(x, blk["attn_norm"]["weight"], sizes["rms_norm_eps"])
    return _selected(_index_operands(h, blk, sizes), sizes["sa_config"]["topk"])


def _route(x, blk, sizes):
    """(the expert layer's input, every expert's probability) of a token."""
    h = _rms_norm(x, blk["moe_norm"]["weight"], sizes["rms_norm_eps"])
    return h, jax.nn.softmax(_mm(h, blk["moe"]["router"]["kernel"]), axis=-1)


@highest
def choice(x, blk, sizes):
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
        # every token through expert e, weighted by the gate of the tokens
        # that chose it and by zero for the rest
        weight = jnp.where(choice == sizes["first_expert_held"] + e, gates, 0.0).sum(-1)
        out = _mm(jax.nn.silu(_mm(h, moe["gate"][e])) * _mm(h, moe["up"][e]), moe["down"][e])
        return y + weight[..., None] * out, None

    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                        jnp.arange(sizes["num_experts"]))
    return x + y, 0.0


@highest
def embed(outer, idx, sizes):
    return outer["tok_emb"]["embedding"][idx]


@highest
def head_loss(outer, x, targets, sizes):
    x = _rms_norm(x, outer["final_norm"]["weight"], sizes["rms_norm_eps"])
    return next_token_loss(_mm(x, outer["lm_head"]["kernel"]), targets)
