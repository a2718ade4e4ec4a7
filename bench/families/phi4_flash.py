"""Phi-4-mini-flash-reasoning (microsoft; `model_type` phi4flash; Ren et al.,
"Decoder-Hybrid-Decoder Architecture for Efficient Reasoning with Long
Generation", the SambaY architecture, with differential attention, Ye et
al., "Differential Transformer"): a pre-norm LayerNorm decoder of two halves.
The self-decoder alternates Mamba-1 mixers and window attention; its last
Mamba layer's scan output is handed down the stack as the *memory* m, and
one full-attention layer after it hands down its keys and values; the
cross-decoder alternates gated memory units, which gate m by the present
stream, and cross attention, whose queries read that one K and V. No
positional encoding of any kind. The equations, d the hidden size, x the
stream, L the published layer count, i a layer's published index from 0:

    x0 = E[idx]
    a layer:  x <- x + mixer_i(LN(x));  x <- x + MLP(LN(x))
              MLP(u) = W_down (silu(g) * h),  [g | h] = W_gate_up u    no bias
    logits = E LN(x);  loss = mean cross-entropy                       (E tied)

    i < L/2:   even i Mamba-1, odd i window attention
    i = L/2:   Mamba-1, and m = its y;   i = L/2 + 1: full attention, and
               K, V = its keys and values
    i > L/2+1: even i gated memory unit, odd i cross attention

    Mamba-1 (Gu & Dao 2023), C channels, N states, rank R, K taps:
        [u | z] = W_in x                         d -> 2 C, no bias
        u <- silu(conv(u))                       depthwise, causal, with bias:
                                                 out_t = bias + sum_i w_i in_{t-(K-1)+i}
        [r | B_t | C_t] = W_x u                  C -> R + 2 N, no bias
        Delta = softplus(W_dt r + b_dt)          R -> C
        A = -exp(A_log)                          (C, N)
        h_t = exp(Delta_t A) * h_{t-1} + (Delta_t u_t) B_t^T      (C, N), h_{-1} = 0
        y_t = h_t C_t + D * u_t
        out = W_out (y * silu(z))                the memory is y, before the gate
    gated memory unit:  out = W_2 (m * silu(W_1 x))      W_1 d -> C, W_2 C -> d
    differential attention (window, full and cross alike), heads of 64:
        the 40 query heads are 20 pairs (q1, q2) = heads (2j, 2j+1), the 20
        key heads 10 pairs (k1, k2), the 20 value heads 10 values
        v = [v1 | v2] 128 wide; query pairs 2g, 2g+1 read key-value pair g
        o_j = (softmax(q1 k1^T / 8) - lambda softmax(q2 k2^T / 8)) v
        out = W_o concat_j ((1 - lambda_init) * RMSNorm_128(o_j) * w) + b_o
        lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
        lambda_init = 0.8 - 0.6 exp(-0.3 i)
        self layers: [q | k | v] = W_qkv x + b;  cross layers: q = W_q x + b
        alone, K and V the full layer's; a window layer's query sees itself
        and the 511 keys before it; every mask causal.

**The recurrence is computed as written**, one time step after another
(`_recurrence`: a lax.scan over t nested in checkpointed blocks of _STEPS
steps, families/granite.py's arrangement), not the program's chunking: the
decay differs by channel and state, so there is no chunked matmul form to
share, and a reference that walked the program's chunks would share its
carry's mistakes. Attention is families/_plain.py's blocked pattern with
this family's additions (a window, two maps a pair, a value wider than the
scores are deep), _QUERIES queries at a time so that a block's 40 score maps
over 16,384 keys are 0.17 GB. A Mamba mixer is worked a group of channels at
a time (`_mamba` says how). The MLP and the gated memory unit take their
rows _ROWS at a time under jax.checkpoint: the same values, bounded memory
(a float32 gate_up product of 16,384 rows is 1.3 GB, and the gradient is
taken beside 6.9 GB of training state and 2.3 GB of its own gradients).

Departures from the published code, as the configuration's file lists them
under `assumed`: Mamba-1's sizes by the family's defaults; which of two
adjacent heads is q1; the convolution's taps stored (K, channels); no clamp
on Delta; lambda_init at the published index.

A *layer* of this family, as the harness takes gradients, is all the blocks
(`p_0` with `h_0` ..), as families/lfm2.py says: their structures differ,
and m and K, V cross from block to block inside it. Each block is under
jax.checkpoint.
"""

import math

import jax
import jax.numpy as jnp

from bench.families._plain import highest, next_token_loss

_STEPS = 64    # time steps a checkpointed block of the recurrence
_QUERIES = 64  # queries a checkpointed block of attention
_ROWS = 2048   # rows a checkpointed part of the MLP and the gated memory unit
_GROUPS = 8    # groups of channels a Mamba mixer is worked in

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"

# The controls of bench/tests/phi4_flash_control.py put a known fault into
# this reference and see whether the comparison refuses it. Each is None or
# False in every other use.
OPERAND = None             # f(array): every matmul operand goes through it
RESET_EVERY = None         # the carried state is dropped at every such time step
DECAY = None               # f(array): a step's decay exp(Delta A) goes through it
MEMORY_AFTER_GATE = False  # the memory is y * silu(z), not y
NO_SECOND_LAMBDA = False   # lambda = exp(lq1 . lk1) + lambda_init
NO_DIFF_NORM = False       # no RMSNorm over a pair's 128 values


def _mm(a, b):
    return a @ b if OPERAND is None else OPERAND(a) @ OPERAND(b)


def kinds(sizes):
    """The kind of each kept layer, by its published index."""
    half = sizes["num_hidden_layers_published"] // 2
    out = []
    for i in sizes["layers_kept"]:
        if i <= half:
            out.append(MAMBA if i % 2 == 0 else WINDOW)
        elif i == half + 1:
            out.append(FULL)
        else:
            out.append(GMU if i % 2 == 0 else CROSS)
    return out


def lambda_init(index):
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def build(sizes, compute_dtype):
    from ray_tpu.models.phi4_flash import Phi4FlashConfig

    if (not sizes["tie_word_embeddings"] or sizes["mlp_bias"] or sizes["lm_head_bias"]
            or sizes["hidden_act"] != "silu" or sizes["mb_per_layer"] != 2
            or sizes["embd_pdrop"] or sizes["resid_pdrop"]
            or len(sizes["layers_kept"]) != sizes["num_hidden_layers"]):
        raise ValueError(
            "models/phi4_flash.py: a tied head, no bias in the MLP or the head, silu, one "
            "Mamba layer in two, no dropout, and as many layers as `layers_kept` names")
    return Phi4FlashConfig(
        vocab_size=sizes["vocab_size"], block_size=sizes["max_position_embeddings"],
        n_embd=sizes["hidden_size"], n_layer_published=sizes["num_hidden_layers_published"],
        layers_kept=tuple(sizes["layers_kept"]), n_head=sizes["num_attention_heads"],
        n_kv_head=sizes["num_key_value_heads"], intermediate=sizes["intermediate_size"],
        window=sizes["sliding_window"], ssm_inner=sizes["mamba_d_inner"],
        ssm_state=sizes["mamba_d_state"], ssm_rank=sizes["mamba_dt_rank"],
        ssm_conv=sizes["mamba_d_conv"], ln_eps=sizes["layer_norm_eps"],
        dtype=jnp.dtype(compute_dtype))


def _mixer_matmul_params(sizes):
    d, c = sizes["hidden_size"], sizes["mamba_d_inner"]
    n, r = sizes["mamba_d_state"], sizes["mamba_dt_rank"]
    kv = 2 * sizes["num_key_value_heads"] * (d // sizes["num_attention_heads"])
    self_attention = d * (d + kv) + d * d
    return {MAMBA: d * 2 * c + c * (r + 2 * n) + r * c + c * d,
            WINDOW: self_attention, FULL: self_attention,
            GMU: 2 * d * c, CROSS: 2 * d * d}


def matmul_params(sizes):
    """A Mamba-1 mixer's W_in (d x 2C), W_x (C x (R + 2N)), W_dt (R x C) and
    W_out (C x d); a self-attention layer's W_qkv (d x (d + 2 x kv heads x
    64)) and W_o (d x d); a cross layer's W_q and W_o; the gated memory
    unit's two (d x C); the MLP's W_gate_up (d x 2 ff) and W_down after
    each; the tied matrix once, as the head: the embedding is a look-up. The
    taps, A, D, the norms, the biases and lambda's vectors are in no matmul."""
    mixer = _mixer_matmul_params(sizes)
    d, ff = sizes["hidden_size"], sizes["intermediate_size"]
    return (sum(mixer[kind] + 3 * d * ff for kind in kinds(sizes))
            + sizes["vocab_size"] * d)


def vector_params(sizes):
    """The parameters `matmul_params` leaves out."""
    d, c, n = sizes["hidden_size"], sizes["mamba_d_inner"], sizes["mamba_d_state"]
    width = d // sizes["num_attention_heads"]
    kv = 2 * sizes["num_key_value_heads"] * width
    lam = 4 * width + 2 * width  # four vectors and the norm's weight
    mixer = {MAMBA: c * (sizes["mamba_d_conv"] + 1) + c + c * n + c,
             WINDOW: d + kv + d + lam, FULL: d + kv + d + lam, GMU: 0, CROSS: 2 * d + lam}
    return sum(mixer[kind] + 4 * d for kind in kinds(sizes)) + 2 * d


def flops_per_token(sizes, seq_len):
    """6 x matmul parameters; an attention layer's two maps a pair by the
    model's own shapes, whatever computes them: scores 64 deep over all 40
    query heads (d in all) and a value 128 wide under each of the 40 maps
    (2 d in all), 2 x 3 d multiply-adds a visible key forward and three times
    that with the backward, at a mean of T / 2 keys (full, cross) or of the
    window's (w - w^2 / 2T); the recurrence as the equations need it: the
    decay's product, the update and the read-out, 6 C N a token forward,
    three times that with the backward. exp is not counted."""
    d, t, w = sizes["hidden_size"], seq_len, min(sizes["sliding_window"], seq_len)
    ks = kinds(sizes)
    causal, windowed = t / 2, w - w * w / (2 * t)
    attention = (ks.count(FULL) + ks.count(CROSS)) * causal + ks.count(WINDOW) * windowed
    return int(6 * matmul_params(sizes) + 3 * 2 * 3 * d * attention
               + 18 * sizes["mamba_d_inner"] * sizes["mamba_d_state"] * ks.count(MAMBA))


def layer_names(sizes):
    return ["p_0"]


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _by_rows(fn, *xs):
    """fn over the rows of the xs (B, T, ..) in checkpointed parts of _ROWS."""
    b, t = xs[0].shape[:2]
    if t <= _ROWS or t % _ROWS:
        return fn(*xs)
    parts = [jnp.moveaxis(x.reshape(b, t // _ROWS, _ROWS, *x.shape[2:]), 1, 0) for x in xs]
    out = jax.lax.map(jax.checkpoint(lambda part: fn(*part)), parts)
    return jnp.moveaxis(out, 0, 1).reshape(b, t, *out.shape[3:])


def _recurrence(u, delta, a, bm, cm):
    """u, delta (B, T, C), a (C, N), bm and cm (B, T, N) -> h_t C_t for every
    t, (B, T, C), h_{-1} = 0."""
    b, t, c = u.shape
    n = a.shape[1]
    steps = math.gcd(t, _STEPS)
    if OPERAND is not None:
        u, bm, cm = OPERAND(u), OPERAND(bm), OPERAND(cm)

    def step(state, now):  # state (B, C, N)
        u_t, d_t, b_t, c_t, at = now
        if RESET_EVERY is not None:
            state = jnp.where(at % RESET_EVERY == 0, 0.0, state)
        decay = jnp.exp(d_t[..., None] * a)
        if DECAY is not None:
            decay = DECAY(decay)
        state = decay * state + (d_t * u_t)[..., None] * b_t[:, None, :]
        return state, jnp.einsum("bcn,bn->bc", state, c_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    by_block = lambda v: jnp.moveaxis(v, 1, 0).reshape(t // steps, steps, *v.shape[:1],
                                                       *v.shape[2:])
    xs = (by_block(u), by_block(delta), by_block(bm), by_block(cm),
          jnp.arange(t).reshape(t // steps, steps))
    _, y = jax.lax.scan(block, jnp.zeros((b, c, n), jnp.float32), xs)
    return jnp.moveaxis(y.reshape(t, b, c), 0, 1)


def _mamba(x, m, sizes):
    """(the mixer's output, its scan output y: the memory where this layer is
    the memory's source). The channels are independent of one another from
    W_in's columns to W_out's rows but for W_x, which reads them all: so u is
    made a group of channels at a time, [r | B_t | C_t] from all of it at
    once, and the steps, the recurrence and the gate again a group at a time,
    each group under jax.checkpoint. The groups change no arithmetic; they
    bound what the block's vjp holds (whole, a dozen float32 arrays of 16,384
    x 5,120 stood side by side: 4.1 GiB where 5 were free)."""
    b, t, d = x.shape
    c, n, r = sizes["mamba_d_inner"], sizes["mamba_d_state"], sizes["mamba_dt_rank"]
    taps = sizes["mamba_d_conv"]
    groups = math.gcd(c // 8, _GROUPS)
    per = c // groups
    columns = lambda w: jnp.moveaxis(w.reshape(*w.shape[:-1], groups, per), -2, 0)
    rows = lambda w: w.reshape(groups, per, w.shape[-1])
    w_u, w_z = jnp.split(m["in_proj"]["kernel"], 2, axis=-1)

    def conv(ws):
        w_in, w, bias = ws
        padded = jnp.pad(_mm(x, w_in), ((0, 0), (taps - 1, 0), (0, 0)))
        return jax.nn.silu(bias + sum(padded[:, i:i + t] * w[i] for i in range(taps)))

    def mm_groups(a, w):  # sum over groups of a_g @ w_g
        if OPERAND is not None:
            a, w = OPERAND(a), OPERAND(w)
        return jnp.einsum("gbtp,gpk->btk", a, w)

    u = jax.lax.map(jax.checkpoint(conv), (columns(w_u), columns(m["conv_kernel"]),
                                           m["conv_bias"].reshape(groups, per)))
    rank, bm, cm = jnp.split(mm_groups(u, rows(m["x_proj"]["kernel"])), [r, r + n], axis=-1)

    def group(ws):
        u, w_z, w_dt, b_dt, a_log, skip = ws
        delta = jax.nn.softplus(_mm(rank, w_dt) + b_dt)
        y = _recurrence(u, delta, -jnp.exp(a_log), bm, cm) + skip * u
        return y, y * jax.nn.silu(_mm(x, w_z))

    y, gated = jax.lax.map(jax.checkpoint(group), (
        u, columns(w_z), columns(m["dt_proj"]["kernel"]),
        m["dt_proj"]["bias"].reshape(groups, per), m["A_log"].reshape(groups, per, n),
        m["D"].reshape(groups, per)))
    memory = gated if MEMORY_AFTER_GATE else y
    return (mm_groups(gated, rows(m["out_proj"]["kernel"])),
            jnp.moveaxis(memory, 0, 2).reshape(b, t, c))


def _gmu(x, memory, g):
    return _by_rows(lambda x, memory: _mm(
        memory * jax.nn.silu(_mm(x, g["in_proj"]["kernel"])), g["out_proj"]["kernel"]), x, memory)


def diff_attention(q, k, v, lam, window=None):
    """q (B, T, G, R, 2, D): G key-value pairs, the R query pairs that read
    each, the two of a pair; k (B, T, G, 2, D); v (B, T, G, 2 D) ->
    (softmax(q1 k1^T / sqrt(D)) - lam softmax(q2 k2^T / sqrt(D))) v,
    (B, T, G, R, 2 D), causal, over the last `window` keys (the query's own
    included) where one is given, in blocks of queries."""
    B, T, G, R, _, D = q.shape
    if OPERAND is not None:
        q, k, v = OPERAND(q), OPERAND(k), OPERAND(v)
    key_pos = jnp.arange(T)

    def block(q_blk, start):
        s = jnp.einsum("bqgrad,bkgad->bgraqk", q_blk, k) / math.sqrt(D)
        q_pos = start + jnp.arange(q_blk.shape[1])
        seen = q_pos[:, None] >= key_pos[None, :]
        if window is not None:
            seen = seen & (q_pos[:, None] - key_pos[None, :] < window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bgraqk,bkgv->bqgrav", p if OPERAND is None else OPERAND(p), v)
        return o[..., 0, :] - lam * o[..., 1, :]

    if T <= _QUERIES:
        return block(q, 0)
    n = T // _QUERIES
    if n * _QUERIES != T:
        raise ValueError(f"sequence {T} is not a multiple of {_QUERIES}")
    blocks = q.reshape(B, n, _QUERIES, G, R, 2, D).swapaxes(0, 1)
    out = jax.lax.map(lambda xs: jax.checkpoint(block)(xs[0], xs[1]),
                      (blocks, jnp.arange(n) * _QUERIES))
    return out.swapaxes(0, 1).reshape(B, T, G, R, 2 * D)


def _keys_values(k, v, sizes):
    b, t, _ = k.shape
    pairs = sizes["num_key_value_heads"] // 2
    width = sizes["hidden_size"] // sizes["num_attention_heads"]
    return k.reshape(b, t, pairs, 2, width), v.reshape(b, t, pairs, 2 * width)


def _attention(x, a, kv, index, window, sizes):
    """A differential attention layer on its own keys and values (kv None:
    it makes them, and hands them up) or on those it is given: (out, (K, V))
    with K and V (B, T, kv heads x 64) as the projection wrote them."""
    b, t, d = x.shape
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    width = d // heads
    if kv is None:
        qkv = _mm(x, a["qkv"]["kernel"]) + a["qkv"]["bias"]
        q, k, v = jnp.split(qkv, [d, d + kv_heads * width], axis=-1)
        kv = (k, v)
    else:
        q = _mm(x, a["wq"]["kernel"]) + a["wq"]["bias"]
    k, v = _keys_values(*kv, sizes)
    pairs = kv_heads // 2
    q = q.reshape(b, t, pairs, heads // kv_heads, 2, width)
    init = lambda_init(index)
    second = 0.0 if NO_SECOND_LAMBDA else jnp.exp(jnp.dot(a["lambda_q2"], a["lambda_k2"]))
    lam = jnp.exp(jnp.dot(a["lambda_q1"], a["lambda_k1"])) - second + init
    o = diff_attention(q, k, v, lam, window)
    if not NO_DIFF_NORM:
        o = o * jax.lax.rsqrt(jnp.square(o).mean(-1, keepdims=True)
                              + sizes["layer_norm_eps"]) * a["subln_weight"]
    o = (o * (1.0 - init)).reshape(b, t, d)
    return _mm(o, a["wo"]["kernel"]) + a["wo"]["bias"], kv


def _mlp(x, mlp):
    def rows(u):
        gate, up = jnp.split(_mm(u, mlp["gate_up"]["kernel"]), 2, axis=-1)
        return _mm(jax.nn.silu(gate) * up, mlp["down"]["kernel"])
    return _by_rows(rows, x)


def block(x, memory, kv, blk, kind, index, sizes):
    """One block of kind `kind` at published index `index`: (x, memory, kv),
    the last two as they were unless this block is their source."""
    eps = sizes["layer_norm_eps"]
    half = sizes["num_hidden_layers_published"] // 2
    h = _layer_norm(x, blk["mixer_norm"], eps)
    if kind == MAMBA:
        mixed, y = _mamba(h, blk["mamba"], sizes)
        if index == half:
            memory = y
    elif kind == GMU:
        mixed = _gmu(h, memory, blk["gmu"])
    elif kind == CROSS:
        mixed, _ = _attention(h, blk["cross"], kv, index, None, sizes)
    else:
        mixed, own = _attention(h, blk["attn"], None, index,
                                sizes["sliding_window"] if kind == WINDOW else None, sizes)
        if kind == FULL:
            kv = own
    x = x + mixed
    return x + _mlp(_layer_norm(x, blk["mlp_norm"], eps), blk["mlp"]), memory, kv


@highest
def layer(x, group, sizes):
    b, t, _ = x.shape
    # nothing yet: a reader before its source is refused by `build`
    memory = jnp.zeros((b, t, 0), x.dtype)
    kv = (jnp.zeros((b, t, 0), x.dtype),) * 2
    for i, (kind, index) in enumerate(zip(kinds(sizes), sizes["layers_kept"])):
        run = jax.checkpoint(
            lambda x, memory, kv, blk, kind=kind, index=index:
            block(x, memory, kv, blk, kind, index, sizes))
        x, memory, kv = run(x, memory, kv, group[f"h_{i}"])
    return x


@highest
def embed(outer, idx, sizes):
    return outer["tok_emb"]["embedding"][idx]


@highest
def head_loss(outer, x, targets, sizes):
    x = _layer_norm(x, outer["final_norm"], sizes["layer_norm_eps"])
    return next_token_loss(_mm(x, outer["tok_emb"]["embedding"].T), targets)
