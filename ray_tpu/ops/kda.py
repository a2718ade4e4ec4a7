"""Kimi Delta Attention's recurrence (Kimi Linear, arXiv:2510.26692 section 3:
a gated delta rule whose decay differs by channel of the key) in three
forms: the recurrence itself, its chunked form in jax.numpy, and a pallas
kernel pair on a TPU.

A head keeps a (K, V) state. With q_t, k_t (K), v_t (V), a log decay g_t (K,
<= 0, float32; alpha_t = exp(g_t)) and a rate beta_t in (0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(K)                                   S_0 = 0

`kda_plain` is that, a `lax.scan` over t in float32.

The chunked form (the WY / UT form of the delta rule, with the decays kept
by channel). Cut T into chunks of C steps; G is the cumulative sum of g
inside a chunk (C, K; falling), S the state at the chunk's start. With
u_t = beta_t (v_t - k_t^T Diag(alpha_t) S_{t-1}) the recurrence is
S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T, so inside a chunk

    A_kk[i, j] = sum_c k_ic k_jc exp(G_ic - G_jc)    j < i, else 0
    A_qk[i, j] = sum_c q_ic k_jc exp(G_ic - G_jc)    j <= i, else 0
    Tm = (I + Diag(beta) A_kk)^-1                    unit lower triangular:
                                                     the rows of a chunk
                                                     depend on one another
    [W | U] = Tm Diag(beta) [k * exp(G) | v]
    Vn = U - W S                                     the u_t of the chunk
    O  = ((q * exp(G)) S + A_qk Vn) / sqrt(K)
    S' = Diag(exp(G_C)) S + (k * exp(G_C - G))^T Vn

**No factor is ever the exp of something positive.** exp(G_i - G_j) does not
factor through exp(-G_j) over a chunk (a strong decay overflows float32), so
A_kk and A_qk are summed over levels of blocks: at the level of blocks of b
rows (b = 2, 4, .. C) the pairs whose row lies in the second half of a block
and whose column lies in its first half are computed against the first
half's last row r: exp(G_i - G_j) = exp(G_i - G_r) exp(G_r - G_j), both
factors at most 1, so one matmul of (q * E)(k * E)^T with E = exp(-|G - G_r|)
serves all the level's blocks at once (masked to them); every pair j < i
belongs to exactly one level, and the diagonal of A_qk is q_i . k_i. The
inverse is made the same way, block by block: with the halves of a block
inverted, [[A, 0], [C, D]]^-1 = [[A^-1, 0], [-D^-1 C A^-1, D^-1]], which over
all blocks of a level is Tm <- Tm - Tm N Tm with N the level's part of
Diag(beta) A_kk. Nothing is left out and no decay is clamped.

Precision: g, G, every exp, A_kk, A_qk, the inverse and the carried state
are float32. Matmul operands are in q's dtype (bf16 from the models; q * E,
Tm, W, Vn and the state are rounded once where they become one) and every
matmul sums in float32; the inverse's matmuls take float32 operands (in the
kernels as three bf16 passes: hi hi + lo hi + hi lo).

The kernels, `kda_fwd` and `kda_bwd` (the names the compiled step and the
profiler's trace show; bench/layer_metrics/kda_* find them by these): a grid
of (batch, heads, chunks), the chunk axis in order, a head's state in a VMEM
scratch handed from chunk to chunk as ops/ssd.py's is, *transposed*, (V, K):
its decay then runs along the lanes. The forward writes the state at each
chunk's start (T/C x H x V x K float32), the one residual the backward needs
beyond the inputs, and the state after the last chunk. The backward walks the
chunks last to first with the state's cotangent in the scratch, makes the
chunk's intermediates again and gives dq, dk, dv, dG and dbeta. The decay's
gradient needs no derivative of the levels' factors: a term q_ic k_jc
exp(G_ic - G_jc) moves with G_ic as it does with log q_ic and against G_jc
as with log k_jc, so dG = q * dq + k * dk_as_row - k * dk_as_column over
the terms that carry a decay.

The cumulative sum is made inside a chunk's step (log2 C rotations of the
rows), and its transpose carries dG back to dg there, so neither G nor dG is
an array in HBM. `kda_gated` goes one step further back: the kernels read the
gate's pre-activation f in the compute dtype with a head's rate a = exp(A_log)
and the channels' dt_bias, make g = -a softplus(f + dt_bias) in float32 where
they use it and give df, da and ddt_bias (the last two summed over a
sequence's chunks in an output block that stays in VMEM), so no float32 array
of g's size is written either: at (2, 8192, 32, 128) each is 268 MB, and a
block's backward held four of them. Told `l2_eps`, `kda_gated` takes q and k
as the layer's convolution wrote them, before the norm of a head (`l2norm`:
u / sqrt(sum of the head's squares + eps), float32, rounded once to the
compute dtype), and the kernels make that norm too: a grid step's q and k
blocks are (C, 128) with one head's channels on the lanes, so the sum is a
lane sum of what the step already holds in float32, the rounding is where
XLA's was (the forward is that of the norm in jax.numpy and then the kernels,
bit for bit in interpret mode), and the backward kernel ends with the norm's
vjp on its float32 dq and dk, du = r (dn - n sum(dn n)) with r the rsqrt and
n = u r unrounded, so dq and dk come out as the gradients of what came in.
XLA normed on (B, T, H, 128) in float32, which under the TPU's tiling is no
bitcast of the (B, T, H x 128) the convolution writes and the kernels read:
seven float32 relayouts and passes a layer and pass, 105 of 754 ms of a step
of the benchmark's cell (PERF.md section 6, PR 55). `kda` itself takes q and
k normed, as `kda_plain` and `kda_chunked` do. The kernels take heads whose K
and V are 128 (a vector's lanes) and T padded to whole chunks (k = 0,
beta = 0, g = 0 leave a state as it was, and a k of zeros norms to zeros);
any other shape, and any backend but a TPU, runs `kda_chunked`,
differentiated by JAX, the norms in jax.numpy before it.

Not here (PERF.md section 7): a state reset at a packed document's boundary,
an initial state handed in, the pair under a mesh.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _on_tpu  # a worker that cannot reach its chip fails there

_LANES = 128
CHUNK = 64  # steps a chunk: six levels of blocks (bench/shape_functions/kda.py reads it off a call)

_NT = (((1,), (1,)), ((), ()))  # (m, c) x (n, c) -> (m, n)
_NN = (((1,), (0,)), ((), ()))  # (m, c) x (c, n) -> (m, n)
_TN = (((0,), (0,)), ((), ()))  # (c, m) x (c, n) -> (m, n)
_F32 = jnp.float32


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _dot_highest(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _dot_3pass(a, b, dims):
    """A float32 product from three bf16 passes (hi hi + lo hi + hi lo): what
    the inverse's matmuls take in the kernels."""
    bf = jnp.bfloat16
    ah, bh = a.astype(bf), b.astype(bf)
    al, bl = (a - ah.astype(_F32)).astype(bf), (b - bh.astype(_F32)).astype(bf)
    return _dot(ah, bh, dims) + _dot(al, bh, dims) + _dot(ah, bl, dims)


# --------------------------------------------------------------------------
# the recurrence
# --------------------------------------------------------------------------


def kda_plain(q, k, v, g, beta, steps=64):
    """The recurrence step by step in float32: q, k, g (b, T, H, K), v (b, T,
    H, V), beta (b, T, H). Returns o (b, T, H, V) float32 and the state after
    the last step, (b, H, K, V). The scan is nested, blocks of `steps` steps
    under jax.checkpoint, so that its gradient keeps T / steps + steps states
    and not T: the same values."""
    q, k, v, g, beta = (a.astype(_F32) for a in (q, k, v, g, beta))
    b, t, h, kd = q.shape
    scale = kd ** -0.5
    steps = math.gcd(t, steps)

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # (b, H, ..)
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S) * scale

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(step, S, xs)

    by_block = lambda a: a.swapaxes(0, 1).reshape(t // steps, steps, b, *a.shape[2:])
    with jax.default_matmul_precision("highest"):
        S, o = jax.lax.scan(block, jnp.zeros((b, h, kd, v.shape[-1]), _F32),
                            tuple(by_block(a) for a in (q, k, v, g, beta)))
    return o.reshape(t, b, h, -1).swapaxes(0, 1), S


# --------------------------------------------------------------------------
# one chunk of one head, as both the jax.numpy form and the kernels run it
# --------------------------------------------------------------------------


def _levels(c):
    return tuple(2 ** n for n in range(1, c.bit_length()))


def _ref_rows(G, b, roll):
    """On every row of a block of b rows, the block's row b/2 - 1: the last
    row of its first half."""
    c, h = G.shape[0], b // 2
    if h >= 8:  # whole tiles of sublanes: a row of each block, spread over it
        parts = [jnp.broadcast_to(G[m * b + h - 1:m * b + h], (b, G.shape[1]))
                 for m in range(c // b)]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    pos = jax.lax.broadcasted_iota(jnp.int32, G.shape, 0) & (b - 1)
    out = G
    for s in range(1, h + 1):  # the second half's rows: s rows back
        out = jnp.where(pos == h - 1 + s, roll(G, s), out)
    for s in range(1, h):  # the first half's: s rows on
        out = jnp.where(pos == h - 1 - s, roll(G, c - s), out)
    return out


def _cumsum_rows(g, roll):
    """The running sum down the rows, log2 C rotations."""
    c = g.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    s = 1
    while s < c:
        g = g + jnp.where(row >= s, roll(g, s), 0.0)
        s *= 2
    return g


def _cumsum_rows_transposed(d, roll):
    """The running sum up the rows: `_cumsum_rows`' transpose."""
    c = d.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
    s = 1
    while s < c:
        d = d + jnp.where(row < c - s, roll(d, c - s), 0.0)
        s *= 2
    return d


def _level_mask(c, b):
    """(c, c): row in the second half of a block of b, column in its first."""
    ri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lb = b.bit_length() - 1
    return (((ri >> lb) == (ci >> lb)) & (((ri >> (lb - 1)) & 1) == 1)
            & (((ci >> (lb - 1)) & 1) == 0))


def _diagonal(c):
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _level_operands(qf, kf, G, b, roll, dtype):
    E = jnp.exp(-jnp.abs(G - _ref_rows(G, b, roll)))
    return E, (qf * E).astype(dtype), (kf * E).astype(dtype)


def _intra(q, k, G, roll):
    """A_qk (its diagonal and below) and A_kk (below its diagonal), float32,
    summed over the levels."""
    c, dtype = q.shape[0], q.dtype
    qf, kf = q.astype(_F32), k.astype(_F32)
    a_qk = jnp.where(_diagonal(c), _dot(q, k, _NT), 0.0)
    a_kk = jnp.zeros((c, c), _F32)
    for b in _levels(c):
        _, qe, ke = _level_operands(qf, kf, G, b, roll, dtype)
        both = _dot(jnp.concatenate([qe, ke], axis=0), ke, _NT)
        mask = _level_mask(c, b)
        a_qk = a_qk + jnp.where(mask, both[:c], 0.0)
        a_kk = a_kk + jnp.where(mask, both[c:], 0.0)
    return a_qk, a_kk


def _inverse(M, exact):
    """(I + M)^-1 of M below the diagonal, float32, block by block."""
    c = M.shape[0]
    levels = _levels(c)
    Tm = jnp.where(_diagonal(c), 1.0, 0.0) - jnp.where(_level_mask(c, levels[0]), M, 0.0)
    for b in levels[1:]:
        N = jnp.where(_level_mask(c, b), M, 0.0)
        Tm = Tm - exact(exact(Tm, N, _NN), Tm, _NN)
    return Tm


class Decay(NamedTuple):
    """What of a chunk follows the decay's kind. By channel (this file's): the
    products inside a chunk summed over the levels. One number a head and
    step (ops/gdn.py): g comes with that number on every lane, so G, exp(G)
    and every product with them are what they are here, and the three below
    are its own.

    intra(q, k, G, roll) -> (A_qk, A_kk), float32.
    over(x): of x (rows, K), the decay's gradient channel by channel, what
    the decay's own numbers take (by channel: x).
    pull(q, k, p, (ri, ci), dA_qk, dA_kk, dq, dk, dG, roll) -> (dq, dk, dG)
    with `intra`'s cotangents added: p `_chunk_parts`' dict, ri and ci the
    (C, C) row and column numbers."""

    intra: Callable
    over: Callable
    pull: Callable


def _levels_pulled(q, k, p, at, dA_qk, dA_kk, dq, dk, dG, roll):
    """`_intra`'s cotangents: the diagonal of A_qk, then level by level."""
    c, dtype = q.shape[0], q.dtype
    qf, kf, G, (ri, ci) = p["qf"], p["kf"], p["G"], at
    on_diag = jnp.where(ri == ci, dA_qk, 0.0).astype(dtype)
    dq = dq + _dot(on_diag, k, _NN)
    dk = dk + _dot(on_diag, q, _TN)
    for b in _levels(c):
        E, qe, ke = _level_operands(qf, kf, G, b, roll, dtype)
        mask = _level_mask(c, b)
        Pq = jnp.where(mask, dA_qk, 0.0).astype(dtype)
        Pk = jnp.where(mask, dA_kk, 0.0).astype(dtype)
        rows = _dot(jnp.concatenate([Pq, Pk], axis=0), ke, _NN)  # (2 c, K): as rows
        dqe, dke_row = rows[:c], rows[c:]
        dke_col = _dot(jnp.concatenate([Pq, Pk], axis=0), jnp.concatenate([qe, ke], axis=0), _TN)
        dq = dq + dqe * E
        dk = dk + (dke_row + dke_col) * E
        # the rounded operands, so that a pair's two shares are one number
        # with two signs: the running sum up the rows then cancels them
        # outside (j, i] to float32's last bits, where q E and k E unrounded
        # leave a bf16 rounding's worth of every pair on every earlier row
        dG = dG + dqe * qe.astype(_F32) + (dke_row - dke_col) * ke.astype(_F32)
    return dq, dk, dG


BY_CHANNEL = Decay(_intra, lambda x: x, _levels_pulled)


def _chunk_parts(q, k, v, g, beta, St, scale, roll, exact, decay=BY_CHANNEL):
    """What both passes make of a chunk: everything up to Vn."""
    dtype = q.dtype
    G = _cumsum_rows(g, roll)
    kd = k.shape[1]
    qf, kf, vf = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    a_qk, a_kk = decay.intra(q, k, G, roll)
    Tm = _inverse(beta * a_kk, exact)
    eG = jnp.exp(G)
    Kg = kf * eG
    X = jnp.concatenate([(beta * Kg).astype(dtype), (beta * vf).astype(dtype)], axis=1)
    Y = _dot(Tm.astype(dtype), X, _NN)  # [W | U]
    W = Y[:, :kd].astype(dtype)
    Sd = St.astype(dtype)
    Vn = Y[:, kd:] - _dot(W, Sd, _NT)
    Qg = qf * eG * scale
    eL = jnp.exp(G[-1:] - G)
    return dict(G=G, qf=qf, kf=kf, vf=vf, a_qk=a_qk, a_kk=a_kk, Tm=Tm, eG=eG, Kg=Kg, Y=Y, W=W,
                Sd=Sd, Vn=Vn, Qg=Qg, eL=eL, Kend=(kf * eL).astype(dtype))


def _chunk_fwd(q, k, v, g, beta, St, scale, roll, exact, decay=BY_CHANNEL):
    """One chunk of one head: q, k (C, K) and v (C, V) in the compute dtype,
    the log decays g (C, K) float32, beta (C, 1) float32, St the transposed
    state (V, K) float32 at the chunk's start. Returns o (C, V) float32 and
    the state at its end."""
    p = _chunk_parts(q, k, v, g, beta, St, scale, roll, exact, decay)
    dtype, G = q.dtype, p["G"]
    Vn = p["Vn"].astype(dtype)
    o = _dot(p["Qg"].astype(dtype), p["Sd"], _NT) + _dot(
        (p["a_qk"] * scale).astype(dtype), Vn, _NN)
    return o, jnp.exp(G[-1:]) * St + _dot(Vn, p["Kend"], _TN)


def _chunk_bwd(q, k, v, g, beta, St, do, dSt, scale, roll, exact, decay=BY_CHANNEL):
    """The chunk's cotangents from do (C, V) and the cotangent dSt (V, K) of
    the state at its end: dq, dk, dv, dg, dbeta in float32, and the cotangent
    of the state at its start."""
    p = _chunk_parts(q, k, v, g, beta, St, scale, roll, exact, decay)
    c, kd = k.shape
    dtype, G = q.dtype, p["G"]
    kf, vf, eG, eL, Sd = p["kf"], p["vf"], p["eG"], p["eL"], p["Sd"]
    do = do.astype(dtype)
    dSd = dSt.astype(dtype)
    Vn = p["Vn"].astype(dtype)
    Qg = p["Qg"].astype(dtype)
    a_qs = (p["a_qk"] * scale).astype(dtype)
    ri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    # o = Qg S + a_qs Vn;  S' = exp(G_C) S + Kend^T Vn
    dVn = _dot(a_qs, do, _TN) + _dot(p["Kend"], dSd, _NT)
    dA_qk = jnp.where(ri >= ci, _dot(do, Vn, _NT), 0.0) * scale
    dQg = _dot(do, Sd, _NN)
    dKend = _dot(Vn, dSd, _NN)
    dVn_d = dVn.astype(dtype)
    dS_start = (jnp.exp(G[-1:]) * dSt + _dot(do, Qg, _TN) - _dot(dVn_d, p["W"], _TN))
    # Vn = U - W S;  [W | U] = Tm X
    dY = jnp.concatenate([-_dot(dVn_d, Sd, _NN), dVn], axis=1).astype(dtype)
    Tm = p["Tm"].astype(dtype)
    dX = _dot(Tm, dY, _TN)
    dM = jnp.where(ri > ci, -_dot(dX.astype(dtype), p["Y"].astype(dtype), _NT), 0.0)
    dA_kk = beta * dM
    dKg = beta * dX[:, :kd]
    dv = beta * dX[:, kd:]
    dbeta = (jnp.sum(dM * p["a_kk"], axis=1, keepdims=True)
             + jnp.sum(dX[:, :kd] * p["Kg"] + dX[:, kd:] * vf, axis=1, keepdims=True))
    # the three decayed copies of q and k
    at_end = dKend * kf * eL
    dq = dQg * eG * scale
    dk = dKg * eG + dKend * eL
    dG = decay.over(dQg * p["Qg"] + dKg * p["Kg"] - at_end)
    d_last = decay.over(jnp.sum(at_end, axis=0, keepdims=True)
                        + jnp.exp(G[-1:]) * jnp.sum(St * dSt, axis=0, keepdims=True))
    # the products inside the chunk, A_qk and A_kk
    dq, dk, dG = decay.pull(q, k, p, (ri, ci), dA_qk, dA_kk, dq, dk, dG, roll)
    last = jax.lax.broadcasted_iota(jnp.int32, dG.shape, 0) == c - 1
    dG = dG + jnp.where(last, d_last, 0.0)
    return dq, dk, dv, _cumsum_rows_transposed(dG, roll), dbeta, dS_start


# --------------------------------------------------------------------------
# the chunked form in jax.numpy
# --------------------------------------------------------------------------


def _np_roll(x, s):
    return jnp.roll(x, s, axis=0)


def kda_chunked(q, k, v, g, beta, chunk, decay=BY_CHANNEL):
    """The chunked form chunk by chunk, differentiated by JAX: what runs
    where there is no TPU, and what the kernels are tested against. Returns
    o (b, T, H, V) in v's dtype, the float32 states at each chunk's start,
    transposed, (b, T/chunk, H, V, K), and the state after the last chunk,
    (b, H, V, K)."""
    b, t, h, kd = q.shape
    vd, nc = v.shape[-1], t // chunk
    scale = kd ** -0.5
    by_chunk = lambda a: a.reshape(b, nc, chunk, h, -1).transpose(1, 0, 3, 2, 4)
    one = functools.partial(_chunk_fwd, scale=scale, roll=_np_roll, exact=_dot_highest,
                            decay=decay)
    many = jax.vmap(jax.vmap(one))  # over batch and heads

    def step(St, xs):
        o, nxt = many(*xs, St)
        return nxt, (o, St)

    final, (o, states) = jax.lax.scan(
        step, jnp.zeros((b, h, vd, kd), _F32),
        tuple(by_chunk(a) for a in (q, k, v, g.astype(_F32), beta.astype(_F32)[..., None])))
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, t, h, vd).astype(v.dtype)
    return o, states.swapaxes(0, 1), final


# --------------------------------------------------------------------------
# the kernels
# --------------------------------------------------------------------------


def _tpu_roll(x, s):
    return pltpu.roll(x, s, 0)


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _log_decay(g_ref, a_ref, dtb_ref, gated):
    """(g, x) of a chunk: g as it was handed in, or made from the gate's
    pre-activation, g = -a softplus(x), x = f + dt_bias."""
    if not gated:
        return g_ref[0], None
    x = g_ref[0].astype(_F32) + dtb_ref[...]
    return -a_ref[...] * _softplus(x), x


def _normed(u, eps):
    """(n, r) of u whose last axis is one head: r = rsqrt(the sum of the
    head's squares + eps) and n = u r, both float32."""
    uf = u.astype(_F32)
    r = jax.lax.rsqrt(jnp.sum(uf * uf, axis=-1, keepdims=True) + eps)
    return uf * r, r


def l2norm(u, eps):
    """u over the root of (the sum of its last axis' squares + eps), in
    float32 and rounded once to u's dtype: the layer's norm of a head's q and
    k (fla's l2norm), as the kernels make it of a block and as `kda_gated`
    makes it where they do not run."""
    return _normed(u, eps)[0].astype(u.dtype)


def _norm_pulled(dn, u, eps):
    """`l2norm`'s vjp at u on the float32 cotangent dn of the normed block,
    from n and r before n's rounding."""
    nf, r = _normed(u, eps)
    return r * (dn - nf * jnp.sum(dn * nf, axis=-1, keepdims=True))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, a_ref, dtb_ref, beta_ref, o_ref, st_ref, fin_ref,
                s_acc, *, scale, gated, l2_eps):
    """One chunk of one head: o of the chunk, the state at its start written
    out, the state at its end left in s_acc. With `l2_eps` q and k come as the
    convolution wrote them and are normed here."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        s_acc[...] = jnp.zeros(s_acc.shape, s_acc.dtype)

    St = s_acc[...]
    st_ref[0, 0] = St
    g, _ = _log_decay(g_ref, a_ref, dtb_ref, gated)
    q, k = q_ref[0], k_ref[0]
    if l2_eps is not None:
        q, k = l2norm(q, l2_eps), l2norm(k, l2_eps)
    o, nxt = _chunk_fwd(q, k, v_ref[0], g, beta_ref[0, 0], St, scale, _tpu_roll, _dot_3pass)
    o_ref[0] = o.astype(o_ref.dtype)
    s_acc[...] = nxt

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        fin_ref[0] = nxt


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, a_ref, dtb_ref, beta_ref, do_ref, st_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, da_ref, ddtb_ref, dbeta_ref, ds_acc,
                *, scale, gated, l2_eps):
    """One chunk of one head, chunks last to first: the state's cotangent at
    the chunk's end in ds_acc on entry and at its start on exit; the rate's
    and dt_bias's gradients summed over the chunks in their output blocks.
    With `l2_eps` q and k are normed here and dq, dk are the gradients of what
    came in."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        ds_acc[...] = jnp.zeros(ds_acc.shape, ds_acc.dtype)
        da_ref[...] = jnp.zeros(da_ref.shape, da_ref.dtype)
        ddtb_ref[...] = jnp.zeros(ddtb_ref.shape, ddtb_ref.dtype)

    g, x = _log_decay(g_ref, a_ref, dtb_ref, gated)
    q, k = q_ref[0], k_ref[0]
    if l2_eps is not None:
        q, k = l2norm(q, l2_eps), l2norm(k, l2_eps)
    dq, dk, dv, dg, dbeta, d_start = _chunk_bwd(
        q, k, v_ref[0], g, beta_ref[0, 0], st_ref[0, 0], do_ref[0], ds_acc[...], scale,
        _tpu_roll, _dot_3pass)
    if l2_eps is not None:
        dq, dk = _norm_pulled(dq, q_ref[0], l2_eps), _norm_pulled(dk, k_ref[0], l2_eps)
    dq_ref[0], dk_ref[0] = dq.astype(dq_ref.dtype), dk.astype(dk_ref.dtype)
    dv_ref[0], dbeta_ref[0, 0] = dv.astype(dv_ref.dtype), dbeta
    ds_acc[...] = d_start
    if gated:
        # g = -a softplus(x): dg/dx = -a sigmoid(x), dg/da = g / a
        dx = dg * -a_ref[...] / (1.0 + jnp.exp(-x))
        dg_ref[0] = dx.astype(dg_ref.dtype)
        ddtb_ref[0] += jnp.sum(dx, axis=0, keepdims=True)
        da_ref[0] += jnp.sum(dg * g, axis=0, keepdims=True) / a_ref[...]
    else:
        dg_ref[0] = dg


def _specs(chunks, chunk, kd, vd, reverse):
    """Block specs by grid (batch, head, chunk); `reverse` walks the chunks
    last to first."""
    at = (lambda j: chunks - 1 - j) if reverse else (lambda j: j)
    keys = pl.BlockSpec((1, chunk, kd), lambda i, h, j: (i, at(j), h))
    values = pl.BlockSpec((1, chunk, vd), lambda i, h, j: (i, at(j), h))
    lane = pl.BlockSpec((1, kd), lambda i, h, j: (0, h))
    lane_sum = pl.BlockSpec((1, 1, kd), lambda i, h, j: (i, 0, h))
    rate = pl.BlockSpec((1, 1, chunk, 1), lambda i, h, j: (i, h, at(j), 0))
    state = pl.BlockSpec((1, 1, vd, kd), lambda i, h, j: (i, at(j), h, 0))
    final = pl.BlockSpec((1, vd, kd), lambda i, h, j: (i, h, 0))
    return keys, values, lane, lane_sum, rate, state, final


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _operands(q, k, v, g, a, dtb, beta):
    """The calls' operands as the kernels take them: heads along the lanes,
    the rate a head spread over its lanes, beta a column a head."""
    b, t, h, kd = q.shape
    flat = lambda x: x.reshape(b, t, h * x.shape[-1])
    return (flat(q), flat(k), flat(v), flat(g), jnp.repeat(a.astype(_F32), kd)[None],
            dtb.astype(_F32).reshape(1, h * kd), beta.astype(_F32).swapaxes(1, 2)[..., None])


def _fwd_call(q, k, v, g, a, dtb, beta, chunk, gated, l2_eps, interpret):
    b, t, h, kd = q.shape
    vd, nc = v.shape[-1], t // chunk
    keys, values, lane, _, rate, state, final = _specs(nc, chunk, kd, vd, False)
    o, states, last = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=kd ** -0.5, gated=gated, l2_eps=l2_eps),
        grid=(b, h, nc),
        in_specs=[keys, keys, values, keys, lane, lane, rate],
        out_specs=[values, state, final],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * vd), v.dtype),
                   jax.ShapeDtypeStruct((b, nc, h * vd, kd), _F32),
                   jax.ShapeDtypeStruct((b, h * vd, kd), _F32)],
        scratch_shapes=[pltpu.VMEM((vd, kd), _F32)],
        compiler_params=_PARAMS, interpret=interpret, name="kda_fwd",
    )(*_operands(q, k, v, g, a, dtb, beta))
    # o as the kernel wrote it, (b, T, H * V): the (b, T, H, V) view is a copy on the chip
    return o, states.reshape(b, nc, h, vd, kd), last.reshape(b, h, vd, kd)


def _bwd_call(q, k, v, g, a, dtb, beta, states, do, chunk, gated, l2_eps, interpret):
    b, t, h, kd = q.shape
    vd, nc = v.shape[-1], t // chunk
    keys, values, lane, lane_sum, rate, state, _ = _specs(nc, chunk, kd, vd, True)
    dq, dk, dv, dg, da, ddtb, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=kd ** -0.5, gated=gated, l2_eps=l2_eps),
        grid=(b, h, nc),
        in_specs=[keys, keys, values, keys, lane, lane, rate, values, state],
        out_specs=[keys, keys, values, keys, lane_sum, lane_sum, rate],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * kd), q.dtype),
                   jax.ShapeDtypeStruct((b, t, h * kd), k.dtype),
                   jax.ShapeDtypeStruct((b, t, h * vd), v.dtype),
                   jax.ShapeDtypeStruct((b, t, h * kd), g.dtype),
                   jax.ShapeDtypeStruct((b, 1, h * kd), _F32),
                   jax.ShapeDtypeStruct((b, 1, h * kd), _F32),
                   jax.ShapeDtypeStruct((b, h, t, 1), _F32)],
        scratch_shapes=[pltpu.VMEM((vd, kd), _F32)],
        compiler_params=_PARAMS, interpret=interpret, name="kda_bwd",
    )(*_operands(q, k, v, g, a, dtb, beta), do, states.reshape(b, nc, h * vd, kd))
    heads = lambda x, w: x.reshape(b, t, h, w)
    return (heads(dq, kd), heads(dk, kd), heads(dv, vd), heads(dg, kd),
            da.reshape(b, h, kd).sum((0, 2)).astype(a.dtype),
            ddtb.sum((0, 1)).reshape(dtb.shape).astype(dtb.dtype),
            dbeta[..., 0].swapaxes(1, 2).astype(beta.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _kda(q, k, v, g, a, dtb, beta, chunk, gated, l2_eps, interpret):
    return _fwd_call(q, k, v, g, a, dtb, beta, chunk, gated, l2_eps, interpret)


def _kda_fwd_rule(q, k, v, g, a, dtb, beta, chunk, gated, l2_eps, interpret):
    # what is dear to compute again and cheap to hold, by name for a remat
    # policy (models/remat.py), as ops/ssd.py names ssm_y, ssm_states
    o, states, last = _fwd_call(q, k, v, g, a, dtb, beta, chunk, gated, l2_eps, interpret)
    o, states = checkpoint_name(o, "kda_out"), checkpoint_name(states, "kda_states")
    return (o, states, last), (q, k, v, g, a, dtb, beta, states)


def _kda_bwd_rule(chunk, gated, l2_eps, interpret, res, cot):
    do = cot[0]  # the states are handed out for a gauge; nothing differentiates them
    return _bwd_call(*res, do, chunk, gated, l2_eps, interpret)


_kda.defvjp(_kda_fwd_rule, _kda_bwd_rule)


def kda_path(seq_len: int, key_dim: int, value_dim: int, chunk: int = CHUNK) -> str:
    """"pallas" or "xla" for a delta rule of these sizes on this process's
    backend: the kernels where a head's keys and values are a vector's lanes
    and a chunk is whole tiles of sublanes in every dtype (T is padded to
    whole chunks, so it decides nothing)."""
    del seq_len
    return "pallas" if _on_tpu() and kernels_take(key_dim, value_dim, chunk) else "xla"


def kernels_take(key_dim: int, value_dim: int, chunk: int) -> bool:
    """Whether the kernels of this frame (this file's pair, ops/gdn.py's) take
    heads of these widths at this chunk."""
    return key_dim == _LANES and value_dim == _LANES and chunk % 32 == 0 and not (
        chunk & (chunk - 1))


def _padded(t, chunk, *arrays):
    """The arrays with T padded with zeros to whole chunks. A padded step has
    k = 0, beta = 0 and g = 0, which leave a state as it was; where the
    kernels norm q and k themselves, a row of zeros norms to zeros (0 times
    eps ** -0.5) and its gradient is finite."""
    if chunk & (chunk - 1) or chunk < 2:
        raise ValueError(f"a chunk is a power of two of steps, not {chunk}")
    pad = -t % chunk
    return [a if not pad else jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in arrays]


def kda(q, k, v, g, beta, chunk=CHUNK, *, interpret=None):
    """o (b, T, H * V) in v's dtype, heads along the lanes as `kda_fwd` writes
    it and ops/kda_norm.py reads it (its cotangent comes back so too), the
    float32 states at each chunk's start,
    transposed, (b, chunks, H, V, K), and the state after the last step,
    (b, H, V, K): the module docstring's recurrence from q and k (b, T, H, K;
    the layer's, normed: `kda_gated` is the entry that norms), v (b, T, H, V),
    the log decays g (b, T, H, K; <= 0,
    float32) and beta (b, T, H). T is padded to whole chunks. `interpret`
    forces the kernels (True: in interpret mode), for the tests."""
    t, (h, kd) = q.shape[1], q.shape[2:]
    q, k, v, g, beta = _padded(t, chunk, q, k, v, g.astype(_F32), beta)
    if interpret is not None or kda_path(t, kd, v.shape[-1], chunk) == "pallas":
        o, states, last = _kda(q, k, v, g, jnp.ones((h,), _F32), jnp.zeros((h, kd), _F32), beta,
                               chunk, False, None, bool(interpret))
    else:
        o, states, last = kda_chunked(q, k, v, g, beta, chunk)
        o = o.reshape(*o.shape[:2], -1)
    return o[:, :t], states, last


def gate_log_decay(f, a_log, dt_bias):
    """g = -exp(A_log) softplus(f + dt_bias) in float32: f (b, T, H, K), a
    rate a head, a bias a channel (H, K)."""
    return -jnp.exp(a_log.astype(_F32))[:, None] * jax.nn.softplus(f.astype(_F32) + dt_bias)


def kda_gated(q, k, v, f, a_log, dt_bias, beta, chunk=CHUNK, *, l2_eps=None, interpret=None):
    """`kda` with the log decays made from the gate's pre-activation f (b, T,
    H, K; any float dtype), A_log (H,) and dt_bias (H, K): `gate_log_decay`,
    inside the kernels where they run (no float32 array of g's size is
    written), before the chunked form elsewhere. With `l2_eps` q and k are the
    layer's before their norm (the convolution's outputs) and `l2norm` is
    applied to a head of each with that eps: inside the kernels too, on the
    block a grid step holds (no float32 array of q's size is written, and dq,
    dk come out as the gradients of what came in), before `kda` elsewhere."""
    t, (h, kd) = q.shape[1], q.shape[2:]
    if interpret is None and kda_path(t, kd, v.shape[-1], chunk) != "pallas":
        if l2_eps is not None:
            q, k = l2norm(q, l2_eps), l2norm(k, l2_eps)
        return kda(q, k, v, gate_log_decay(f, a_log, dt_bias), beta, chunk)
    q, k, v, beta = _padded(t, chunk, q, k, v, beta)
    if q.shape[1] != t:  # a padded step's g is 0: softplus of something very negative
        f = jnp.pad(f, ((0, 0), (0, q.shape[1] - t), (0, 0), (0, 0)), constant_values=-3e4)
    o, states, last = _kda(q, k, v, f, jnp.exp(a_log.astype(_F32)), dt_bias, beta, chunk, True,
                           l2_eps, bool(interpret))
    return o[:, :t], states, last
