"""The gated delta rule whose decay is one number a head and step (Gated
Delta Networks, arXiv:2412.06464; Qwen3-Next's linear layers), with several
value heads on each key head, in three forms: the recurrence, its chunked form
in jax.numpy and a pallas kernel pair on a TPU. It is ops/kda.py's rule with
every channel's decay equal, on that file's frame.

A value head keeps a (K, V) state. Value head j reads key head j // rep
(rep = Hv / Hk). With q_t, k_t (K) of its key head, v_t (V), a log decay g_t
(one number, <= 0, float32) and a rate beta_t in (0, 1):

    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(K)                                   S_0 = 0

`gdn_plain` is that, a `lax.scan` over t in float32.

The chunked form is ops/kda.py's, `_chunk_parts` to `_chunk_bwd`, told the
decay's kind (`kda.Decay`): g reaches it with a step's one number on every
lane of the key, so G, exp(G) and every product with them are what they are
there, and what differs is the products inside a chunk. KDA cannot factor
exp(G_i - G_j) through a chunk and sums A_kk and A_qk over six levels of
blocks, each level a matmul of decayed copies with an exp of its own. Here

    Gamma[i, j] = exp(G_i - G_j)    j <= i, else 0: (C, C) numbers at most 1
    A_qk = (q k^T) * Gamma          its diagonal and below
    A_kk = (k k^T) * Gamma          below its diagonal

one matmul for both raw products and one (C, C) exp a chunk: nothing can
overflow, whatever the decay. The raw products are a key head's, so the
value heads on it share them. Backward, with X = dA_qk * A_qk + dA_kk * A_kk,
dG_i takes the sum of X's row i less the sum of its column i (a pair (i, j)
moves with G_i and against G_j: one float32 number with two signs, which
the running sum up the rows cancels outside (j, i]), and q and k take
(dA * Gamma) times k, and its transpose times q and k: the value heads'
dA * Gamma are summed first and the three matmuls made once a key head.

The kernels, `gdn_fwd` and `gdn_bwd` (the names the compiled step and the
profiler's trace show; bench/layer_metrics/gdn_* find them by these, and no
other metric's pattern does): a grid of (batch, key heads, chunks), the chunk
axis in order; a grid step reads a key head's q and k once, (C, K), norms
them (`kda.l2norm`, with `l2_eps`) and makes their raw products, then walks
the key head's value heads: each one's state in its rows of a VMEM scratch,
(rep V, K) transposed as KDA's, handed from chunk to chunk; the states at each
chunk's start are the forward's one residual. q and k are never repeated
along heads in HBM. g and beta come a block a key head and chunk, (B, Hk,
T / C, 2 rep, C) float32: the value heads' log decays and then their rates, a
step a lane (a (C, 1) column of a step comes out of a row through the
diagonal of a (C, C): `_as_column`); the gate's softplus
(`gate_log_decay`) and beta's sigmoid are the layer's lines on (B, T, Hv)
outside, 2 MB each at the benchmark's cell where KDA's decay is 268 MB: the
kernels would gain nothing by making them. dg comes back through the
cumulative sum's transpose inside, dq and dk as the gradients of what came
in (the norm's vjp last, on the float32 sums over the value heads).

Precision as ops/kda.py states its own: g, G, Gamma, the inverse and the
carried state float32; matmul operands in q's dtype, float32 sums; the
inverse's matmuls three bf16 passes.

The kernels take heads whose K and V are 128 lanes and T padded to whole
chunks (k = 0, beta = 0, g = 0 leave a state as it was); any other shape,
and any backend but a TPU, runs the chunked form in jax.numpy
(`kda.kda_chunked` with this decay, q and k repeated along heads there),
differentiated by JAX.

Not here (PERF.md section 7): a state reset at a packed document's
boundary, an initial state handed in, the pair under a mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import kda
from ray_tpu.ops.attention import _on_tpu  # a worker that cannot reach its chip fails there
from ray_tpu.ops.kda import _F32, _NN, _NT, _TN, _dot, _dot_3pass, _tpu_roll

CHUNK = 64  # steps a chunk (bench/shape_functions/gdn.py reads it off a call)


# --------------------------------------------------------------------------
# the recurrence
# --------------------------------------------------------------------------


def gdn_plain(q, k, v, g, beta, steps=64):
    """The recurrence step by step in float32: q, k (b, T, Hk, K), v (b, T,
    Hv, V), g and beta (b, T, Hv). Returns o (b, T, Hv, V) float32 and the
    state after the last step, (b, Hv, K, V). Nested as `kda.kda_plain`."""
    q, k, v, g, beta = (a.astype(_F32) for a in (q, k, v, g, beta))
    b, t, hk, kd = q.shape
    hv = v.shape[2]
    rep, scale = hv // hk, kd ** -0.5
    steps = max(d for d in range(1, steps + 1) if t % d == 0)

    def step(S, xs):  # S (b, Hk, rep, K, V)
        q_t, k_t, v_t, g_t, b_t = xs
        v_t, g_t, b_t = (a.reshape(b, hk, rep, *a.shape[2:]) for a in (v_t, g_t, b_t))
        S = jnp.exp(g_t)[..., None, None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhk,bhrkv->bhrv", k_t, S))
        S = S + k_t[:, :, None, :, None] * u[..., None, :]
        return S, (jnp.einsum("bhk,bhrkv->bhrv", q_t, S) * scale).reshape(b, hv, -1)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(step, S, xs)

    by_block = lambda a: a.swapaxes(0, 1).reshape(t // steps, steps, b, *a.shape[2:])
    with jax.default_matmul_precision("highest"):
        S, o = jax.lax.scan(block, jnp.zeros((b, hk, rep, kd, v.shape[-1]), _F32),
                            tuple(by_block(a) for a in (q, k, v, g, beta)))
    return o.reshape(t, b, hv, -1).swapaxes(0, 1), S.reshape(b, hv, kd, -1)


# --------------------------------------------------------------------------
# the products inside a chunk under one decay a step
# --------------------------------------------------------------------------


def _as_column(row):
    """(1, C) -> (C, 1) through the diagonal of a (C, C): no transpose."""
    c = row.shape[1]
    return jnp.sum(jnp.where(kda._diagonal(c), jnp.broadcast_to(row, (c, c)), 0.0), axis=1,
                   keepdims=True)


def _as_row(column):
    """(C, 1) -> (1, C), the same way."""
    c = column.shape[0]
    return jnp.sum(jnp.where(kda._diagonal(c), jnp.broadcast_to(column, (c, c)), 0.0), axis=0,
                   keepdims=True)


def _gamma(G):
    """exp(G_i - G_j) at j <= i and 0 above, (C, C), from G (C, K) whose
    lanes all hold the decay's running sum."""
    c = G.shape[0]
    down = jnp.broadcast_to(G[:, :1], (c, c))
    along = jnp.sum(jnp.where(kda._diagonal(c), down, 0.0), axis=0, keepdims=True)
    seen = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))
    return jnp.where(seen, jnp.exp(jnp.where(seen, down - along, 0.0)), 0.0)


class _Scalar:
    """`kda.Decay` of one number a step. `both`: a key head's raw products,
    [q k^T ; k k^T] (2 C, C) float32, where its value heads share them (None:
    made here). `gathered`: a list that takes each value head's [dA_qk ;
    dA_kk] * Gamma in place of the matmuls into dq and dk (`_raw_pulled`
    makes them of the sum, once a key head)."""

    def __init__(self, both=None, gathered=None):
        self.both, self.gathered = both, gathered

    def intra(self, q, k, G, roll):
        c = q.shape[0]
        both = self.both if self.both is not None else _raw(q, k)
        gamma = _gamma(G)
        return both[:c] * gamma, both[c:] * jnp.where(kda._diagonal(c), 0.0, gamma)

    @staticmethod
    def over(x):
        return jnp.broadcast_to(jnp.sum(x, axis=1, keepdims=True), x.shape)

    def pull(self, q, k, p, at, dA_qk, dA_kk, dq, dk, dG, roll):
        moved = dA_qk * p["a_qk"] + dA_kk * p["a_kk"]
        dG = dG + (jnp.sum(moved, axis=1, keepdims=True)
                   - _as_column(jnp.sum(moved, axis=0, keepdims=True)))
        gamma = _gamma(p["G"])
        d_both = jnp.concatenate([dA_qk * gamma, dA_kk * gamma], axis=0)
        if self.gathered is not None:
            self.gathered.append(d_both)
            return dq, dk, dG
        more_q, more_k = _raw_pulled(q, k, d_both)
        return dq + more_q, dk + more_k, dG


def _raw(q, k):
    """[q k^T ; k k^T], (2 C, C) float32: one matmul."""
    return _dot(jnp.concatenate([q, k], axis=0), k, _NT)


def _raw_pulled(q, k, d_both):
    """(dq, dk) float32 from `_raw`'s cotangent."""
    c = q.shape[0]
    d_both = d_both.astype(q.dtype)
    rows = _dot(d_both, k, _NN)  # (2 C, K): q's rows, then k's as rows
    return rows[:c], rows[c:] + _dot(d_both, jnp.concatenate([q, k], axis=0), _TN)


# --------------------------------------------------------------------------
# the kernels
# --------------------------------------------------------------------------


def _rates(r_ref, r, rep, shape):
    """(g, beta) of a key head's value head r in a chunk: g with the step's
    number on every lane, (C, K), beta a column, (C, 1), from the block's
    rows (2 rep, C): the value heads' log decays and then their rates, a step
    a lane."""
    rows = r_ref[0, 0, 0]
    return (jnp.broadcast_to(_as_column(rows[r:r + 1]), shape),
            _as_column(rows[rep + r:rep + r + 1]))


def _normed_pair(q_ref, k_ref, l2_eps):
    q, k = q_ref[0], k_ref[0]
    if l2_eps is not None:
        q, k = kda.l2norm(q, l2_eps), kda.l2norm(k, l2_eps)
    return q, k


def _fwd_kernel(q_ref, k_ref, v_ref, r_ref, o_ref, st_ref, fin_ref, s_acc, *, scale, rep, l2_eps):
    """One chunk of one key head: o of its value heads' chunk, their states
    at its start written out, those at its end left in s_acc."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        s_acc[...] = jnp.zeros(s_acc.shape, s_acc.dtype)

    q, k = _normed_pair(q_ref, k_ref, l2_eps)
    decay = _Scalar(_raw(q, k))
    vd = v_ref.shape[2] // rep
    for r in range(rep):
        rows = slice(r * vd, (r + 1) * vd)
        St = s_acc[rows]
        st_ref[0, 0, rows] = St
        g, beta = _rates(r_ref, r, rep, q.shape)
        o, nxt = kda._chunk_fwd(q, k, v_ref[0, :, rows], g, beta, St, scale, _tpu_roll,
                                _dot_3pass, decay)
        o_ref[0, :, rows] = o.astype(o_ref.dtype)
        s_acc[rows] = nxt

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        fin_ref[0] = s_acc[...]


def _bwd_kernel(q_ref, k_ref, v_ref, r_ref, do_ref, st_ref, dq_ref, dk_ref, dv_ref, dr_ref,
                ds_acc, *, scale, rep, l2_eps):
    """One chunk of one key head, chunks last to first: the value heads'
    state cotangents at the chunk's end in ds_acc on entry and at its start
    on exit; dq and dk summed over the value heads."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        ds_acc[...] = jnp.zeros(ds_acc.shape, ds_acc.dtype)

    q, k = _normed_pair(q_ref, k_ref, l2_eps)
    gathered = []
    decay = _Scalar(_raw(q, k), gathered)
    vd = v_ref.shape[2] // rep
    dq = dk = jnp.zeros(q.shape, _F32)
    for r in range(rep):
        rows = slice(r * vd, (r + 1) * vd)
        g, beta = _rates(r_ref, r, rep, q.shape)
        dq_r, dk_r, dv, dg, dbeta, d_start = kda._chunk_bwd(
            q, k, v_ref[0, :, rows], g, beta, st_ref[0, 0, rows],
            do_ref[0, :, rows], ds_acc[rows], scale, _tpu_roll, _dot_3pass, decay)
        dq, dk = dq + dq_r, dk + dk_r
        dv_ref[0, :, rows] = dv.astype(dv_ref.dtype)
        dr_ref[0, 0, 0, r:r + 1] = _as_row(dg[:, :1])
        dr_ref[0, 0, 0, rep + r:rep + r + 1] = _as_row(dbeta)
        ds_acc[rows] = d_start
    more_q, more_k = _raw_pulled(q, k, sum(gathered[1:], gathered[0]))
    dq, dk = dq + more_q, dk + more_k
    if l2_eps is not None:
        dq, dk = kda._norm_pulled(dq, q_ref[0], l2_eps), kda._norm_pulled(dk, k_ref[0], l2_eps)
    dq_ref[0], dk_ref[0] = dq.astype(dq_ref.dtype), dk.astype(dk_ref.dtype)


def _specs(chunks, chunk, kd, vd, rep, reverse):
    """Block specs by grid (batch, key head, chunk); `reverse` walks the
    chunks last to first."""
    at = (lambda j: chunks - 1 - j) if reverse else (lambda j: j)
    keys = pl.BlockSpec((1, chunk, kd), lambda i, h, j: (i, at(j), h))
    values = pl.BlockSpec((1, chunk, rep * vd), lambda i, h, j: (i, at(j), h))
    rates = pl.BlockSpec((1, 1, 1, 2 * rep, chunk), lambda i, h, j: (i, h, at(j), 0, 0))
    state = pl.BlockSpec((1, 1, rep * vd, kd), lambda i, h, j: (i, at(j), h, 0))
    final = pl.BlockSpec((1, rep * vd, kd), lambda i, h, j: (i, h, 0))
    return keys, values, rates, state, final


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _by_chunk(g, beta, hk, chunk):
    """g and beta (b, T, Hv) as the kernels take them: (b, Hk, T / C, 2 rep,
    C) float32, a block a key head and chunk, whole in its last two axes: the
    value heads' log decays and then their rates, a step a lane. (Columns,
    (.., T, 1), are 128 lanes a number in HBM and a row of 4 bytes a DMA:
    537 MB an array at the benchmark's cell and 12 ms a step in copies, my
    chip run, PR 64, call 1.)"""
    b, t, hv = g.shape
    both = jnp.stack([g.astype(_F32), beta.astype(_F32)], axis=2)  # (b, T, 2, Hv)
    both = both.reshape(b, t // chunk, chunk, 2, hk, hv // hk).transpose(0, 4, 1, 3, 5, 2)
    return both.reshape(b, hk, t // chunk, 2 * hv // hk, chunk)


def _from_chunks(d, like):
    """`_by_chunk`'s transpose: (dg, dbeta), (b, T, Hv) each."""
    b, hk, nc, twice, c = d.shape
    d = d.reshape(b, hk, nc, 2, twice // 2, c).transpose(3, 0, 2, 5, 1, 4).reshape(2, b, nc * c, -1)
    return d[0].astype(like[0].dtype), d[1].astype(like[1].dtype)


def _fwd_call(q, k, v, g, beta, chunk, l2_eps, interpret):
    b, t, hk, kd = q.shape
    hv, vd = v.shape[2:]
    rep, nc = hv // hk, t // chunk
    keys, values, rates, state, final = _specs(nc, chunk, kd, vd, rep, False)
    flat = lambda x: x.reshape(b, t, -1)
    o, states, last = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=kd ** -0.5, rep=rep, l2_eps=l2_eps),
        grid=(b, hk, nc),
        in_specs=[keys, keys, values, rates],
        out_specs=[values, state, final],
        out_shape=[jax.ShapeDtypeStruct((b, t, hv * vd), v.dtype),
                   jax.ShapeDtypeStruct((b, nc, hv * vd, kd), _F32),
                   jax.ShapeDtypeStruct((b, hv * vd, kd), _F32)],
        scratch_shapes=[pltpu.VMEM((rep * vd, kd), _F32)],
        compiler_params=_PARAMS, interpret=interpret, name="gdn_fwd",
    )(flat(q), flat(k), flat(v), _by_chunk(g, beta, hk, chunk))
    # o as the kernel wrote it, (b, T, Hv * V): ops/kda_norm.py reads it there
    return o, states.reshape(b, nc, hv, vd, kd), last.reshape(b, hv, vd, kd)


def _bwd_call(q, k, v, g, beta, states, do, chunk, l2_eps, interpret):
    b, t, hk, kd = q.shape
    hv, vd = v.shape[2:]
    rep, nc = hv // hk, t // chunk
    keys, values, rates, state, _ = _specs(nc, chunk, kd, vd, rep, True)
    flat = lambda x: x.reshape(b, t, -1)
    dq, dk, dv, d_rates = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=kd ** -0.5, rep=rep, l2_eps=l2_eps),
        grid=(b, hk, nc),
        in_specs=[keys, keys, values, rates, values, state],
        out_specs=[keys, keys, values, rates],
        out_shape=[jax.ShapeDtypeStruct((b, t, hk * kd), q.dtype),
                   jax.ShapeDtypeStruct((b, t, hk * kd), k.dtype),
                   jax.ShapeDtypeStruct((b, t, hv * vd), v.dtype),
                   jax.ShapeDtypeStruct((b, hk, nc, 2 * rep, chunk), _F32)],
        scratch_shapes=[pltpu.VMEM((rep * vd, kd), _F32)],
        compiler_params=_PARAMS, interpret=interpret, name="gdn_bwd",
    )(flat(q), flat(k), flat(v), _by_chunk(g, beta, hk, chunk), do,
      states.reshape(b, nc, hv * vd, kd))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            *_from_chunks(d_rates, (g, beta)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _gdn(q, k, v, g, beta, chunk, l2_eps, interpret):
    return _fwd_call(q, k, v, g, beta, chunk, l2_eps, interpret)


def _gdn_fwd_rule(q, k, v, g, beta, chunk, l2_eps, interpret):
    # what is dear to compute again and cheap to hold, by name for a remat
    # policy (models/remat.py), as ops/kda.py names its own
    o, states, last = _fwd_call(q, k, v, g, beta, chunk, l2_eps, interpret)
    o, states = checkpoint_name(o, "gdn_out"), checkpoint_name(states, "gdn_states")
    return (o, states, last), (q, k, v, g, beta, states)


def _gdn_bwd_rule(chunk, l2_eps, interpret, res, cot):
    do = cot[0]  # the states are handed out for a gauge; nothing differentiates them
    return _bwd_call(*res, do, chunk, l2_eps, interpret)


_gdn.defvjp(_gdn_fwd_rule, _gdn_bwd_rule)


def gdn_path(seq_len: int, key_dim: int, value_dim: int, chunk: int = CHUNK) -> str:
    """"pallas" or "xla" for a rule of these sizes on this process's backend:
    `kda.kda_path`'s rule (heads of a vector's lanes, a chunk of whole tiles
    of sublanes), by this file's own look at the backend."""
    del seq_len
    return "pallas" if _on_tpu() and kda.kernels_take(key_dim, value_dim, chunk) else "xla"


def gate_log_decay(a, a_log, dt_bias):
    """g = -exp(A_log) softplus(a + dt_bias) in float32: a (b, T, Hv), a rate
    and a bias a value head."""
    return -jnp.exp(a_log.astype(_F32)) * jax.nn.softplus(a.astype(_F32) + dt_bias)


def gdn(q, k, v, g, beta, chunk=CHUNK, *, l2_eps=None, interpret=None):
    """o (b, T, Hv * V) in v's dtype, value heads along the lanes as `gdn_fwd`
    writes it and ops/kda_norm.py reads it (its cotangent comes back so too),
    the float32 states at each chunk's start, transposed, (b, chunks, Hv, V,
    K), and the state after the last step, (b, Hv, V, K): the module
    docstring's recurrence from q and k (b, T, Hk, K), v (b, T, Hv, V), the
    log decays g (b, T, Hv; <= 0, float32) and beta (b, T, Hv). With `l2_eps`
    q and k are the layer's before their norm (the convolution's outputs)
    and `kda.l2norm` is applied to a head of each: inside the kernels where
    they run, before the chunked form elsewhere. T is padded to whole chunks.
    `interpret` forces the kernels (True: in interpret mode), for the
    tests."""
    t, (hk, kd), hv = q.shape[1], q.shape[2:], v.shape[2]
    if hv % hk:
        raise ValueError(f"{hv} value heads on {hk} key heads")
    q, k, v, g, beta = kda._padded(t, chunk, q, k, v, g.astype(_F32), beta)
    if interpret is not None or gdn_path(t, kd, v.shape[-1], chunk) == "pallas":
        o, states, last = _gdn(q, k, v, g, beta, chunk, l2_eps, bool(interpret))
    else:
        if l2_eps is not None:
            q, k = kda.l2norm(q, l2_eps), kda.l2norm(k, l2_eps)
        q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
        o, states, last = kda.kda_chunked(
            q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, chunk, _Scalar())
        o = o.reshape(*o.shape[:2], -1)
    return o[:, :t], states, last
