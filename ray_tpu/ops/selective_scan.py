"""The Mamba-1 recurrence (Gu & Dao 2023, the selective scan): a pallas kernel
pair on a TPU, the recurrence step by step in jax.numpy elsewhere.

A channel c keeps N states. With u_t and a step Delta_t > 0 a channel, B_t
and C_t (N, shared by all channels), rates A < 0 a channel and state, and a
skip D a channel:

    h_t = exp(Delta_t A) * h_{t-1} + (Delta_t u_t) B_t^T        h_{-1} = 0
    y_t = h_t C_t + D u_t

The decay differs by channel **and** state, so a chunk's decays are no
(L * C B^T) product as Mamba-2's (ops/ssd.py): nothing here is a matmul. The
work is an exp and a few multiply-adds a channel, state and step, on the
vector and transcendental units.

The kernels, `sscan_fwd` and `sscan_bwd` (the names the compiled step and the
profiler's trace show; bench/layer_metrics/sscan_* find them by these): a
grid of (batch, channel tiles, chunks of _CHUNK steps), the chunk axis in
order, with the tile's float32 state (N, lanes: states down the sublanes,
channels along the lanes) in a VMEM scratch handed from chunk to chunk. A
step's u_t and Delta_t are a row of their tile, spread down the sublanes;
B_t and C_t have to lie down the sublanes and be equal along the lanes, and
come so from outside: XLA writes them once a call 128 lanes wide, (b, T, N,
128) in the operands' dtype (2 x 4 MB a thousand tokens at N = 16), and a
grid step reads a chunk's. The forward writes the state at each chunk's
start, (b, T / _CHUNK, N, C) float32, the one residual the backward needs
beyond the inputs. The backward walks the chunks last to first: it makes a
chunk's states again from the chunk's start into a VMEM scratch, then walks
the steps back with the state's cotangent carried,

    dh_t   = carried + dy_t C_t^T
    dC_t   = sum_c dy_t h_t            dB_t = sum_c dh_t (Delta_t u_t)
    g      = dh_t * exp(Delta_t A) * h_{t-1}
    dDelta = sum_n (g A + dh_t B_t u_t)      dA += g Delta_t
    du_t   = sum_n dh_t B_t Delta_t + D dy_t           dD += dy_t u_t
    carried = dh_t * exp(Delta_t A)

dB and dC are sums over channels: a step's partial sums over the tile's
vectors of lanes are kept 128 lanes wide for the chunk, and one matmul with
a row of ones a chunk sums the lanes and lays the (steps, N) results along
lanes (the partial split into two bf16 parts, so the sum is float32's to
2^-17); each channel tile writes its own, summed outside. dA and dD are
summed over a batch row's chunks in blocks that stay in VMEM, and over batch
rows outside.

Precision: the state, the decays, their exp and every product are float32;
u, B and C arrive in the stream's dtype and are widened once; Delta arrives
float32 (the model's softplus is XLA's, in float32); y, du are rounded once
to u's dtype, dDelta is float32, dB and dC float32 sums rounded outside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _on_tpu  # a worker that cannot reach its chip fails there

_LANES = 128
_CHUNK = 128  # steps of a grid step: the state is written out, and made again from, every so many
_SUB = 16     # steps of the body's unrolled run: a bf16 tile's rows
_TILE = 512   # channels of a grid step at most: its state is N x _TILE / 1024 vregs
_VMEM_LIMIT = 64 << 20
_NT = (((1,), (1,)), ((), ()))  # (m, c) x (n, c) -> (m, n)


def chunk_of(seq_len: int) -> int:
    """Steps between the states `selective_scan` hands out: _CHUNK, or the
    whole of a sequence that is no multiple of it."""
    return _CHUNK if seq_len % _CHUNK == 0 else seq_len


def selective_scan_plain(u, delta, A, B, C, D):
    """The recurrence as written, one step after another: (y (b, T, C) in
    u's dtype, the float32 state at each chunk's start (b, T / chunk, N, C)),
    `chunk_of(T)` steps a chunk. float32 inside. The steps of a chunk are
    under jax.checkpoint: a gradient keeps the chunks' states, not every
    step's."""
    b, t, c = u.shape
    n = A.shape[1]
    chunk = chunk_of(t)
    f32 = jnp.float32
    a_t = A.astype(f32).T  # (N, C)

    def step(h, now):  # h (b, N, C)
        u_t, d_t, b_t, c_t = now
        h = jnp.exp(d_t[:, None, :] * a_t) * h + (d_t * u_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    @jax.checkpoint
    def one_chunk(h, xs):
        end, ys = jax.lax.scan(step, h, xs)
        return end, (h, ys)

    by_chunk = lambda v: jnp.moveaxis(v.astype(f32), 1, 0).reshape(
        t // chunk, chunk, b, v.shape[2])
    _, (states, ys) = jax.lax.scan(
        one_chunk, jnp.zeros((b, n, c), f32),
        (by_chunk(u), by_chunk(delta), by_chunk(B), by_chunk(C)))
    y = jnp.moveaxis(ys.reshape(t, b, c), 0, 1) + D.astype(f32) * u.astype(f32)
    return y.astype(u.dtype), jnp.moveaxis(states, 0, 1)


def scan_path(seq_len: int, channels: int, states: int) -> str:
    """"pallas" or "plain" for a scan of these sizes on this process's
    backend: the kernels where the sequence is whole chunks, the channels
    whole vectors of lanes and the states whole float32 sublane tiles."""
    fits = seq_len % _CHUNK == 0 and channels % _LANES == 0 and states % 8 == 0
    return "pallas" if _on_tpu() and fits else "plain"


def selective_scan(u, delta, A, B, C, D, *, interpret=None):
    """(y, states): y (b, T, C) in u's dtype and the float32 state at each
    chunk's start (b, T / chunk_of(T), N, C), handed out for a gauge (nothing
    differentiates it), from u (b, T, C), the steps delta (b, T, C; positive,
    float32), the rates A (C, N; negative), B and C (b, T, N) and the skip D
    (C). `interpret` forces the kernels (True: in interpret mode), for the
    tests."""
    _, t, c = u.shape
    delta = delta.astype(jnp.float32)
    if interpret is not None or scan_path(t, c, A.shape[1]) == "pallas":
        return _sscan(u, delta, A.astype(jnp.float32), B, C, D.astype(jnp.float32),
                      bool(interpret))
    y, states = selective_scan_plain(u, delta, A, B, C, D)
    return (checkpoint_name(y, "sscan_y"),
            checkpoint_name(jax.lax.stop_gradient(states), "sscan_states"))


def _tile(c):
    return max(w for w in range(_LANES, min(c, _TILE) + 1, _LANES) if c % w == 0)


def _groups(width):
    return [slice(g * _LANES, (g + 1) * _LANES) for g in range(width // _LANES)]


def _step_forward(h, dt, du, a, bt, s):
    """h_t of every vector of lanes from h_{t-1} (a list, each (N, 128)):
    row s of dt and du (rows, lanes), a (N, lanes), bt (N, 128)."""
    return [jnp.exp(dt[s:s + 1, g] * a[:, g]) * h[i] + du[s:s + 1, g] * bt
            for i, g in enumerate(_groups(a.shape[1]))]


def _fwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, st_ref, h_acc, y_rows):
    """One chunk of one tile of channels: y of the chunk, the state at its
    start written out, the state at its end left in h_acc."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_acc[...] = jnp.zeros(h_acc.shape, h_acc.dtype)

    st_ref[0, 0] = h_acc[...]
    f32 = jnp.float32
    a, skip = a_ref[...], d_ref[...]
    groups = _groups(a.shape[1])

    def run(r, h):
        rows = pl.ds(pl.multiple_of(r * _SUB, _SUB), _SUB)
        u, dt = u_ref[0, rows, :].astype(f32), dt_ref[0, rows, :]
        du = dt * u
        for s in range(_SUB):
            bt, ct = b_ref[0, r * _SUB + s].astype(f32), c_ref[0, r * _SUB + s].astype(f32)
            h = _step_forward(h, dt, du, a, bt, s)
            for i, g in enumerate(groups):
                y_rows[s:s + 1, g] = jnp.sum(h[i] * ct, axis=0, keepdims=True)
        y_ref[0, rows, :] = (y_rows[...] + skip * u).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, u_ref.shape[1] // _SUB, run, [h_acc[:, g] for g in groups])
    for i, g in enumerate(groups):
        h_acc[:, g] = h[i]


def _lane_sums(partial):
    """(rows, 128) float32 -> its rows' sums over the lanes, laid along
    lanes, (1, rows): one matmul with a row of ones, the partial in two bf16
    parts."""
    ones = jnp.ones((8, _LANES), jnp.bfloat16)
    hi = partial.astype(jnp.bfloat16)
    lo = (partial - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    dot = lambda part: jax.lax.dot_general(ones, part, _NT, preferred_element_type=jnp.float32)
    return (dot(hi) + dot(lo))[0:1, :]


def _bwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, st_ref, dy_ref,
                du_ref, ddt_ref, da_ref, db_ref, dc_ref, dd_ref,
                dh_acc, hs, pb, pc, du_rows, ddt_rows):
    """One chunk of one tile of channels, chunks last to first: the chunk's
    states made again from its start into `hs` (slot t + 1 holds h_t, slot 0
    the start), then the steps walked back with the state's cotangent in
    dh_acc on entry (at the chunk's end) and on exit (at its start)."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_acc[...] = jnp.zeros(dh_acc.shape, dh_acc.dtype)
        da_ref[...] = jnp.zeros(da_ref.shape, da_ref.dtype)
        dd_ref[...] = jnp.zeros(dd_ref.shape, dd_ref.dtype)

    f32 = jnp.float32
    a, skip = a_ref[...], d_ref[...]
    n = a.shape[0]
    groups = _groups(a.shape[1])
    runs = u_ref.shape[1] // _SUB
    hs[0] = st_ref[0, 0]

    def again(r, h):
        rows = pl.ds(pl.multiple_of(r * _SUB, _SUB), _SUB)
        u, dt = u_ref[0, rows, :].astype(f32), dt_ref[0, rows, :]
        du = dt * u
        for s in range(_SUB):
            h = _step_forward(h, dt, du, a, b_ref[0, r * _SUB + s].astype(f32), s)
            for i, g in enumerate(groups):
                hs[r * _SUB + s + 1, :, g] = h[i]
        return h

    jax.lax.fori_loop(0, runs, again, [st_ref[0, 0, :, g] for g in groups])

    def back(back_r, carry):
        dh, da, dd = carry
        r = runs - 1 - back_r
        rows = pl.ds(pl.multiple_of(r * _SUB, _SUB), _SUB)
        u, dt = u_ref[0, rows, :].astype(f32), dt_ref[0, rows, :]
        dy = dy_ref[0, rows, :].astype(f32)
        du = dt * u
        dd = dd + jnp.sum(dy * u, axis=0, keepdims=True)
        for s in reversed(range(_SUB)):
            t = r * _SUB + s
            bt, ct = b_ref[0, t].astype(f32), c_ref[0, t].astype(f32)
            part_b = part_c = jnp.zeros((n, _LANES), f32)
            for i, g in enumerate(groups):
                h_t, h_before = hs[t + 1, :, g], hs[t, :, g]
                dy_t, dt_t = dy[s:s + 1, g], dt[s:s + 1, g]
                dh_t = dh[i] + dy_t * ct
                part_c = part_c + dy_t * h_t
                part_b = part_b + dh_t * du[s:s + 1, g]
                carried = dh_t * jnp.exp(dt_t * a[:, g])
                through = carried * h_before  # dh_t exp(Delta_t A) h_{t-1}
                da[i] = da[i] + through * dt_t
                fed = jnp.sum(dh_t * bt, axis=0, keepdims=True)  # d(Delta_t u_t)
                ddt_rows[s:s + 1, g] = (jnp.sum(through * a[:, g], axis=0, keepdims=True)
                                        + fed * u[s:s + 1, g])
                du_rows[s:s + 1, g] = fed * dt_t
                dh[i] = carried
            at = pl.ds(pl.multiple_of(t * n, n), n)
            pb[at, :], pc[at, :] = part_b, part_c
        du_ref[0, rows, :] = (du_rows[...] + skip * dy).astype(du_ref.dtype)
        ddt_ref[0, rows, :] = ddt_rows[...]
        return dh, da, dd

    zeros = [jnp.zeros((n, _LANES), f32) for _ in groups]
    dh, da, dd = jax.lax.fori_loop(
        0, runs, back, ([dh_acc[:, g] for g in groups], zeros, jnp.zeros(skip.shape, f32)))
    for i, g in enumerate(groups):
        dh_acc[:, g] = dh[i]
        da_ref[0, :, g] += da[i]
    dd_ref[0] += dd
    db_ref[0, 0, 0] = _lane_sums(pb[...])
    dc_ref[0, 0, 0] = _lane_sums(pc[...])


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                               vmem_limit_bytes=_VMEM_LIMIT)


def _operands(u, A, B, C, D):
    """A as the kernels hold it, (N, C); B and C a vector of lanes wide, (b,
    T, N, 128), every lane a copy; D a row."""
    b, t, _ = u.shape
    n = A.shape[1]
    wide = lambda v: jnp.broadcast_to(v[..., None], (b, t, n, _LANES))
    return A.T, wide(B), wide(C), D[None, :]


def _grid(u, A, reverse):
    """(grid, where chunk k of the walk lies, the operands' block specs: u's
    and Delta's kind, B's and C's, A's, D's and the chunk states') for a grid
    of (batch, tiles, chunks), the chunks walked last to first where
    `reverse`."""
    (b, t, c), n = u.shape, A.shape[1]
    tile, chunks = _tile(c), t // _CHUNK
    at = (lambda k: chunks - 1 - k) if reverse else (lambda k: k)
    return (b, c // tile, chunks), at, (
        pl.BlockSpec((1, _CHUNK, tile), lambda i, j, k: (i, at(k), j)),
        pl.BlockSpec((1, _CHUNK, n, _LANES), lambda i, j, k: (i, at(k), 0, 0)),
        pl.BlockSpec((n, tile), lambda i, j, k: (0, j)),
        pl.BlockSpec((1, tile), lambda i, j, k: (0, j)),
        pl.BlockSpec((1, 1, n, tile), lambda i, j, k: (i, at(k), 0, j)))


def _fwd_call(u, delta, A, B, C, D, interpret):
    (b, t, c), n, f32 = u.shape, A.shape[1], jnp.float32
    grid, _, (stream, shared, rates, skip, states) = _grid(u, A, False)
    tile = c // grid[1]
    return pl.pallas_call(
        _fwd_kernel, name="sscan_fwd", grid=grid,
        in_specs=[stream, stream, rates, shared, shared, skip],
        out_specs=[stream, states],
        out_shape=[jax.ShapeDtypeStruct((b, t, c), u.dtype),
                   jax.ShapeDtypeStruct((b, grid[2], n, c), f32)],
        scratch_shapes=[pltpu.VMEM((n, tile), f32), pltpu.VMEM((_SUB, tile), f32)],
        compiler_params=_PARAMS, interpret=interpret,
    )(u, delta, *_operands(u, A, B, C, D))


def _bwd_call(u, delta, A, B, C, D, states, dy, interpret):
    (b, t, c), n, f32 = u.shape, A.shape[1], jnp.float32
    grid, at, (stream, shared, rates, skip, at_states) = _grid(u, A, True)
    _, tiles, chunks = grid
    tile = c // tiles
    sums = pl.BlockSpec((1, 1, 1, 1, _CHUNK * n), lambda i, j, k: (i, j, at(k), 0, 0))
    a_row = lambda rows: pl.BlockSpec((1, rows, tile), lambda i, j, k: (i, 0, j))  # a batch row's sum
    du, ddelta, dA, dB, dC, dD = pl.pallas_call(
        _bwd_kernel, name="sscan_bwd", grid=grid,
        in_specs=[stream, stream, rates, shared, shared, skip, at_states, stream],
        out_specs=[stream, stream, a_row(n), sums, sums, a_row(1)],
        out_shape=[jax.ShapeDtypeStruct((b, t, c), u.dtype),
                   jax.ShapeDtypeStruct((b, t, c), f32),
                   jax.ShapeDtypeStruct((b, n, c), f32),
                   jax.ShapeDtypeStruct((b, tiles, chunks, 1, _CHUNK * n), f32),
                   jax.ShapeDtypeStruct((b, tiles, chunks, 1, _CHUNK * n), f32),
                   jax.ShapeDtypeStruct((b, 1, c), f32)],
        scratch_shapes=[pltpu.VMEM((n, tile), f32), pltpu.VMEM((_CHUNK + 1, n, tile), f32),
                        pltpu.VMEM((_CHUNK * n, _LANES), f32),
                        pltpu.VMEM((_CHUNK * n, _LANES), f32),
                        pltpu.VMEM((_SUB, tile), f32), pltpu.VMEM((_SUB, tile), f32)],
        compiler_params=_PARAMS, interpret=interpret,
    )(u, delta, *_operands(u, A, B, C, D), states, dy)
    over_tiles = lambda part, like: part.sum(1).reshape(b, t, n).astype(like.dtype)
    return (du, ddelta, dA.sum(0).T.astype(A.dtype), over_tiles(dB, B), over_tiles(dC, C),
            dD.sum((0, 1)).astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _sscan(u, delta, A, B, C, D, interpret):
    return _fwd_call(u, delta, A, B, C, D, interpret)


def _sscan_fwd_rule(u, delta, A, B, C, D, interpret):
    # what is dear to compute again and cheap to hold, by name for a remat
    # policy (models/remat.py), as ops/ssd.py names ssm_y, ssm_states
    y, states = _fwd_call(u, delta, A, B, C, D, interpret)
    y, states = checkpoint_name(y, "sscan_y"), checkpoint_name(states, "sscan_states")
    return (y, states), (u, delta, A, B, C, D, states)


def _sscan_bwd_rule(interpret, res, cot):
    dy, _ = cot  # the states are handed out for a gauge; nothing differentiates them
    return _bwd_call(*res, dy, interpret)


_sscan.defvjp(_sscan_fwd_rule, _sscan_bwd_rule)
