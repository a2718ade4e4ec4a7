"""The Mamba-2 recurrence (Dao & Gu 2024, "state-space duality") in its
chunked form: a pallas kernel pair on a TPU, the same form in jax.numpy
elsewhere.

A head h of width P keeps a (P, N) state. With x_t (P), B_t and C_t (N,
shared by the heads of a group), a step Delta_t > 0 and a rate A < 0:

    a_t = exp(Delta_t A)
    S_t = a_t S_{t-1} + Delta_t x_t B_t^T          S_{-1} = 0
    y_t = S_t C_t + D x_t

The chunked form cuts the sequence into chunks of Q steps. With c the
cumulative sum of Delta A inside a chunk (c <= 0, falling), L[i, j] =
exp(c_i - c_j) for j <= i and 0 above the diagonal, and S the state at the
chunk's start:

    Y      = ((C B^T) * L) (Delta * x)  +  exp(c) * (C S^T)  +  D x
    S_next = exp(c_Q) S  +  (exp(c_Q - c) * Delta * x)^T B

so a chunk is five matmuls a head and the recurrence runs over T/Q steps.
Every decay (c, its differences, their exp) is float32 and at most 1: no
factor is ever exp of something positive. x, B and C reach the MXU in the
dtype they arrive in (bf16 from the models), Delta * x and (C B^T) * L are
rounded to that dtype just before their matmuls, every matmul accumulates in
float32, and the carried state is float32 (rounded once where it is a
matmul's operand).

The kernels, `ssd_fwd` and `ssd_bwd` (the names the compiled step and the
profiler's trace show; bench/layer_metrics/ssd_* find them by these): a
grid of (batch, head tiles, chunks), the chunk axis in order, with the
float32 state of the tile's heads in a VMEM scratch handed from chunk to
chunk, as flash_bwd_fused hands dq from key tile to key tile. C B^T of a
chunk is made once a grid step for all its heads; L never leaves VMEM. The
forward writes the state at each chunk's start (T/Q x H x P x N float32),
the one residual the backward needs beyond the inputs. The backward walks
the chunks last to first with the state's cotangent in the scratch and makes
L again. Heads are narrower than a vector's 128 lanes and are worked on
128 / P at a time (a slab): one matmul then serves the slab for everything
but the product with L, which is a head's own and is selected by lane. B
and C reach the calls as (b, T, G N), every group's side by side, and a grid
step reads its own group's N columns: the heads of a grid step lie within
one group (a group's heads are whole slabs, and a step takes as many slabs
as divide a group's), so C B^T is still made once a step, and each step's
part of dB and dC is summed over its group's steps and no further. The
kernels take heads of a width that divides 128 in such groups
(models/granite.py: one group; models/nemotron_h.py: eight, a grid step's
eight heads one group); any other shape runs the jax.numpy form.

The cumulative sum is made outside the kernels (`chunk_log_decay`), and the
calls take Delta and c as two inputs: XLA's own rules carry c's cotangent
back to Delta and A.
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
_SLABS = 4  # slabs of heads a grid step takes at most

_NT = (((1,), (1,)), ((), ()))  # (m, c) x (n, c) -> (m, n)
_NN = (((1,), (0,)), ((), ()))  # (m, c) x (c, n) -> (m, n)
_TN = (((0,), (0,)), ((), ()))  # (c, m) x (c, n) -> (m, n)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def chunk_log_decay(dt, A, chunk):
    """(b, T, H) float32: the cumulative sum of Delta A within each chunk of
    `chunk` steps, c of the module's docstring."""
    b, t, h = dt.shape
    a = dt.astype(jnp.float32) * A.astype(jnp.float32)
    return jnp.cumsum(a.reshape(b, t // chunk, chunk, h), axis=2).reshape(b, t, h)


# --------------------------------------------------------------------------
# the chunked form in jax.numpy
# --------------------------------------------------------------------------


def ssd_chunked(x, dt, cs, B, C, D, chunk):
    """The chunked form as einsums, differentiated by JAX: what runs where
    there is no TPU, and what the kernels are tested against. x (b, T, H, P),
    dt and cs (b, T, H) float32, B and C (b, T, G, N), D (H,). Returns y as
    x and the float32 state at each chunk's start, (b, T/chunk, H, P, N)."""
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    nc, q, r = t // chunk, chunk, h // g
    f32, dtype = jnp.float32, x.dtype
    xc = x.reshape(b, nc, q, g, r, p)
    dtc, csc = (v.reshape(b, nc, q, g, r) for v in (dt, cs))
    Bc, Cc = B.reshape(b, nc, q, g, n), C.reshape(b, nc, q, g, n)
    xd = (xc.astype(f32) * dtc[..., None]).astype(dtype)
    rows = csc.transpose(0, 1, 3, 4, 2)  # (b, nc, g, r, q)
    seen = jnp.tril(jnp.ones((q, q), bool))
    L = jnp.exp(jnp.where(seen, rows[..., :, None] - rows[..., None, :], -jnp.inf))
    cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc, preferred_element_type=f32)
    M = (cb[:, :, :, None] * L).astype(dtype)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", M, xd, preferred_element_type=f32)
    last = csc[:, :, -1]  # (b, nc, g, r)
    to_end = jnp.exp(last[:, :, None] - csc)
    own = jnp.einsum("bcjgrp,bcjgn->bcgrpn", (xd.astype(f32) * to_end[..., None]).astype(dtype),
                     Bc, preferred_element_type=f32)

    def carry(s, k):
        return jnp.exp(last[:, k])[..., None, None] * s + own[:, k], s

    _, states = jax.lax.scan(carry, jnp.zeros((b, g, r, p, n), f32), jnp.arange(nc))
    states = states.swapaxes(0, 1)  # (b, nc, g, r, p, n)
    y = y + jnp.exp(csc)[..., None] * jnp.einsum(
        "bcign,bcgrpn->bcigrp", Cc, states.astype(dtype), preferred_element_type=f32)
    y = y + D.astype(f32).reshape(g, r)[..., None] * xc.astype(f32)
    return y.astype(dtype).reshape(b, t, h, p), states.reshape(b, nc, h, p, n)


# --------------------------------------------------------------------------
# the kernels
# --------------------------------------------------------------------------


def head_tile(h, p, groups=1):
    """(heads a slab, heads a grid step): a slab is 128 lanes of heads, and a
    grid step takes up to _SLABS slabs, all of one group of h / groups heads."""
    per = _LANES // p
    if p >= _LANES or _LANES % p or h % groups or (h // groups) % per:
        raise ValueError(f"{h} heads of width {p} in {groups} groups do not fill slabs of "
                         f"{_LANES} lanes a group")
    slabs = max(s for s in range(1, _SLABS + 1) if (h // groups // per) % s == 0)
    return per, per * slabs


def _spread(cols, first, per, lane_head):
    """Columns first .. first+per-1 of `cols` (rows, heads), each along its
    own head's lanes of a slab."""
    out = cols[:, first:first + 1]
    for a in range(1, per):
        out = jnp.where(lane_head == a, cols[:, first + a:first + a + 1], out)
    return out


def _set_col(acc, index, col):
    return jnp.where(jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1) == index, col, acc)


def _set_row(acc, index, row):
    return jnp.where(jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0) == index, row, acc)


def _chunk_setup(b_ref, c_ref):
    Bm, Cm = b_ref[0], c_ref[0]
    q = Bm.shape[0]
    seen = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    return Bm, Cm, _dot(Cm, Bm, _NT), seen


def _decay_matrix(seen, csc, csr, h):
    """L of head h of the tile: exp(c_i - c_j) at and below the diagonal."""
    return jnp.where(seen, jnp.exp(csc[:, h:h + 1] - csr[h:h + 1, :]), 0.0)


def _end_decay(csc, first, per, row_head):
    """exp(c_Q) of a slab's heads, each down its own head's rows of the
    slab's (per * P, N) state."""
    last = csc.shape[0] - 1
    out = jnp.exp(csc[last:, first:first + 1])  # (1, 1)
    for a in range(1, per):
        out = jnp.where(row_head == a, jnp.exp(csc[last:, first + a:first + a + 1]), out)
    return out


def _fwd_kernel(x_ref, dtc_ref, csc_ref, csr_ref, b_ref, c_ref, d_ref, y_ref, st_ref, s_acc,
                *, p, per):
    """One chunk of one tile of heads: y of the chunk, the state at its
    start written out, the state at its end left in s_acc."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        s_acc[...] = jnp.zeros(s_acc.shape, s_acc.dtype)

    st_ref[0, 0] = s_acc[...]
    Bm, Cm, cb, seen = _chunk_setup(b_ref, c_ref)
    q, dtype = Bm.shape[0], x_ref.dtype
    dtc, csc, csr = dtc_ref[0, 0], csc_ref[0, 0], csr_ref[0, 0]
    width = per * p
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (q, width), 1) // p
    row_head = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0) // p
    for s in range(x_ref.shape[2] // width):
        lanes = slice(s * width, (s + 1) * width)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        c = _spread(csc, s * per, per, lane_head)
        xd = x * _spread(dtc, s * per, per, lane_head)
        xd_op = xd.astype(dtype)
        state = s_acc[lanes, :]
        y = jnp.exp(c) * _dot(Cm, state.astype(dtype), _NT) + d_ref[:, lanes] * x
        for a in range(per):
            L = _decay_matrix(seen, csc, csr, s * per + a)
            own = _dot((cb * L).astype(dtype), xd_op, _NN)
            y = y + jnp.where(lane_head == a, own, 0.0)
        y_ref[0, :, lanes] = y.astype(dtype)
        to_end = jnp.exp(c[q - 1:q, :] - c)
        s_acc[lanes, :] = (_end_decay(csc, s * per, per, row_head) * state
                           + _dot((xd * to_end).astype(dtype), Bm, _TN))


def _bwd_kernel(x_ref, dy_ref, dtc_ref, csc_ref, csr_ref, b_ref, c_ref, d_ref, st_ref,
                dx_ref, ddt_ref, dcc_ref, dcr_ref, db_ref, dc_ref, dd_ref, ds_acc, *, p, per):
    """One chunk of one tile of heads, chunks last to first: the cotangents
    of everything the forward read there, the state's cotangent at the
    chunk's end in ds_acc on entry and at its start on exit."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        ds_acc[...] = jnp.zeros(ds_acc.shape, ds_acc.dtype)

    Bm, Cm, cb, seen = _chunk_setup(b_ref, c_ref)
    q, dtype = Bm.shape[0], x_ref.dtype
    f32 = jnp.float32
    dtc, csc, csr = dtc_ref[0, 0], csc_ref[0, 0], csr_ref[0, 0]
    width = per * p
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (q, width), 1) // p
    row_head = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0) // p
    last_row = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    d_cb = jnp.zeros((q, q), f32)
    d_B = jnp.zeros(Bm.shape, f32)
    d_C = jnp.zeros(Cm.shape, f32)
    ddt, dcc = jnp.zeros(dtc.shape, f32), jnp.zeros(dtc.shape, f32)
    dcr = jnp.zeros(csr.shape, f32)

    def head_sum(v, a):  # (q, width) -> (q, 1): over head a's lanes
        return jnp.sum(jnp.where(lane_head == a, v, 0.0), axis=1, keepdims=True)

    for s in range(x_ref.shape[2] // width):
        lanes = slice(s * width, (s + 1) * width)
        x = x_ref[0, :, lanes].astype(f32)
        dy_op = dy_ref[0, :, lanes]
        dy = dy_op.astype(f32)
        c = _spread(csc, s * per, per, lane_head)
        dts = _spread(dtc, s * per, per, lane_head)
        xd = x * dts
        xd_op = xd.astype(dtype)
        to_end = jnp.exp(c[q - 1:q, :] - c)
        state, d_state = st_ref[0, 0, lanes, :], ds_acc[lanes, :]
        state_op, d_state_op = state.astype(dtype), d_state.astype(dtype)

        # the carried state's part of y, and the chunk's part of the next state
        e_dy = jnp.exp(c) * dy
        y_off_dy = e_dy * _dot(Cm, state_op, _NT)
        e_dy = e_dy.astype(dtype)
        b_ds = _dot(Bm, d_state_op, _NT)                  # (q, width)
        fed = xd * to_end
        d_C = d_C + _dot(e_dy, state_op, _NN)
        d_B = d_B + _dot(fed.astype(dtype), d_state_op, _NN)
        d_xd = to_end * b_ds
        fed_ds = fed * b_ds
        decay = _end_decay(csc, s * per, per, row_head)    # (width, 1)
        carried = decay * state * d_state                  # (width, n)
        for a in range(per):
            h = s * per + a
            L = _decay_matrix(seen, csc, csr, h)
            M = cb * L
            own = _dot(M.astype(dtype), dy_op, _TN)        # M^T dY
            d_xd = d_xd + jnp.where(lane_head == a, own, 0.0)
            d_M = _dot(jnp.where(lane_head == a, dy_op, 0).astype(dtype),
                       xd_op, _NT)                         # dY_a Xd_a^T
            d_cb = d_cb + d_M * L
            w = d_M * M
            fed_a = head_sum(fed_ds, a)
            at_end = jnp.sum(fed_a, axis=0, keepdims=True) + jnp.sum(
                jnp.where(row_head == a, carried, 0.0), keepdims=True)
            col = (jnp.sum(w, axis=1, keepdims=True) + head_sum(y_off_dy, a) - fed_a
                   + jnp.where(last_row, at_end, 0.0))
            dcc = _set_col(dcc, h, col)
            dcr = _set_row(dcr, h, -jnp.sum(w, axis=0, keepdims=True))
            ddt = _set_col(ddt, h, head_sum(d_xd * x, a))  # head a's lanes are whole now
        ds_acc[lanes, :] = decay * d_state + _dot(e_dy, Cm, _TN)
        dx_ref[0, :, lanes] = (d_xd * dts + d_ref[:, lanes] * dy).astype(dtype)
        dd_ref[0, 0, :, lanes] = jnp.sum(dy * x, axis=0, keepdims=True)
    cb_op = d_cb.astype(dtype)
    dc_ref[0, 0] = d_C + _dot(cb_op, Bm, _NN)
    db_ref[0, 0] = d_B + _dot(cb_op, Cm, _TN)
    ddt_ref[0, 0], dcc_ref[0, 0], dcr_ref[0, 0] = ddt, dcc, dcr


def _tiled(v, tile):
    """(b, T, H) -> heads by tile: columns (b, H/tile, T, tile) and rows
    (b, H/tile, tile, T)."""
    b, t, h = v.shape
    cols = v.reshape(b, t, h // tile, tile).transpose(0, 2, 1, 3)
    return cols, cols.swapaxes(2, 3)


def _specs(chunks, chunk, tile, p, n, reverse, steps_a_group=None):
    """Block specs of a call's operands, by grid (batch, head tile, chunk);
    `reverse` walks the chunks last to first. `steps_a_group`: head tiles
    that share a group's N columns of B and C (None: one group, all)."""
    at = (lambda k: chunks - 1 - k) if reverse else (lambda k: k)
    group = (lambda j: 0) if steps_a_group is None else (lambda j: j // steps_a_group)
    wide = pl.BlockSpec((1, chunk, tile * p), lambda i, j, k: (i, at(k), j))
    cols = pl.BlockSpec((1, 1, chunk, tile), lambda i, j, k: (i, j, at(k), 0))
    rows = pl.BlockSpec((1, 1, tile, chunk), lambda i, j, k: (i, j, 0, at(k)))
    shared = pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, at(k), group(j)))
    skip = pl.BlockSpec((1, tile * p), lambda i, j, k: (0, j))
    state = pl.BlockSpec((1, 1, tile * p, n), lambda i, j, k: (i, at(k), j, 0))
    own_shared = pl.BlockSpec((1, 1, chunk, n), lambda i, j, k: (i, j, at(k), 0))
    lane_sum = pl.BlockSpec((1, 1, 1, tile * p), lambda i, j, k: (i, at(k), 0, j))
    return wide, cols, rows, shared, skip, state, own_shared, lane_sum


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _side_by_side(v):
    """(b, T, G, N) -> (b, T, G N): the groups' columns side by side."""
    b, t, g, n = v.shape
    return v[:, :, 0] if g == 1 else v.reshape(b, t, g * n)


def _steps_a_group(h, tile, groups):
    """`_specs`' steps_a_group of h heads in `groups` groups, `tile` a step."""
    return None if groups == 1 else h // groups // tile


def _operands(x, dt, cs, B, C, D, tile):
    """What both calls read, as the kernels take it: x with its heads along
    the lanes, Delta by columns, c by columns and by rows, B and C with their
    groups side by side, D a lane."""
    b, t, h, p = x.shape
    csc, csr = _tiled(cs, tile)
    return (x.reshape(b, t, h * p), _tiled(dt, tile)[0], csc, csr, _side_by_side(B),
            _side_by_side(C), jnp.repeat(D.astype(jnp.float32), p)[None])


def _fwd_call(x, dt, cs, B, C, D, chunk, interpret):
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    per, tile = head_tile(h, p, g)
    wide, cols, rows, shared, skip, state, _, _ = _specs(
        t // chunk, chunk, tile, p, n, False, _steps_a_group(h, tile, g))
    y, states = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, per=per),
        grid=(b, h // tile, t // chunk),
        in_specs=[wide, cols, cols, rows, shared, shared, skip],
        out_specs=[wide, state],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * p), x.dtype),
                   jax.ShapeDtypeStruct((b, t // chunk, h * p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tile * p, n), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="ssd_fwd",
    )(*_operands(x, dt, cs, B, C, D, tile))
    return y.reshape(b, t, h, p), states.reshape(b, t // chunk, h, p, n)


def _bwd_call(x, dt, cs, B, C, D, states, dy, chunk, interpret):
    b, t, h, p = x.shape
    (g, n), nc = B.shape[2:], t // chunk
    per, tile = head_tile(h, p, g)
    wide, cols, rows, shared, skip, state, own_shared, lane_sum = _specs(
        nc, chunk, tile, p, n, True, _steps_a_group(h, tile, g))
    f32 = jnp.float32
    tiles = h // tile
    operands = _operands(x, dt, cs, B, C, D, tile)
    dx, ddt, dcc, dcr, dB, dC, dD = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, per=per),
        grid=(b, tiles, nc),
        in_specs=[wide, wide, cols, cols, rows, shared, shared, skip, state],
        out_specs=[wide, cols, cols, rows, own_shared, own_shared, lane_sum],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * p), x.dtype),
                   jax.ShapeDtypeStruct((b, tiles, t, tile), f32),
                   jax.ShapeDtypeStruct((b, tiles, t, tile), f32),
                   jax.ShapeDtypeStruct((b, tiles, tile, t), f32),
                   jax.ShapeDtypeStruct((b, tiles, t, n), f32),
                   jax.ShapeDtypeStruct((b, tiles, t, n), f32),
                   jax.ShapeDtypeStruct((b, nc, 1, h * p), f32)],
        scratch_shapes=[pltpu.VMEM((tile * p, n), f32)],
        compiler_params=_PARAMS, interpret=interpret, name="ssd_bwd",
    )(operands[0], dy.reshape(b, t, h * p), *operands[1:], states.reshape(b, nc, h * p, n))
    untile = lambda cols: cols.transpose(0, 2, 1, 3).reshape(b, t, h)
    # every tile's part of dB and dC adds up, within its group
    over_tiles = lambda v: v.sum(1)[:, :, None] if g == 1 else (
        v.reshape(b, g, tiles // g, t, n).sum(2).swapaxes(1, 2))
    return (dx.reshape(b, t, h, p), untile(ddt), untile(dcc) + untile(dcr.swapaxes(2, 3)),
            over_tiles(dB).astype(B.dtype), over_tiles(dC).astype(C.dtype),
            dD.reshape(-1, h, p).sum((0, 2)).astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, cs, B, C, D, chunk, interpret):
    return _fwd_call(x, dt, cs, B, C, D, chunk, interpret)


def _ssd_fwd_rule(x, dt, cs, B, C, D, chunk, interpret):
    # what is dear to compute again and cheap to hold, by name for a remat
    # policy (models/remat.py), as ops/attention.py names attn_out, attn_lse
    y, states = _fwd_call(x, dt, cs, B, C, D, chunk, interpret)
    y, states = checkpoint_name(y, "ssm_y"), checkpoint_name(states, "ssm_states")
    return (y, states), (x, dt, cs, B, C, D, states)


def _ssd_bwd_rule(chunk, interpret, res, cot):
    dy, _ = cot  # the states are handed out for a gauge; nothing differentiates them
    return _bwd_call(*res, dy, chunk, interpret)


_ssd.defvjp(_ssd_fwd_rule, _ssd_bwd_rule)


def ssd_path(seq_len: int, heads: int, width: int, groups: int, chunk: int) -> str:
    """"pallas" or "xla" for a scan of these sizes on this process's backend:
    the kernels where the chunks are whole vectors of lanes and the heads of
    each of B's and C's groups fill slabs of them."""
    fits = (chunk % _LANES == 0 and seq_len % chunk == 0 and heads % groups == 0
            and width < _LANES and _LANES % width == 0
            and heads // groups % (_LANES // width) == 0)
    return "pallas" if _on_tpu() and fits else "xla"


def ssd(x, dt, A, B, C, D, chunk, *, interpret=None):
    """y (b, T, H, P) in x's dtype and the float32 state at each chunk's
    start (b, T/chunk, H, P, N), from x (b, T, H, P), the steps dt (b, T, H,
    positive, float32), the rates A (H, negative), B and C (b, T, G, N) and
    the skip D (H). A sequence shorter than a chunk is one chunk. `interpret`
    forces the kernels (True: in interpret mode), for the tests."""
    _, t, h, p = x.shape
    chunk = min(chunk, t)
    if t % chunk:
        raise ValueError(f"sequence {t} is not a multiple of the chunk {chunk}")
    dt = dt.astype(jnp.float32)
    cs = chunk_log_decay(dt, A, chunk)
    if interpret is not None or ssd_path(t, h, p, B.shape[2], chunk) == "pallas":
        return _ssd(x, dt, cs, B, C, D, chunk, bool(interpret))
    return ssd_chunked(x, dt, cs, B, C, D, chunk)
