"""Two depthwise causal convolutions of a few taps, each a pallas kernel pair
on a TPU and the same sums in jax.numpy elsewhere: the gated short
convolution of LFM2's `conv` layers (Liquid AI, 2025), below, and the Mamba-2
mixer's convolution under a bias and silu (the file's second half:
`causal_conv_within`). They share the walk over tiles of T with the boundary
rows carried, the rotations and the taps' sum; the bodies are two, since the
mathematics is (two gates there, a bias and an activation and their
derivative here).

The gated one.

A layer projects its input to three streams B, C and u of the hidden width
d, and mixes along time with a depthwise causal convolution of k taps (k = 3
as published) between two gates:

    z_t = B_t * u_t
    y_t = C_t * sum_{j=0..k-1} w_j * z_{t-(k-1)+j}        z before a row's
                                                           first token is 0

so `w[k-1]` multiplies the token's own z and `w[0]` the one k-1 tokens back.
No activation, no bias. Per token the operator reads 3 d elements and writes
d: it is bound by its bytes, and the kernels are written to move each once.

`gated_conv_fwd` and `gated_conv_bwd` (the names the compiled step and the
profiler's trace show; bench/layer_metrics/gated_conv_* find them by these)
take `bcu` (b, T, 3 d) whole rows at a time, a grid of (batch, tiles of T),
and work through the lanes in steps inside. Every product and sum is
float32; results are rounded once to the operands' dtype.

Forward: the tiles of T in order, the last rows of z of the tile before in a
VMEM scratch, as ssd_fwd hands its state from chunk to chunk. A tile's
convolution is k-1 rotations of z along the rows (`pltpu.roll`); the first
rows, which the rotation wraps, are made again from the carried rows.

Backward: one call gives d_bcu and the taps' float32 gradient. With g = C *
dy the gradient of z is the convolution run backwards in time,
dz_t = sum_j w_j g_{t+(k-1)-j}, so the tiles are walked last to first with the
first rows of the later tile's g carried; dC = dy * (the convolution, made
again in the tile) needs the rows of z *before* the tile, which a walk from
the end has not seen: they are read through a second, 16-row view of `bcu`
(1/16 of a tile's rows at the tile the cell runs). The taps' gradient is
summed over a batch row's tiles in an output block that stays in VMEM.

The kernels take a hidden width that is whole vectors of 128 lanes; T is
padded to whole tiles where it is not (zeros after a causal sequence change
nothing before them). Any other shape, and any backend but a TPU, runs
`gated_conv_plain`, differentiated by JAX.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _on_tpu  # a worker that cannot reach its chip fails there

_LANES = 128
# Rows carried between tiles and made again at a tile's edge: a whole tile of
# sublanes in bf16 (16 x 128), so the second store of those rows is aligned.
# The taps reach k - 1 rows back; k - 1 <= _HALO.
_HALO = 16
# Rows of T a grid step takes, and lanes a step inside works on. On the v5e at
# the benchmark's shape, (2, 8192, 3 x 2048) bf16 (my chip run, PR 41): 0.418
# ms forward and 0.763 backward, 78% and 75% of what their bytes take at 819
# GB/s; tiles of 128 to 1,024 rows by steps of 256 to 2,048 lanes all read
# 0.415-0.423 and 0.760-0.796 (1,024 rows fit VMEM at 256 lanes alone), and
# the plain form as XLA compiles it 2.42 and 5.26.
_TILE = 256
_LANE_STEP = 512
# A backward step holds bcu, d_bcu (3 d wide each) and dy twice over (the
# pipeline's two buffers): 14 MiB at the tile above, more than Mosaic's
# default scoped limit leaves beside the temporaries.
_VMEM_LIMIT = 64 << 20


def gated_conv_plain(bcu, w):
    """The equations as they stand: a sum of k shifted slices between the two
    gates, float32 inside, the result in bcu's dtype."""
    t, k = bcu.shape[1], w.shape[0]
    b, c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
    z = jnp.pad(b * u, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(z[:, j:j + t] * w[j].astype(jnp.float32) for j in range(k))
    return (c * conv).astype(bcu.dtype)


# --------------------------------------------------------------------------
# the kernels
# --------------------------------------------------------------------------


def _rolled(v, k, ahead=False):
    """v[t-s] for s = 1 .. k-1 by rotation along the rows (v[t+s] with
    `ahead`): right but for the k-1 rows the rotation wraps."""
    n = v.shape[0]
    return [pltpu.roll(v, n - s if ahead else s, 0) for s in range(1, k)]


def _at_edge(edge, beyond, k, ahead=False):
    """The same over a tile's first _HALO rows `edge`, with the _HALO rows
    before the tile, `beyond`, in place of what a rotation wraps; with
    `ahead` over its last rows, with the first rows of the tile after it."""
    n = edge.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, edge.shape, 0)
    wrapped = (lambda s: rows + s >= n) if ahead else (lambda s: rows < s)
    return [jnp.where(wrapped(s), there, here) for s, there, here in zip(
        range(1, k), _rolled(beyond, k, ahead), _rolled(edge, k, ahead))]


def _taps(v, shifted, w):
    """sum_s w[k-1-s] * shifted_s, shifted_0 = v: the convolution of v (or,
    shifted ahead, its transpose), w the taps' k rows, each (1, lanes)."""
    return w[-1] * v + sum(w[-1 - s] * shifted[s - 1] for s in range(1, len(w)))


def _fwd_kernel(x_ref, w_ref, y_ref, carried, *, d, step):
    """One tile of one batch row: y of the tile; the tile's last rows of z
    left in `carried` for the next."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        carried[...] = jnp.zeros(carried.shape, carried.dtype)

    f32, dtype, n = jnp.float32, y_ref.dtype, x_ref.shape[1]
    k = w_ref.shape[0]
    head = slice(0, _HALO)
    for lo in range(0, d, step):
        at = slice(lo, lo + step)

        def third(i, rows=slice(None)):
            return x_ref[0, rows, i * d + lo:i * d + lo + step].astype(f32)

        w = [w_ref[j:j + 1, at] for j in range(k)]
        z = third(0) * third(2)
        y_ref[0, :, at] = (third(1) * _taps(z, _rolled(z, k), w)).astype(dtype)
        # the first rows again, the carried rows before them
        conv = _taps(z[head], _at_edge(z[head], carried[:, at], k), w)
        y_ref[0, head, at] = (third(1, head) * conv).astype(dtype)
        carried[:, at] = z[n - _HALO:]


def _bwd_kernel(x_ref, dy_ref, w_ref, b_before, u_before, dx_ref, dw_ref, carried,
                *, d, step, tiles):
    """One tile of one batch row, tiles last to first: d_bcu of the tile, the
    tile's part of the taps' gradient added to dw_ref, the tile's first rows
    of g = C * dy left in `carried` for the tile before."""
    visit = pl.program_id(1)

    @pl.when(visit == 0)
    def _():
        carried[...] = jnp.zeros(carried.shape, carried.dtype)
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    f32, dtype, n = jnp.float32, dx_ref.dtype, x_ref.shape[1]
    k = w_ref.shape[0]
    # the view of the rows before a row's first tile is clamped onto the tile
    # itself: nothing comes before the first token
    has_before = (visit < tiles - 1).astype(f32)
    head, tail = slice(0, _HALO), slice(n - _HALO, n)
    for lo in range(0, d, step):
        at = slice(lo, lo + step)

        def third(i):
            return x_ref[0, :, i * d + lo:i * d + lo + step].astype(f32)

        def put(i, rows, value):
            dx_ref[0, rows, i * d + lo:i * d + lo + step] = value.astype(dtype)

        w = [w_ref[j:j + 1, at] for j in range(k)]
        b, c, u, dy = third(0), third(1), third(2), dy_ref[0, :, at].astype(f32)
        z, g = b * u, c * dy
        before = b_before[0, :, at].astype(f32) * u_before[0, :, at].astype(f32) * has_before
        back, back_head = _rolled(z, k), _at_edge(z[head], before, k)
        put(1, slice(None), dy * _taps(z, back, w))
        put(1, head, dy[head] * _taps(z[head], back_head, w))
        dz = _taps(g, _rolled(g, k, ahead=True), w)
        dz_tail = _taps(g[tail], _at_edge(g[tail], carried[:, at], k, ahead=True), w)
        put(0, slice(None), dz * u)
        put(0, tail, dz_tail * u[tail])
        put(2, slice(None), dz * b)
        put(2, tail, dz_tail * b[tail])
        # dw[k-1-s] = sum_t g_t z_{t-s}: the rotation's sum over every row,
        # with the wrapped first rows' part put right
        dw_ref[0, k - 1:k, at] += jnp.sum(g * z, axis=0, keepdims=True)
        for s in range(1, k):
            dw_ref[0, k - 1 - s:k - s, at] += (
                jnp.sum(g * back[s - 1], axis=0, keepdims=True)
                + jnp.sum(g[head] * (back_head[s - 1] - back[s - 1][head]),
                          axis=0, keepdims=True))
        carried[:, at] = g[head]


def _tile(t):
    """Rows of T a grid step takes, for a sequence of t: _TILE, or the whole
    sequence rounded up to the halo where that is shorter."""
    return min(_TILE, -(-t // _HALO) * _HALO)


def _lane_step(d):
    return next(s for s in (_LANE_STEP, 256, _LANES) if s <= _LANE_STEP and d % s == 0)


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                               vmem_limit_bytes=_VMEM_LIMIT)


def _fwd_call(bcu, w, interpret):
    b, t, d3 = bcu.shape
    d, tile = d3 // 3, _tile(t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, step=_lane_step(d)),
        grid=(b, t // tile),
        in_specs=[pl.BlockSpec((1, tile, d3), lambda i, j: (i, j, 0)),
                  pl.BlockSpec(w.shape, lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((1, tile, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, t, d), bcu.dtype),
        scratch_shapes=[pltpu.VMEM((_HALO, d), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="gated_conv_fwd",
    )(bcu, w)


def _bwd_call(bcu, w, dy, interpret):
    b, t, d3 = bcu.shape
    d, tile = d3 // 3, _tile(t)
    tiles, halos = t // tile, tile // _HALO
    at = lambda j: tiles - 1 - j
    before = lambda third: pl.BlockSpec(
        (1, _HALO, d), lambda i, j: (i, jnp.maximum(at(j) * halos - 1, 0), third))
    d_bcu, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, step=_lane_step(d), tiles=tiles),
        grid=(b, tiles),
        in_specs=[pl.BlockSpec((1, tile, d3), lambda i, j: (i, at(j), 0)),
                  pl.BlockSpec((1, tile, d), lambda i, j: (i, at(j), 0)),
                  pl.BlockSpec(w.shape, lambda i, j: (0, 0)),
                  before(0), before(2)],
        out_specs=[pl.BlockSpec((1, tile, d3), lambda i, j: (i, at(j), 0)),
                   pl.BlockSpec((1,) + w.shape, lambda i, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
                   jax.ShapeDtypeStruct((b,) + w.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_HALO, d), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="gated_conv_bwd",
    )(bcu, dy, w, bcu, bcu)
    return d_bcu, dw.sum(0)


def _padded(x, t):
    """x (b, T, ...) with zeros after it up to whole tiles of a sequence of t."""
    short = -t % _tile(t)
    return jnp.pad(x, ((0, 0), (0, short), (0, 0))) if short else x


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gated_conv(bcu, w, interpret):
    t = bcu.shape[1]
    return _fwd_call(_padded(bcu, t), w, interpret)[:, :t]


def _gated_conv_fwd_rule(bcu, w, interpret):
    return _gated_conv(bcu, w, interpret), (bcu, w)


def _gated_conv_bwd_rule(interpret, res, dy):
    bcu, w = res
    t = bcu.shape[1]
    d_bcu, dw = _bwd_call(_padded(bcu, t), w, _padded(dy, t), interpret)
    return d_bcu[:, :t], dw.astype(w.dtype)


_gated_conv.defvjp(_gated_conv_fwd_rule, _gated_conv_bwd_rule)


def conv_path(d: int, taps: int, reach: int = _HALO) -> str:
    """"pallas" or "xla" for a hidden width d and that many taps on this
    process's backend: the kernels where the width is whole vectors of lanes
    and the taps reach no further back than the rows a kernel carries
    (`reach`: _HALO for the gated pair, _EDGE for the Mamba one)."""
    return "pallas" if _on_tpu() and d % _LANES == 0 and taps - 1 <= reach else "xla"


def gated_short_conv(bcu, w, *, interpret=None):
    """y (b, T, d) in bcu's dtype from bcu (b, T, 3 d), the streams B, C, u
    side by side, and the taps w (k, d) float32: the module docstring's
    equations. `interpret` forces the kernels (True: in interpret mode), for
    the tests."""
    d = bcu.shape[-1] // 3
    if 3 * d != bcu.shape[-1] or w.shape[1] != d:
        raise ValueError(f"bcu {bcu.shape} is not three streams as wide as the taps {w.shape}")
    w = w.astype(jnp.float32)
    if interpret is not None or conv_path(d, w.shape[0]) == "pallas":
        return _gated_conv(bcu, w, bool(interpret))
    return gated_conv_plain(bcu, w)


# --------------------------------------------------------------------------
# the Mamba mixer's convolution: the same walk over tiles, a bias and silu
# --------------------------------------------------------------------------
#
# With x (b, T, C) in the model's dtype, taps w (k, C) and bias (C,) float32:
#
#     a_t = bias + sum_{j=0..k-1} w_j * x_{t-(k-1)+j}     x before a row's
#     y_t = silu(a_t)                                      first token is 0
#
# float32 inside, y rounded once to x's dtype. Backward, one call: with
# g = dy * silu'(a), a made again from x and not stored,
# dx_t = sum_j w_j g_{t+(k-1)-j}, dw_j = sum_t g_t x_{t-(k-1)+j}, dbias =
# sum_t g_t; dw and dbias float32, summed over a batch row's tiles in blocks
# that stay in VMEM and over batch rows outside, as gated_conv_bwd's dw.
#
# `causal_conv_fwd` and `causal_conv_bwd` (the names the compiled step and
# the trace show) take a grid of (batch, blocks of lanes, tiles of T): a block
# is a tile's rows of as many of x's lanes as _BLOCK_BYTES allows, read where
# they lie in a wider array (the mixer's [z | xBC | dt]), and the body works
# through it a vector of lanes and a run of _ROWS rows at a time, each run
# with the _EDGE rows before it (after it, for g) beside it, so that what a
# rotation wraps is never read. The gated pair's way, a whole tile's z, its
# rotations and its products each a value of 128 vregs, is bound by the stores
# and loads of values that do not fit the 64 registers (one store a cycle),
# which the gated pair's bytes hide and this operator's do not: at (2, 8192,
# 6144) 1.17 ms forward and 2.33 backward that way, 0.87 and 1.31 a run of
# rows at a time in blocks of 256 x 512, 0.62 and 1.01 in blocks of all of
# x's width read in place (79% and 73% of what their bytes take at 819 GB/s;
# my chip runs, PR 48).
_ROWS = 64
_EDGE = 8  # float32 rows of one vreg; the taps reach k - 1 <= _EDGE


def causal_conv_plain(x, w, bias):
    """silu(bias + sum_j w_j x_{t-(k-1)+j}) as the mixer wrote it before it
    had kernels: k shifted slices of the padded float32 rows, the result in
    x's dtype."""
    t, k = x.shape[1], w.shape[0]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    conv = bias + sum(padded[:, i:i + t] * w[i] for i in range(k))
    return jax.nn.silu(conv).astype(x.dtype)


def _sigmoid(a):
    """1 / (1 + exp(-a)) in float32: the unit's reciprocal and one Newton step,
    which is the general division without its care for zeros, infinities and
    NaNs (a third of the forward body's vector operations), none of which
    1 + exp(-a) with a held above -80 can be. On the chip dw and dbias stand
    3e-7 off the plain form's either way (my chip runs, PR 48); interpret mode
    starts from a cruder reciprocal and ends 2e-5 off."""
    d = 1 + jnp.exp(-jnp.maximum(a, -80.0))
    r = pl.reciprocal(d, approx=True)
    return r * (2 - d * r)


def _reaching(x, beyond, k, ahead=False):
    """x[t-s] for s = 1 .. k-1 over a run of rows x, with the _EDGE rows
    before it, `beyond`, on top (x[t+s] with `ahead`, `beyond` the rows after
    it, below): rotations of the two together, so that what a rotation wraps
    is never among the rows kept."""
    both = jnp.concatenate([x, beyond] if ahead else [beyond, x], axis=0)
    kept = slice(0, x.shape[0]) if ahead else slice(_EDGE, None)
    return [v[kept] for v in _rolled(both, k, ahead)]


def _by_lanes(refs, body):
    """body(ref, at, at_ref) for every vector of _LANES lanes of a block that
    `refs` hold side by side (one result, or the parts it is split into): `at`
    the vector's lanes in the block, `at_ref` in its ref. One loop a ref, not
    unrolled: the body's code once a part, whatever the width."""
    lo = 0
    for ref in refs:
        def one(i, carry, ref=ref, lo=lo):
            at = pl.multiple_of(i * _LANES, _LANES)
            body(ref, pl.ds(lo + at, _LANES), pl.ds(at, _LANES))
            return carry

        jax.lax.fori_loop(0, ref.shape[2] // _LANES, one, None)
        lo += ref.shape[2]


def _causal_fwd_kernel(x_ref, w_ref, bias_ref, *rest, rows):
    """One tile of one batch row's block of lanes: y of the tile (`rest`: y's
    parts, then `carried`), a run of rows at a time with the _EDGE rows
    before it on top, so that what a rotation wraps is never read; the tile's
    last rows of x left in `carried` for the next."""
    *y_refs, carried = rest

    @pl.when(pl.program_id(2) == 0)
    def _():
        carried[...] = jnp.zeros(carried.shape, carried.dtype)

    f32, k = jnp.float32, w_ref.shape[0]
    starts = range(0, x_ref.shape[1], rows)

    def lanes(y_ref, at, at_y):
        w, bias = [w_ref[j:j + 1, at] for j in range(k)], bias_ref[:, at]
        before = carried[:, at]
        for r0 in starts:
            x = x_ref[0, r0:r0 + rows, at].astype(f32)
            a = bias + _taps(x, _reaching(x, before, k), w)
            y_ref[0, r0:r0 + rows, at_y] = (a * _sigmoid(a)).astype(y_ref.dtype)
            before = x[rows - _EDGE:]
        carried[:, at] = before

    _by_lanes(y_refs, lanes)


def _causal_bwd_kernel(x_ref, w_ref, bias_ref, x_before, *rest, rows, parts, tiles):
    """One tile of one batch row's block of lanes, tiles and the runs of rows
    in a tile last to first: dx of the tile, the tile's part of the taps' and
    the bias's gradients added to dw_ref and dbias_ref, the tile's first rows
    of g = dy * silu'(a) left in `carried` for the tile before. a is made
    again from x, the rows before the tile read through `x_before` as
    _bwd_kernel reads its own. `rest`: dy's `parts`; the buffer dx_ref is a
    part of, where one was given (not touched here); the three results and
    `carried`."""
    dy_refs, (dx_ref, dw_ref, dbias_ref, carried) = rest[:parts], rest[-4:]
    visit = pl.program_id(2)

    @pl.when(visit == 0)
    def _():
        carried[...] = jnp.zeros(carried.shape, carried.dtype)
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)
        dbias_ref[...] = jnp.zeros(dbias_ref.shape, dbias_ref.dtype)

    f32, dtype, k = jnp.float32, dx_ref.dtype, w_ref.shape[0]
    starts = range(0, x_ref.shape[1], rows)
    has_before = (visit < tiles - 1).astype(f32)
    by_sublane = lambda v: sum(v[i:i + _EDGE] for i in range(0, rows, _EDGE))

    def lanes(dy_ref, at, at_dy):
        w, bias = [w_ref[j:j + 1, at] for j in range(k)], bias_ref[:, at]
        ahead = carried[:, at]
        sums = [jnp.zeros((_EDGE, _LANES), f32)] * (k + 1)  # dbias, then dw's k rows
        for r0 in reversed(starts):
            x = x_ref[0, r0:r0 + rows, at].astype(f32)
            dy = dy_ref[0, r0:r0 + rows, at_dy].astype(f32)
            before = (x_ref[0, r0 - _HALO:r0, at].astype(f32) if r0 else
                      x_before[0, :, at].astype(f32) * has_before)[_HALO - _EDGE:]
            back = _reaching(x, before, k)
            a = bias + _taps(x, back, w)
            s = _sigmoid(a)
            g = dy * (s * (1 + a * (1 - s)))  # dy * silu'(a)
            dx_ref[0, r0:r0 + rows, at] = _taps(g, _reaching(g, ahead, k, ahead=True),
                                                w).astype(dtype)
            # dw[k-1-s] = sum_t g_t x_{t-s}
            sums = [acc + by_sublane(v) for acc, v in zip(
                sums, [g] + [g * v for v in back[::-1] + [x]])]
            ahead = g[:_EDGE]
        carried[:, at] = ahead
        dbias_ref[0, :, at] += jnp.sum(sums[0], axis=0, keepdims=True)
        for j in range(k):
            dw_ref[0, j:j + 1, at] += jnp.sum(sums[1 + j], axis=0, keepdims=True)

    _by_lanes(dy_refs, lanes)


# Bytes of a block of x the Mamba pair's grid step takes, in bf16: rows of a
# tile by as many of x's lanes as divide its width and stay under this (a row
# of a block is one run of HBM: the wider, the nearer the copies come to the
# memory's rate; blocks 256 lanes wide ran granite's calls at half the rate).
_BLOCK_BYTES = 3 << 20


class _Cut(NamedTuple):
    """How the Mamba pair's calls cut their operands: static, part of what
    their jits are keyed by."""
    tile: int  # rows of T a grid step takes
    width: int  # lanes of x a grid step takes: a grid axis walks x's width in these
    rows: int  # rows of a tile the body works on at a time
    parts: tuple  # the lanes at which the calls themselves split y and take dy


def _cut(t, c, cuts=()):
    """The cut for a sequence of t, x's width c and y wanted split at the
    lanes `cuts`: the calls split it where those are whole vectors of lanes
    and a block is all of x's width, else not (the split is then XLA's,
    beside the calls)."""
    tile, vectors = _tile(t), c // _LANES
    width = _LANES * max(n for n in range(1, vectors + 1) if vectors % n == 0
                         and (n == 1 or tile * n * _LANES * 2 <= _BLOCK_BYTES))
    whole = width == c and all(cut % _LANES == 0 for cut in cuts)
    return _Cut(tile, width, next(r for r in (_ROWS, 32, _HALO) if tile % r == 0),
                tuple(cuts) if whole else ())


_CAUSAL_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)


def _window(rows, width, index_map):
    """A block of `rows` x `width` of one batch row at any row and lane that
    are whole tiles of its dtype (_HALO rows, _LANES lanes): `index_map`
    gives the three starts."""
    def starts(*ids):
        i, row, lane = index_map(*ids)
        return i, pl.multiple_of(row, _HALO), pl.multiple_of(lane, _LANES)

    return pl.BlockSpec((pl.Element(1), pl.Element(rows), pl.Element(width)), starts)


@functools.partial(jax.jit, static_argnames=("at", "cut", "interpret"))
def _causal_fwd_call(wide, w, bias, *, at, cut, interpret):
    """y (b, T, C), in the parts `cut` says, of the C = w.shape[1] lanes of
    `wide` from lane `at`, whole vectors of lanes: the blocks are read where
    they lie. Under a jit of its own, as `_causal_bwd_call` and
    ops/attention.py's calls: a model's layers share one trace and one
    lowering of a kernel (granite's 27 calls a step took 5 s to lower each on
    its own)."""
    b, t, _ = wide.shape
    k, c = w.shape
    tile, width, rows, cuts = cut
    parts = [hi - lo for lo, hi in zip((0, *cuts), (*cuts, width))]
    return pl.pallas_call(
        functools.partial(_causal_fwd_kernel, rows=rows),
        grid=(b, c // width, t // tile),
        in_specs=[_window(tile, width, lambda i, l, j: (i, j * tile, at + l * width)),
                  pl.BlockSpec((k, width), lambda i, l, j: (0, l)),
                  pl.BlockSpec((1, width), lambda i, l, j: (0, l))],
        out_specs=[pl.BlockSpec((1, tile, part), lambda i, l, j: (i, j, l)) for part in parts],
        out_shape=[jax.ShapeDtypeStruct((b, t, part * (c // width)), wide.dtype)
                   for part in parts],
        scratch_shapes=[pltpu.VMEM((_EDGE, width), jnp.float32)],
        compiler_params=_CAUSAL_PARAMS, interpret=interpret, name="causal_conv_fwd",
    )(wide, w, bias[None])


@functools.partial(jax.jit, static_argnames=("at", "cut", "interpret"))
def _causal_bwd_call(wide, w, bias, dys, d_wide, *, at, cut, interpret):
    """(d_wide, dw, dbias) from dy in the parts the forward call gave y in:
    x's gradient written over lanes `at` .. of `d_wide`, which holds the
    gradients of wide's other lanes and is given up to the call (the result
    is that buffer); with d_wide None, wide is x alone and the result a
    buffer of its own."""
    b, t, _ = wide.shape
    k, c = w.shape
    tile, width, rows, _ = cut
    tiles = t // tile
    back = lambda j: tiles - 1 - j
    x_tile = _window(tile, width, lambda i, l, j: (i, back(j) * tile, at + l * width))
    sums = lambda i, l, j: (i, 0, l)
    given = () if d_wide is None else (d_wide,)
    dx, dw, dbias = pl.pallas_call(
        functools.partial(_causal_bwd_kernel, rows=rows, parts=len(dys), tiles=tiles),
        grid=(b, c // width, tiles),
        in_specs=[x_tile,
                  pl.BlockSpec((k, width), lambda i, l, j: (0, l)),
                  pl.BlockSpec((1, width), lambda i, l, j: (0, l)),
                  _window(_HALO, width, lambda i, l, j: (
                      i, jnp.maximum(back(j) * tile - _HALO, 0), at + l * width))]
                 + [pl.BlockSpec((1, tile, dy.shape[2] // (c // width)),
                                 lambda i, l, j: (i, back(j), l)) for dy in dys]
                 + [pl.BlockSpec(memory_space=pl.ANY) for _ in given],
        out_specs=[x_tile, pl.BlockSpec((1, k, width), sums), pl.BlockSpec((1, 1, width), sums)],
        out_shape=[jax.ShapeDtypeStruct(wide.shape, wide.dtype),
                   jax.ShapeDtypeStruct((b, k, c), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, c), jnp.float32)],
        input_output_aliases={4 + len(dys): 0} if given else {},
        scratch_shapes=[pltpu.VMEM((_EDGE, width), jnp.float32)],
        compiler_params=_CAUSAL_PARAMS, interpret=interpret, name="causal_conv_bwd",
    )(wide, w, bias[None], wide, *dys, *given)
    return dx, dw.sum(0), dbias.sum((0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv_within(wide, w, bias, at, cuts, interpret):
    t, c = wide.shape[1], w.shape[1]
    cut = _cut(t, c, cuts)
    ys = _causal_fwd_call(_padded(wide, t), w, bias, at=at, cut=cut, interpret=interpret)
    if cut.parts != cuts:
        ys = jnp.split(ys[0], cuts, axis=-1)
    return (wide[..., :at], *(y[:, :t] for y in ys), wide[..., at + c:])


def _conv_within_fwd_rule(wide, w, bias, at, cuts, interpret):
    return _conv_within(wide, w, bias, at, cuts, interpret), (wide, w, bias)


def _conv_within_bwd_rule(at, cuts, interpret, res, grads):
    wide, w, bias = res
    d_left, *dys, d_right = grads
    t, c = wide.shape[1], w.shape[1]
    cut = _cut(t, c, cuts)
    if cut.parts != cuts:
        dys = [jnp.concatenate(dys, axis=-1)]
    # the neighbours' gradients written once, x's between them by the call
    d_wide = (_padded(jnp.concatenate(
        [d_left, jnp.zeros((*d_left.shape[:2], c), d_left.dtype), d_right], axis=-1), t)
              if wide.shape[2] > c else None)
    d_wide, dw, dbias = _causal_bwd_call(_padded(wide, t), w, bias, [_padded(dy, t) for dy in dys],
                                         d_wide, at=at, cut=cut, interpret=interpret)
    return d_wide[:, :t], dw, dbias


_conv_within.defvjp(_conv_within_fwd_rule, _conv_within_bwd_rule)


def causal_conv_within(wide, w, bias, at=0, cuts=(), *, interpret=None):
    """(left, *ys, right) from wide (b, T, at + C + more), the taps w (k, C)
    and the bias (C,), both float32: wide's lanes before `at`; y = silu(bias
    + the causal convolution of its next C lanes) in wide's dtype, in the
    parts that the lanes `cuts` of y cut it into (one where there are none);
    and its lanes after them. The Mamba mixer's input projection is such an
    array, [z | xBC | dt], and x, B and C such parts. The kernels where
    `conv_path` says so and x starts at a whole vector of wide's lanes,
    reading x where it lies and writing y's parts as
    arrays of their own (a copy of C lanes out of a wider array cost as much
    as the forward call, and the three gradients put side by side again
    slowed the projection's backward matmul that read them: my chip runs,
    PR 48); elsewhere the splits and `causal_conv_plain`. `interpret` forces
    the kernels (True: in interpret mode), for the tests."""
    c, cuts = w.shape[1], tuple(cuts)
    if bias.shape != (c,) or at + c > wide.shape[2] or sorted({0, c, *cuts}) != [0, *cuts, c]:
        raise ValueError(f"taps {w.shape}, bias {bias.shape}, lane {at} of {wide.shape}, {cuts}")
    kernels = interpret is not None or conv_path(c, w.shape[0], _EDGE) == "pallas"
    if kernels and at % _LANES == 0:
        return _conv_within(wide, w.astype(jnp.float32), bias.astype(jnp.float32), at, cuts,
                            bool(interpret))
    left, x, right = jnp.split(wide, [at, at + c], axis=-1)
    return (left, *jnp.split(causal_conv_plain(x, w, bias), cuts, axis=-1), right)

