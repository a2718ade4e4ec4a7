"""The gated short convolution of LFM2's `conv` layers (Liquid AI, 2025): a
pallas kernel pair on a TPU, the same sums in jax.numpy elsewhere.

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


def conv_path(d: int, taps: int) -> str:
    """"pallas" or "xla" for a hidden width d and that many taps on this
    process's backend: the kernels where the width is whole vectors of lanes
    and the taps reach no further back than the carried rows."""
    return "pallas" if _on_tpu() and d % _LANES == 0 and taps - 1 <= _HALO else "xla"


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
