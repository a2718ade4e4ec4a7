"""The Mamba-2 mixer's gated norm a group at a time, a pallas kernel pair on a
TPU and the mixer's own lines in jax.numpy elsewhere.

With y (b, T, C) from the scan, z (b, T, C) from the input projection, a
weight (C,) and the C channels in G equal groups of W = C / G lanes:

    x = y * silu(z)
    out[group g] = x[g] / sqrt(mean(x[g]^2) + eps) * weight[g]

Per token the operator reads 2 C elements and writes C, its backward reads
3 C and writes 2 C: it is bound by its bytes, and the kernels move each once.
Written as `reshape(..., G, W)`, norm, `reshape` back, the group axis takes
the place of T as the second-minor one, which under the TPU's (8, 128) tiling
is no bitcast: XLA wrote the whole float32 array out in the other layout and
back, forward and backward (23 of the 46 ms a step the gate and the norm took
in nemotron3_nano_l9_ep16.t8192, PERF.md section 6, PR 49). Here a group's
lanes are summed where they lie.

`gated_norm_fwd` and `gated_norm_bwd` (the names the compiled step and the
profiler's trace show) take a grid of (batch, tiles of T), whole rows of C
lanes a block, and work through a block a group and a run of _ROWS rows at a
time: (16, 512) float32 values at the cell's widths, eight vregs each, so
that the body's values stay in registers (ops/short_conv.py's second half
says what a whole tile's value at a time costs), a few runs an iteration. z
is read where the input projection wrote it, inside [z | xBC | dt], by the
block's index map. Every product and sum is float32 from y and z as read;
`out`, dy and dz are rounded once.

Backward, one call, with s = sigmoid(z), r = rsqrt(mean(x^2) + eps), both
made again from y and z (two exps a value are cheaper than an array kept):

    dn = dout * weight                      dweight = sum_{b,t} dout * x * r
    dx = r * dn - x * r^3 * mean(dn * x)    over the group's lanes
    dy = dx * z * s                         dz = dx * y * s * (1 + z * (1 - s))

dweight is summed in float32 over a batch row's tiles in an output block that
stays in VMEM, eight sublanes of partial sums, and over those and the batch
rows outside.

The kernels take groups that are whole vectors of 128 lanes; T is padded to
whole tiles where it is not (a row of zeros norms to zeros). Any other
width, and any backend but a TPU, runs `gated_norm_plain`, differentiated by
JAX. One group is not this file's: `models/llama.py:RMSNorm` norms all
channels at once in lines XLA fuses into their neighbours.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _on_tpu  # a worker that cannot reach its chip fails there
from ray_tpu.ops.short_conv import _LANES, _VMEM_LIMIT, _padded, _sigmoid, _tile, _window

# Rows of a tile the body works on at a time: one packed bf16 vreg a vector of
# lanes. A run's chain (exp, reciprocal, the sum over lanes, rsqrt) is longer
# than its work, so a loop's iteration takes several runs for the scheduler to
# lay side by side: at the cell's shape, ten calls in one program (my chip
# run, PR 49, call 3), forward 1.27 ms a run an iteration, 0.81 two, 0.77
# eight; backward 1.42, 1.19 two, 1.18 four (its values are three times the
# forward's). 0.77 and 1.19 are 522 and 569 GB/s of the 819 the chip has.
_ROWS = 16
_FWD_RUNS, _BWD_RUNS = 8, 2


def norm_by_group(x, weight, eps, groups):
    """RMSNorm of each of `groups` equal parts of x's last axis on its own,
    times `weight`, as the lines XLA compiles: float32 over a (..., G, W)
    view, rounded, times the weight in x's dtype."""
    parts = lambda v: v.reshape(*v.shape[:-1], groups, -1)
    x32 = parts(x).astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * parts(weight.astype(x.dtype))
    return normed.reshape(x.shape)


def gated_norm_plain(y, z, weight, eps, groups):
    """The mixer's lines before it had kernels: the gate in y's dtype, then
    `norm_by_group`."""
    return norm_by_group(y * jax.nn.silu(z), weight, eps, groups)


def _over_runs(tile, together, body, carry=None):
    """body(rows, carry) -> carry over a tile's runs of _ROWS rows, in a loop
    whose iteration takes `together` of them (or as many as divide the
    tile's)."""
    runs = tile // _ROWS
    together = next(n for n in range(min(together, runs), 0, -1) if runs % n == 0)

    def some(i, carry):
        for j in range(together):
            carry = body(pl.ds(pl.multiple_of((i * together + j) * _ROWS, _ROWS), _ROWS), carry)
        return carry

    return jax.lax.fori_loop(0, runs // together, some, carry)


def _lanes_of(g, width):
    return pl.ds(pl.multiple_of(g * width, _LANES), width)


def _gated(y_ref, z_ref, rows, at, eps):
    """(y, z, s, x, r) of a run of rows of a group: s = sigmoid(z), x = y z s,
    r = rsqrt(mean(x^2) + eps) a row, all float32."""
    f32 = jnp.float32
    y, z = y_ref[0, rows, at].astype(f32), z_ref[0, rows, at].astype(f32)
    s = _sigmoid(z)
    x = y * (z * s)
    r = jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) * (1.0 / x.shape[-1]) + eps)
    return y, z, s, x, r


def _fwd_kernel(y_ref, z_ref, w_ref, out_ref, *, groups, eps):
    """One tile of one batch row: `out` of the tile, a group and a run of
    rows at a time."""
    tile, width = y_ref.shape[1], y_ref.shape[2] // groups

    def group(g, _):
        at = _lanes_of(g, width)

        def run(rows, _):
            *_, x, r = _gated(y_ref, z_ref, rows, at, eps)
            out_ref[0, rows, at] = (x * r * w_ref[:, at]).astype(out_ref.dtype)

        _over_runs(tile, _FWD_RUNS, run)

    jax.lax.fori_loop(0, groups, group, None)


def _bwd_kernel(y_ref, z_ref, w_ref, dout_ref, dy_ref, dz_ref, dw_ref, *, groups, eps):
    """One tile of one batch row: dy and dz of the tile, the tile's part of
    the weight's gradient added to dw_ref, eight sublanes of partial sums."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    f32, dtype = jnp.float32, dy_ref.dtype
    tile, width = y_ref.shape[1], y_ref.shape[2] // groups
    sublanes = dw_ref.shape[1]

    def group(g, _):
        at = _lanes_of(g, width)

        def run(rows, acc):
            y, z, s, x, r = _gated(y_ref, z_ref, rows, at, eps)
            dout = dout_ref[0, rows, at].astype(f32)
            dn = dout * w_ref[:, at]
            k = jnp.sum(dn * x, axis=-1, keepdims=True) * (r * r * r * (1.0 / width))
            dx = r * dn - x * k
            dy_ref[0, rows, at] = (dx * (z * s)).astype(dtype)
            dz_ref[0, rows, at] = (dx * y * (s * (1 + z * (1 - s)))).astype(dtype)
            dw = dout * (x * r)
            return acc + sum(dw[i:i + sublanes] for i in range(0, _ROWS, sublanes))

        dw_ref[0, :, at] += _over_runs(tile, _BWD_RUNS, run, jnp.zeros((sublanes, width), f32))

    jax.lax.fori_loop(0, groups, group, None)


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                               vmem_limit_bytes=_VMEM_LIMIT)
_SUBLANES = 8  # float32 rows of one vreg: the partial sums of dweight a batch row


def _specs(y, at):
    """The grid of (batch, tiles of T) and the blocks of y's shape, of z read
    at lane `at` of a wider array, and of the weight."""
    b, t, c = y.shape
    tile = _tile(t)
    rows = pl.BlockSpec((1, tile, c), lambda i, j: (i, j, 0))
    return ((b, t // tile), rows, _window(tile, c, lambda i, j: (i, j * tile, at)),
            pl.BlockSpec((1, c), lambda i, j: (0, 0)))


@functools.partial(jax.jit, static_argnames=("eps", "groups", "at", "interpret"))
def _fwd_call(y, wide, weight, *, eps, groups, at, interpret):
    """out (b, T, C) from y and the C lanes of `wide` from lane `at`, a whole
    vector of lanes. Under a jit of its own, as ops/short_conv.py's calls: a
    model's layers share one trace and one lowering of a kernel."""
    grid, rows, z_rows, w_row = _specs(y, at)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, groups=groups, eps=eps),
        grid=grid, in_specs=[rows, z_rows, w_row], out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=_PARAMS, interpret=interpret, name="gated_norm_fwd",
    )(y, wide, weight[None])


@functools.partial(jax.jit, static_argnames=("eps", "groups", "at", "interpret"))
def _bwd_call(y, wide, weight, dout, *, eps, groups, at, interpret):
    """(dy, dz, dweight): dz an array of its own, (b, T, C)."""
    b, _, c = y.shape
    grid, rows, z_rows, w_row = _specs(y, at)
    shape = jax.ShapeDtypeStruct(y.shape, y.dtype)
    dy, dz, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, groups=groups, eps=eps),
        grid=grid, in_specs=[rows, z_rows, w_row, rows],
        out_specs=[rows, rows, pl.BlockSpec((1, _SUBLANES, c), lambda i, j: (i, 0, 0))],
        out_shape=[shape, shape, jax.ShapeDtypeStruct((b, _SUBLANES, c), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="gated_norm_bwd",
    )(y, wide, weight[None], dout)
    return dy, dz, dw.sum((0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _gated_norm(y, z, wide, weight, eps, groups, at, interpret):
    del z  # its values are read in `wide`; the gradient is taken by it
    t = y.shape[1]
    return _fwd_call(_padded(y, t), _padded(wide, t), weight, eps=eps, groups=groups, at=at,
                     interpret=interpret)[:, :t]


def _gated_norm_fwd_rule(y, z, wide, weight, eps, groups, at, interpret):
    return _gated_norm(y, z, wide, weight, eps, groups, at, interpret), (y, wide, weight)


def _gated_norm_bwd_rule(eps, groups, at, interpret, res, dout):
    y, wide, weight = res
    t = y.shape[1]
    dy, dz, dw = _bwd_call(_padded(y, t), _padded(wide, t), weight, _padded(dout, t), eps=eps,
                           groups=groups, at=at, interpret=interpret)
    return dy[:, :t], dz[:, :t], None, dw


_gated_norm.defvjp(_gated_norm_fwd_rule, _gated_norm_bwd_rule)


def norm_path(width: int) -> str:
    """"pallas" or "xla" for groups of `width` lanes on this process's
    backend: the kernels where a group is whole vectors of lanes."""
    return "pallas" if _on_tpu() and width % _LANES == 0 else "xla"


def gated_norm(y, z, weight, eps, groups, within=None, *, interpret=None):
    """RMSNorm(y * silu(z)) over each of `groups` equal parts of the last
    axis on its own, times `weight` (C,) float32, in y's dtype: y and z
    (b, T, C). `within`, (wide, at): an array that holds z's values at its
    lanes `at` on (the mixer's [z | xBC | dt], of which z is a slice), which
    the kernels then read in z's place, where it lies: a slice of lanes
    handed to a call is a copy as long as the call (ops/short_conv.py). z's
    own slice is then no operand of anything and XLA drops it; the gradient
    is z's all the same. The kernels where `norm_path` says so and z starts
    at a whole vector of lanes; elsewhere `gated_norm_plain`. `interpret`
    forces the kernels (True: in interpret mode), for the tests."""
    c = y.shape[-1]
    wide, at = (z, 0) if within is None else within
    if (z.shape != y.shape or weight.shape != (c,) or c % groups or at + c > wide.shape[-1]
            or wide.shape[:-1] != y.shape[:-1]):
        raise ValueError(f"y {y.shape}, z {z.shape} at lane {at} of {wide.shape}, weight "
                         f"{weight.shape}, {groups} groups")
    kernels = interpret is not None or norm_path(c // groups) == "pallas"
    if kernels and at % _LANES == 0:
        return _gated_norm(y, z, jax.lax.stop_gradient(wide), weight.astype(jnp.float32),
                           float(eps), groups, at, bool(interpret))
    return gated_norm_plain(y, z, weight, eps, groups)
