"""The q/k norm and the rotary embedding between a projection and the flash
calls, a pallas kernel pair that reads the heads where the projection wrote
them and writes the rows the flash calls take.

With x (B, T, Hin * 128) as `wq` or `wk` wrote it, a head its 128 lanes:

    n = x * rsqrt(mean(x^2 over the head's lanes) + eps) * weight     (a layer that norms q and k)
    y = n * cos + roll(n, 64 lanes) * sin_signed                      (a rotary layer)
    y = y * scale                                                     (q alone, where a layer says so)

cos = [c | c] and sin_signed = [-s | s], float32 (T, 128) (`rope_tables`):
rotate-half over a head's 128 lanes is one lane rotation by 64. `y` goes out
as (B * Hin * rep, T, 128), a head a row as `ops/attention.py:_as_rows` gives
a head of 128, each head written to `rep` rows: the repeat of the key-value
heads to their query heads.

Written as `reshape(B, T, H, 128)`, norm, rotary, repeat and `_as_rows`'
transpose, the head axis takes the place of T as the second-minor one, which
under the TPU's (8, 128) tiling is no bitcast: XLA passed over q and k five
times a layer, `copy` rows among them, and `wq` wrote float32 because the
norm read it so (49.1 ms of 412 a step in trinity_mini_l5_ep16.t8192, PERF.md
section 6, PR 53; ops/gated_norm.py found the same of the Mamba mixer's
grouped norm). Here an operand is read once and written once.

`qk_prep_fwd` and `qk_prep_bwd` (the names the compiled step and the
profiler's trace show) take a grid of (batch, tiles of T), whole rows of x a
block, and work through a block a head at a time, (tile, 128) values, a few
heads a loop's iteration. Every product and sum is float32 from x as read;
`y` and dx are rounded once. The mean over a head's lanes is made on the MXU
(`_lane_mean`), float32 too.

Backward, one call, with r = rsqrt(mean(x^2) + eps) and xh = x * r made again
from x, dy the cotangent's rows summed over a head's `rep`:

    dn = (dy * cos - roll(dy, 64) * sin_signed) * scale
    dweight = sum_{b,t,head} dn * xh           g = dn * weight
    dx = r * (g - xh * mean(g * xh))           over the head's lanes

(the transpose of a rotation by 64 of 128 lanes is itself, and
roll(sin_signed) = -sin_signed). dweight is summed in float32 over a batch
row's tiles in an output block that stays in VMEM, eight sublanes of partial
sums, and over those and the batch rows outside. The pair's only residual is
x, and only where the layer norms: the turn alone is linear.

This file has no form of its own for other backends and widths:
`models/llama.py:LlamaAttention` takes the pair where it applies and runs its
own lines, the plain form, elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.gated_norm import _PARAMS, _SUBLANES
from ray_tpu.ops.short_conv import _LANES

# Rows of T a grid step takes, a head's (256, 128) the value the body works on,
# and the heads a loop's iteration takes for the scheduler to lay side by side:
# the chain from a head's squares through the MXU to rsqrt is longer than its
# work. At (2, 8192, 32 x 128) bf16, normed and turned, ten calls in one program
# (my chip run, PR 53, call 4), forward / backward ms: one head an iteration
# 0.84 / 1.17, two 0.63 / 0.93, four 0.57 / 0.88, all thirty-two 0.54 / 0.80 (6 s
# to compile); runs of 64 and 128 rows of a head at a time 2.19 / 2.58 and 1.26 /
# 1.65 (call 3: an iteration costs about 140 cycles whatever it holds); tiles of
# 512 the same as 256. 0.57 and 0.88 are 467 and 455 GB/s of the chip's 819; the
# turn alone reads 0.55 / 0.57 at any of these, k's calls 0.36 / 0.35.
_TILE = 256
_HEADS = 4


def rope_tables(angles, scale: float = 1.0):
    """(cos, sin_signed), float32 (T, 128), from `rope_angles`' (T, 64):
    [c | c] and [-s | s], both times `scale` (YaRN's attention factor), so
    that rotate-half is x * cos + roll(x, 64) * sin_signed."""
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    return jnp.concatenate([cos, cos], axis=-1), jnp.concatenate([-sin, sin], axis=-1)


def _lane_mean():
    """v (rows, 128) float32 -> the mean over a row's lanes, in every lane of
    the row, float32: on the MXU, which has nothing else to do here. v is
    three bf16 parts that add up to it exactly (a float32's 24 bits are three
    times 8), each times a (128, 128) matrix of 1/128 and summed in float32:
    the sum a float32 sum of the float32 values. The unit that sums across
    lanes takes 52 cycles a vreg (`jnp.sum(axis=-1)`: q's normed forward 2.78
    ms where its un-normed one took 0.55, my chip run, PR 53, call 2), a
    head of 128 lanes being one vreg a sum where the Mamba mixer's groups
    of 512 add four on the vector unit first."""
    each = jnp.full((_LANES, _LANES), 1.0 / _LANES, jnp.bfloat16)

    def mean(v):
        total = None
        for _ in range(3):
            part = v.astype(jnp.bfloat16)
            v = v - part.astype(jnp.float32)
            # bf16 operands as they are, whatever `jax.default_matmul_precision` the caller is
            # under (chip_smoke.py's "highest" asked Mosaic for a float32 matmul of them)
            dot = jnp.dot(part, each, precision=jax.lax.Precision.DEFAULT,
                          preferred_element_type=jnp.float32)
            total = dot if total is None else total + dot
        return total

    return mean


def _over(heads, body, carry=None):
    """body(lanes, h, carry) -> carry over the heads of a tile, _HEADS of
    them a loop's iteration (or as many as divide the heads)."""
    together = next(n for n in range(min(_HEADS, heads), 0, -1) if heads % n == 0)

    def some(g, carry):
        for j in range(together):
            h = g * together + j
            carry = body(pl.ds(pl.multiple_of(h * _LANES, _LANES), _LANES), h, carry)
        return carry

    return jax.lax.fori_loop(0, heads // together, some, carry)


def _fwd_kernel(*refs, heads, rep, norm, rotary, eps, scale):
    """One tile of one batch row: every head's rows of the tile, normed,
    turned and written to the head's `rep` rows of the output."""
    refs = list(refs)
    x_ref, out_ref = refs.pop(0), refs.pop()
    if norm:
        w, mean = refs.pop(0)[...], _lane_mean()

    def head(lanes, h, _):
        y = x_ref[0, :, lanes].astype(jnp.float32)
        if norm:
            y = y * jax.lax.rsqrt(mean(y * y) + eps) * w
        if rotary:
            y = y * refs[0][...] + pltpu.roll(y, _LANES // 2, 1) * refs[1][...]
        if scale != 1.0:
            y = y * scale
        y = y.astype(out_ref.dtype)
        for j in range(rep):
            out_ref[h * rep + j] = y

    _over(heads, head)


def _bwd_kernel(*refs, heads, rep, norm, rotary, eps, scale):
    """One tile of one batch row: dx of the tile from the rows' cotangent,
    the tile's part of the weight's gradient added to dw_ref."""
    refs = list(refs)
    dy_ref = refs.pop(0)
    if norm:
        x_ref, w, dw_ref, mean = refs.pop(0), refs.pop(0)[...], refs.pop(), _lane_mean()

        @pl.when(pl.program_id(1) == 0)
        def _():
            dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    dx_ref = refs.pop()
    f32 = jnp.float32

    def head(lanes, h, acc):
        d = sum(dy_ref[h * rep + j].astype(f32) for j in range(rep))
        if rotary:
            d = d * refs[0][...] - pltpu.roll(d, _LANES // 2, 1) * refs[1][...]
        if scale != 1.0:
            d = d * scale
        if norm:
            x = x_ref[0, :, lanes].astype(f32)
            r = jax.lax.rsqrt(mean(x * x) + eps)
            xh = x * r
            dw = d * xh
            acc = acc + sum(dw[i:i + _SUBLANES] for i in range(0, dw.shape[0], _SUBLANES))
            g = d * w
            d = r * (g - xh * mean(g * xh))
        dx_ref[0, :, lanes] = d.astype(dx_ref.dtype)
        return acc

    acc = _over(heads, head, jnp.zeros((_SUBLANES, _LANES), f32) if norm else None)
    if norm:
        dw_ref[0] += acc


def _tile(t):
    """Rows of T a grid step takes, for a sequence of t: _TILE, or the whole
    sequence rounded up to a packed bf16 vreg's 16 rows where that is shorter."""
    return min(_TILE, -(-t // 16) * 16)


def _padded(tree, t):
    """Every array (..., T, lanes) of `tree` (None: none) with zeros after it
    up to whole tiles of a sequence of t: a row of zeros norms and turns to
    zeros."""
    short = -t % _tile(t)
    pad = lambda x: jnp.pad(x, ((0, 0),) * (x.ndim - 2) + ((0, short), (0, 0))) if short else x
    return jax.tree.map(pad, tree)


def _specs(b, t, heads, rep):
    """The grid of (batch, tiles of T) and the blocks of x (B, T, heads *
    128), of the rows (B * heads * rep, T, 128), of a table (T, 128) and of
    the weight."""
    tile = _tile(t)
    return ((b, t // tile), pl.BlockSpec((1, tile, heads * _LANES), lambda i, j: (i, j, 0)),
            pl.BlockSpec((heads * rep, tile, _LANES), lambda i, j: (i, j, 0)),
            pl.BlockSpec((tile, _LANES), lambda i, j: (j, 0)),
            pl.BlockSpec((1, _LANES), lambda i, j: (0, 0)))


@functools.partial(jax.jit, static_argnames=("rep", "eps", "scale", "interpret"))
def _fwd_call(x, weight, tables, *, rep, eps, scale, interpret):
    """The rows (B * heads * rep, T, 128) of x (B, T, heads * 128); `weight`
    (128,) float32 or None for no norm, `tables` (cos, sin_signed) or None
    for no rotary. Under a jit of its own, as ops/attention.py's calls: a
    model's layers share one trace and one lowering of the kernel."""
    b, t, c = x.shape
    heads = c // _LANES
    grid, x_rows, y_rows, table, w_row = _specs(b, t, heads, rep)
    norm, rotary = weight is not None, tables is not None
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, rep=rep, norm=norm, rotary=rotary, eps=eps,
                          scale=scale),
        grid=grid, in_specs=[x_rows] + [w_row] * norm + [table, table] * rotary,
        out_specs=y_rows, out_shape=jax.ShapeDtypeStruct((b * heads * rep, t, _LANES), x.dtype),
        compiler_params=_PARAMS, interpret=interpret, name="qk_prep_fwd",
    )(x, *((weight[None],) if norm else ()), *(tables or ()))


@functools.partial(jax.jit, static_argnames=("heads", "rep", "eps", "scale", "interpret"))
def _bwd_call(dy, x, weight, tables, *, heads, rep, eps, scale, interpret):
    """(dx (B, T, heads * 128), dweight) from the rows' cotangent dy
    (B * heads * rep, T, 128); x and `weight` are None where the layer does
    not norm, and dweight with them."""
    rows, t, _ = dy.shape
    b = rows // (heads * rep)
    grid, x_rows, y_rows, table, w_row = _specs(b, t, heads, rep)
    norm, rotary = weight is not None, tables is not None
    dx, *dw = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, rep=rep, norm=norm, rotary=rotary, eps=eps,
                          scale=scale),
        grid=grid, in_specs=[y_rows] + [x_rows, w_row] * norm + [table, table] * rotary,
        out_specs=[x_rows] + [pl.BlockSpec((1, _SUBLANES, _LANES), lambda i, j: (i, 0, 0))] * norm,
        out_shape=[jax.ShapeDtypeStruct((b, t, heads * _LANES), dy.dtype)]
        + [jax.ShapeDtypeStruct((b, _SUBLANES, _LANES), jnp.float32)] * norm,
        compiler_params=_PARAMS, interpret=interpret, name="qk_prep_bwd",
    )(dy, *((x, weight[None]) if norm else ()), *(tables or ()))
    return dx, (dw[0].sum((0, 1)) if norm else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _qk_prep(x, weight, tables, heads, rep, eps, scale, interpret):
    t = x.shape[1]
    return _fwd_call(_padded(x, t), weight, _padded(tables, t), rep=rep, eps=eps, scale=scale,
                     interpret=interpret)[:, :t]


def _qk_prep_fwd_rule(x, weight, tables, heads, rep, eps, scale, interpret):
    return (_qk_prep(x, weight, tables, heads, rep, eps, scale, interpret),
            (None if weight is None else x, weight, tables))


def _qk_prep_bwd_rule(heads, rep, eps, scale, interpret, res, dy):
    x, weight, tables = res
    t = dy.shape[1]
    dx, dw = _bwd_call(_padded(dy, t), _padded(x, t), weight, _padded(tables, t), heads=heads,
                       rep=rep, eps=eps, scale=scale, interpret=interpret)
    return dx[:, :t], dw, None


_qk_prep.defvjp(_qk_prep_fwd_rule, _qk_prep_bwd_rule)


def qk_prep(x, weight=None, tables=None, *, rep=1, eps=1e-5, scale=1.0, interpret=False):
    """The rows (B * heads * rep, T, 128) the flash calls take, in x's dtype,
    from x (B, T, heads * 128) as a projection wrote it: each head normed
    over its 128 lanes times `weight` (128,) (None: no norm), turned by
    `tables` = `rope_tables(...)` (None: no rotary), times `scale`, and
    written to `rep` rows (a key-value head's query heads). The tables are
    taken as constants: no gradient flows to the angles."""
    b, t, c = x.shape
    if c % _LANES or (weight is not None and weight.shape != (_LANES,)) or (
            tables is not None and any(v.shape != (t, _LANES) for v in tables)):
        raise ValueError(f"x {x.shape}, weight {getattr(weight, 'shape', None)}, tables "
                         f"{[v.shape for v in tables or ()]}")
    if weight is not None:
        weight = weight.astype(jnp.float32)
    if tables is not None:
        tables = tuple(jax.lax.stop_gradient(v.astype(jnp.float32)) for v in tables)
    return _qk_prep(x, weight, tables, c // _LANES, rep, float(eps), float(scale), bool(interpret))
