"""A delta-rule mixer's head norm and its gate between the rule and W_o
(the KDA mixer's, whose gate is a sigmoid; the Gated DeltaNet mixer's, whose
gate is a silu: `gate`, a static argument, names the activation), a pallas
kernel pair on a TPU and the mixer's own lines in jax.numpy elsewhere.

With o (B, T, H * 128) as `kda_fwd` wrote it, a head its 128 lanes, z (B, T,
H * 128) the gate's pre-activation as `g_b_proj` wrote it and one weight w
(128,) for every head:

    r = rsqrt(mean(o^2 over the head's lanes) + eps)       a head and token
    y = o * r * w * act(z)                 act: sigmoid, or silu (z sigmoid(z))

Written as `reshape(B, T, H, 128)`, RMSNorm, `reshape` back and the gate in
float32, the head axis takes the place of T as the second-minor one, which
under the TPU's (8, 128) tiling is no bitcast: XLA wrote o out in the other
layout and back in float32, forward, again under remat, and backward, and the
cotangent once more on its way into `kda_bwd` (42 of the 50 ms a step under
`kda.norm` in kimi_linear_l5_ep32.t8192, PERF.md section 6, PR 60;
ops/gated_norm.py and ops/qk_prep.py found the same of their norms). Here o
and z are read once and y written once, each where its neighbour's matmul or
kernel reads or wrote it.

`kda_norm_fwd` and `kda_norm_bwd` (the names the compiled step and the
profiler's trace show; bench/layer_metrics/kda_norm_share_pct.json finds them
by these, and no other metric's pattern does) are built as ops/qk_prep.py's
pair, whose norm is over the same 128 lanes: a grid of (batch, tiles of T),
whole rows of H * 128 lanes a block, a head's (tile, 128) the value the body
works on, four heads a loop's iteration (`qk_prep._over`), the mean over a
head's lanes on the MXU (`qk_prep._lane_mean`: the unit that sums across
lanes takes 52 cycles a vreg, and a head of 128 lanes is one vreg a sum). At
the cell's shape, (2, 8192, 32 x 128) bf16, ten calls in one program (my chip
run, PR 60, call 1): forward 0.70 ms whatever the heads an iteration (1 to
16), backward 1.26 at one head an iteration and 1.14-1.16 at two to sixteen:
578 and 584 GB/s of the chip's 819; the plain lines 3.93 forward and 7.01
forward and backward. Every product and sum is float32 from o and z as read;
y, do and dz are rounded once, where the plain lines round the norm and then
the gated product.

Backward, one call, with r and s = sigmoid(z) made again from o and z (no
array is kept beside o and z, which `kda_bwd` and `g_b_proj`'s transpose keep
anyway), n = o * r:

    dn = dy * s * w                        dw = sum_{b,t,head} dy * s * n
    do = r * (dn - n * mean(dn * n))       over the head's lanes
    dz = dy * (n * w) * s * (1 - s)        under silu: dn = dy * z s * w and
                                           dz = dy * (n * w) * s * (1 + z (1 - s))

dw is summed in float32 over a batch row's tiles and heads in an output block
that stays in VMEM, eight sublanes of partial sums, and over those and the
batch rows outside.

The kernels take heads of 128 lanes; T is padded to whole tiles where it is
not (a row of zeros norms to zeros). Any other width, and any backend but a
TPU, runs `kda_norm_plain`, differentiated by JAX.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu.ops.attention import _on_tpu  # a worker that cannot reach its chip fails there
from ray_tpu.ops.gated_norm import _PARAMS, _SUBLANES, norm_by_group
from ray_tpu.ops.qk_prep import _lane_mean, _over, _padded, _tile
from ray_tpu.ops.short_conv import _LANES


SIGMOID, SILU = "sigmoid", "silu"  # the gate's activations
_ACTIVATIONS = {SIGMOID: jax.nn.sigmoid, SILU: jax.nn.silu}


def kda_norm_plain(o, z, weight, eps, gate=SIGMOID):
    """The mixer's lines before it had kernels: RMSNorm over a (..., H, W)
    view (`norm_by_group`: float32, rounded, times the weight in o's dtype),
    then the gate in float32, rounded."""
    heads = o.shape[-1] // weight.shape[0]
    normed = norm_by_group(o, jnp.tile(weight, heads), eps, heads)
    return (normed.astype(jnp.float32) * _ACTIVATIONS[gate](z.astype(jnp.float32))).astype(o.dtype)


def _gate(z):
    """(s, 1 - s) of s = sigmoid(z), float32: s as ops/short_conv.py's
    `_sigmoid` makes it (the unit's reciprocal and one Newton step), and
    1 - s = exp(-z) s, which loses nothing where s is near one."""
    e = jnp.exp(-jnp.maximum(z, -80.0))
    d = 1 + e
    r = pl.reciprocal(d, approx=True)
    s = r * (2 - d * r)
    return s, e * s


def _fwd_kernel(o_ref, z_ref, w_ref, y_ref, *, eps, gate):
    """One tile of one batch row: y of the tile, a head at a time."""
    f32 = jnp.float32
    w, mean = w_ref[...], _lane_mean()

    def head(lanes, _, __):
        o = o_ref[0, :, lanes].astype(f32)
        n = o * jax.lax.rsqrt(mean(o * o) + eps)
        z = z_ref[0, :, lanes].astype(f32)
        s, _ = _gate(z)
        y_ref[0, :, lanes] = (n * w * (s if gate == SIGMOID else z * s)).astype(y_ref.dtype)

    _over(o_ref.shape[2] // _LANES, head)


def _bwd_kernel(o_ref, z_ref, w_ref, dy_ref, do_ref, dz_ref, dw_ref, *, eps, gate):
    """One tile of one batch row: do and dz of the tile, the tile's part of
    the weight's gradient added to dw_ref, eight sublanes of partial sums."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    f32 = jnp.float32
    w, mean = w_ref[...], _lane_mean()

    def head(lanes, _, acc):
        o, dy = o_ref[0, :, lanes].astype(f32), dy_ref[0, :, lanes].astype(f32)
        z = z_ref[0, :, lanes].astype(f32)
        s, rest = _gate(z)
        r = jax.lax.rsqrt(mean(o * o) + eps)
        n = o * r
        if gate == SIGMOID:
            gated = dy * s
            dz_ref[0, :, lanes] = (gated * rest * (n * w)).astype(dz_ref.dtype)
        else:  # act = z s, act' = s (1 + z (1 - s))
            gated = dy * (z * s)
            dz_ref[0, :, lanes] = (dy * s * (1 + z * rest) * (n * w)).astype(dz_ref.dtype)
        dw = gated * n
        dn = gated * w
        do_ref[0, :, lanes] = (r * (dn - n * mean(dn * n))).astype(do_ref.dtype)
        return acc + sum(dw[i:i + _SUBLANES] for i in range(0, dw.shape[0], _SUBLANES))

    dw_ref[0] += _over(o_ref.shape[2] // _LANES, head, jnp.zeros((_SUBLANES, _LANES), f32))


def _specs(o):
    """The grid of (batch, tiles of T) and the blocks of o's shape and of the
    weight."""
    b, t, c = o.shape
    tile = _tile(t)
    return ((b, t // tile), pl.BlockSpec((1, tile, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, _LANES), lambda i, j: (0, 0)))


@functools.partial(jax.jit, static_argnames=("eps", "gate", "interpret"))
def _fwd_call(o, z, weight, *, eps, gate, interpret):
    """y (B, T, H * 128). Under a jit of its own, as ops/qk_prep.py's calls:
    a model's layers share one trace and one lowering of a kernel."""
    grid, rows, w_row = _specs(o)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, gate=gate),
        grid=grid, in_specs=[rows, rows, w_row], out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=_PARAMS, interpret=interpret, name="kda_norm_fwd",
    )(o, z, weight[None])


@functools.partial(jax.jit, static_argnames=("eps", "gate", "interpret"))
def _bwd_call(o, z, weight, dy, *, eps, gate, interpret):
    """(do, dz, dweight)."""
    grid, rows, w_row = _specs(o)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    do, dz, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, gate=gate),
        grid=grid, in_specs=[rows, rows, w_row, rows],
        out_specs=[rows, rows, pl.BlockSpec((1, _SUBLANES, _LANES), lambda i, j: (i, 0, 0))],
        out_shape=[like(o), like(z),
                   jax.ShapeDtypeStruct((o.shape[0], _SUBLANES, _LANES), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="kda_norm_bwd",
    )(o, z, weight[None], dy)
    return do, dz, dw.sum((0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _kda_norm(o, z, weight, eps, interpret, gate=SIGMOID):
    t = o.shape[1]
    return _fwd_call(*_padded((o, z), t), weight, eps=eps, gate=gate, interpret=interpret)[:, :t]


def _kda_norm_fwd_rule(o, z, weight, eps, interpret, gate):
    return _kda_norm(o, z, weight, eps, interpret, gate), (o, z, weight)


def _kda_norm_bwd_rule(eps, interpret, gate, res, dy):
    o, z, weight = res
    t = o.shape[1]
    o, z, dy = _padded((o, z, dy), t)
    do, dz, dw = _bwd_call(o, z, weight, dy, eps=eps, gate=gate, interpret=interpret)
    return do[:, :t], dz[:, :t], dw


_kda_norm.defvjp(_kda_norm_fwd_rule, _kda_norm_bwd_rule)


def norm_path(width: int) -> str:
    """"pallas" or "xla" for heads of `width` lanes on this process's
    backend: the kernels where a head is one vector of lanes."""
    return "pallas" if _on_tpu() and width == _LANES else "xla"


def kda_norm(o, z, weight, eps, *, gate=SIGMOID, interpret=None):
    """RMSNorm of each head of o's last axis on its own, times `weight` (W,)
    float32, one for every head, times `gate` of z (SIGMOID or SILU), in o's
    dtype: o and z (B, T, H * W). The kernels where `norm_path` says so;
    elsewhere `kda_norm_plain`. `interpret` forces the kernels (True: in
    interpret mode), for the tests."""
    width, = weight.shape
    if o.ndim != 3 or z.shape != o.shape or o.shape[-1] % width or gate not in _ACTIVATIONS:
        raise ValueError(f"o {o.shape}, z {z.shape}, weight {weight.shape}, gate {gate!r}")
    if (interpret is not None and width == _LANES) or norm_path(width) == "pallas":
        return _kda_norm(o, z, weight.astype(jnp.float32), float(eps), bool(interpret), gate)
    return kda_norm_plain(o, z, weight, eps, gate)
