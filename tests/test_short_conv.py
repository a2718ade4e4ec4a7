"""ops/short_conv.py: the plain form and, in interpret mode, the two kernels
against a sum of shifted slices written out here: values and the gradients
of the three streams and of the taps, at a T of several tiles and at one that
is no multiple of the tile, at 3 and 4 taps; a row's first tokens see zeros.
The same of the Mamba mixer's pair (the file's second half): y, dx, dw and
dbias, the rows at the edges of its runs of rows on their own, x within a
wider array and y in parts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import short_conv
from ray_tpu.ops.short_conv import (
    causal_conv_plain, causal_conv_within, gated_conv_plain, gated_short_conv)

B_, D = 2, 256


def _inputs(t, k, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    bcu = jax.random.normal(ks[0], (B_, t, 3 * D), jnp.float32).astype(dtype)
    w = jax.random.uniform(ks[1], (k, D), jnp.float32, -0.6, 0.6)
    dy = jax.random.normal(ks[2], (B_, t, D), jnp.float32)  # loss = <y, dy>
    return bcu, w, dy


def shifted_slices(bcu, w):
    """y_t = C_t * sum_j w_j (B u)_{t-(k-1)+j}, one shifted slice a tap, in
    float64 on the host."""
    bcu, w = np.asarray(bcu, np.float64), np.asarray(w, np.float64)
    t, k = bcu.shape[1], w.shape[0]
    b, c, u = np.split(bcu, 3, axis=-1)
    z = b * u
    conv = np.zeros_like(z)
    for j in range(k):
        back = k - 1 - j
        conv[:, back:] += w[j] * z[:, :t - back]
    return c * conv


def _value_and_grads(fn, bcu, w, dy):
    return jax.value_and_grad(lambda x, w: jnp.vdot(fn(x, w), dy), argnums=(0, 1))(bcu, w)


def _reference_grads(bcu, w, dy):
    """The gradients by hand in float64: g = C dy, dz the convolution run
    backwards in time, dw_j the correlation of g with z shifted."""
    bcu, w, dy = (np.asarray(v, np.float64) for v in (bcu, w, dy))
    t, k = bcu.shape[1], w.shape[0]
    b, c, u = np.split(bcu, 3, axis=-1)
    z, g = b * u, c * dy
    conv, dz, dw = np.zeros_like(z), np.zeros_like(z), np.zeros_like(w)
    for j in range(k):
        back = k - 1 - j
        conv[:, back:] += w[j] * z[:, :t - back]
        dz[:, :t - back] += w[j] * g[:, back:]
        dw[j] = (g[:, back:] * z[:, :t - back]).sum((0, 1))
    return np.concatenate([dz * u, dy * conv, dz * b], axis=-1), dw


CASES = [("plain", 40, 3), ("plain", 64, 4), ("kernels", 64, 3), ("kernels", 40, 3),
         ("kernels", 48, 4), ("kernels", 16, 3), ("kernels", 100, 4)]


@pytest.mark.parametrize("path,t,k", CASES)
def test_against_the_shifted_slices(path, t, k, monkeypatch):
    """Tiles of 32 rows: T = 64 is two tiles, 40, 48 and 100 are padded to
    whole ones, 16 is one short tile."""
    monkeypatch.setattr(short_conv, "_TILE", 32)
    bcu, w, dy = _inputs(t, k)
    fn = gated_conv_plain if path == "plain" else (
        lambda x, w: gated_short_conv(x, w, interpret=True))
    np.testing.assert_allclose(fn(bcu, w), shifted_slices(bcu, w), rtol=1e-5, atol=1e-5)
    _, (d_bcu, dw) = _value_and_grads(fn, bcu, w, dy)
    want_bcu, want_dw = _reference_grads(bcu, w, dy)
    assert d_bcu.shape == bcu.shape and dw.shape == w.shape and dw.dtype == jnp.float32
    for name, got, want in (("dB", d_bcu[..., :D], want_bcu[..., :D]),
                            ("dC", d_bcu[..., D:2 * D], want_bcu[..., D:2 * D]),
                            ("du", d_bcu[..., 2 * D:], want_bcu[..., 2 * D:]),
                            ("dw", dw, want_dw)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4 * np.abs(want).max(),
                                   err_msg=name)


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_a_row_s_first_tokens_see_zeros(path, monkeypatch):
    """Token 0 has only its own tap, token 1 its own and one back; and what a
    batch row holds says nothing of what the row after it computes (the
    carried rows are dropped between rows)."""
    monkeypatch.setattr(short_conv, "_TILE", 32)
    bcu, w, _ = _inputs(64, 3)
    fn = gated_conv_plain if path == "plain" else (
        lambda x, w: gated_short_conv(x, w, interpret=True))
    y = fn(bcu, w)
    b, c, u = jnp.split(bcu, 3, axis=-1)
    z = b * u
    np.testing.assert_allclose(y[:, 0], c[:, 0] * w[2] * z[:, 0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y[:, 1], c[:, 1] * (w[2] * z[:, 1] + w[1] * z[:, 0]),
                               rtol=1e-5, atol=1e-6)
    alone = fn(bcu[1:], w)
    np.testing.assert_array_equal(np.asarray(alone[0]), np.asarray(y[1]))


def test_bf16_streams_float32_sums():
    """bf16 in and out, every product and sum in float32: the result is the
    float32 one rounded once, and the taps' gradient is float32."""
    bcu, w, dy = _inputs(64, 3, dtype=jnp.bfloat16)
    want = shifted_slices(bcu.astype(jnp.float32), w)
    for fn in (gated_conv_plain, lambda x, w: gated_short_conv(x, w, interpret=True)):
        y = fn(bcu, w)
        assert y.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(y, np.float32), want, rtol=2 ** -7, atol=1e-3)
        _, (d_bcu, dw) = _value_and_grads(fn, bcu, w, dy)
        assert d_bcu.dtype == jnp.bfloat16 and dw.dtype == jnp.float32


def test_off_a_tpu_the_plain_form_runs():
    assert short_conv.conv_path(2048, 3) == "xla"  # this process has no TPU
    bcu, w, _ = _inputs(40, 3)
    np.testing.assert_array_equal(np.asarray(gated_short_conv(bcu, w)),
                                  np.asarray(gated_conv_plain(bcu, w)))
    with pytest.raises(ValueError):
        gated_short_conv(bcu[..., :-1], w)


# --------------------------------------------------------------------------
# the Mamba mixer's pair: silu(bias + the causal convolution of x)
# --------------------------------------------------------------------------

_causal_kernels = lambda x, w, bias: causal_conv_within(x, w, bias, interpret=True)[1]


def _causal_inputs(t, k, c, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (B_, t, c), jnp.float32).astype(dtype)
    w = jax.random.uniform(ks[1], (k, c), jnp.float32, -0.5, 0.5)
    bias = 0.3 * jax.random.normal(ks[2], (c,), jnp.float32)
    dy = jax.random.normal(ks[3], (B_, t, c), jnp.float32)  # loss = <y, dy>
    return x, w, bias, dy


def causal_slices(x, w, bias, dy):
    """y = silu(a), a_t = bias + sum_j w_j x_{t-(k-1)+j}, and its gradients
    by hand, one shifted slice a tap, in float64 on the host."""
    x, w, bias, dy = (np.asarray(v, np.float64) for v in (x, w, bias, dy))
    t, k = x.shape[1], w.shape[0]
    a = np.zeros_like(x) + bias
    for j in range(k):
        back = k - 1 - j
        a[:, back:] += w[j] * x[:, :t - back]
    s = 1 / (1 + np.exp(-a))
    g = dy * s * (1 + a * (1 - s))
    dx, dw = np.zeros_like(x), np.zeros_like(w)
    for j in range(k):
        back = k - 1 - j
        dx[:, :t - back] += w[j] * g[:, back:]
        dw[j] = (g[:, back:] * x[:, :t - back]).sum((0, 1))
    return a * s, dx, dw, g.sum((0, 1))


def _causal_value_and_grads(fn, x, w, bias, dy):
    y = fn(x, w, bias)
    grads = jax.grad(lambda *a: jnp.vdot(fn(*a), dy), argnums=(0, 1, 2))(x, w, bias)
    return (y, *grads)


def _edge_rows(t, tile, k):
    """The first k-1 rows of every tile and the last k-1 before it."""
    at = np.arange(t) % tile
    return np.flatnonzero((at < k - 1) | (at >= tile - (k - 1)))


# (path, T, taps, width, rows a run, lanes a block): tiles of 32 rows, so 64
# and 96 are whole tiles, 40 and 100 are padded, 16 is one short tile; a tile
# is one run of rows or, at 16, two (the hand-over inside a tile); blocks of
# x's whole width (2,176 is 17 vectors of lanes, as granite's 4,352 is 17 of
# 256) or, the bytes of a block held down, of 256 lanes or one vector
CAUSAL_CASES = [("plain", 40, 4, 256, 64, 256), ("plain", 64, 3, 512, 64, 512),
                ("kernels", 64, 4, 256, 64, 256), ("kernels", 96, 4, 512, 16, 256),
                ("kernels", 40, 4, 768, 16, 256), ("kernels", 100, 4, 1024, 64, 1024),
                ("kernels", 16, 4, 256, 64, 128), ("kernels", 64, 3, 2176, 16, 2176),
                ("kernels", 64, 2, 2176, 64, 128)]


def _assert_close(got, want, edges):
    for name, g, v in zip(("y", "dx", "dw", "dbias"), got, want):
        assert g.shape == v.shape and g.dtype == jnp.float32, name
        np.testing.assert_allclose(g, v, rtol=1e-4, atol=2e-5 * np.abs(v).max(), err_msg=name)
        if g.ndim == 3:
            np.testing.assert_allclose(g[:, edges], v[:, edges], rtol=1e-4,
                                       atol=2e-5 * np.abs(v).max(), err_msg=name + " at the edges")


@pytest.mark.parametrize("path,t,k,c,rows,block", CAUSAL_CASES)
def test_the_mamba_pair_against_the_shifted_slices(path, t, k, c, rows, block, monkeypatch):
    """y, dx, dw and dbias at float32 tolerance (the kernels' reciprocal is
    the unit's with one Newton step, which interpret mode makes from a
    cruder guess than the chip's: 2e-5), and the rows at the edges of the
    runs of rows on their own: the first k-1 of a run read the rows carried
    from the one before, the last k-1 take their gradient from the one after."""
    monkeypatch.setattr(short_conv, "_TILE", 32)
    monkeypatch.setattr(short_conv, "_ROWS", rows)
    if block < c:
        monkeypatch.setattr(short_conv, "_BLOCK_BYTES", min(t, 32) * block * 2)
    assert short_conv._cut(t, c).width == block
    x, w, bias, dy = _causal_inputs(t, k, c)
    fn = causal_conv_plain if path == "plain" else _causal_kernels
    _assert_close(_causal_value_and_grads(fn, x, w, bias, dy), causal_slices(x, w, bias, dy),
                  _edge_rows(t, min(32, rows, t), k))


# (lanes before x, lanes after it, where y is cut, lanes a block): x where it
# lies in a wider array, at a whole vector of its lanes (the calls read and
# write it there) and not (the plain lines run), and with nothing beside it; y in
# parts that the calls write as arrays of their own (cuts at whole vectors of
# lanes, a block all of x's width) or that are cut from one y beside them
WITHIN = [(512, 64, (256, 384), 512), (256, 0, (), 512), (128, 64, (64,), 512),
          (0, 128, (128,), 256), (100, 64, (256,), 512), (0, 0, (), 512)]


@pytest.mark.parametrize("path", ["plain", "kernels"])
@pytest.mark.parametrize("at,more,cuts,block", WITHIN)
def test_the_mamba_pair_within_a_wider_array(path, at, more, cuts, block, monkeypatch):
    """(left, *y's parts, right) and the gradient of the whole array: the
    neighbours' gradients pass through, x's lies between them."""
    monkeypatch.setattr(short_conv, "_TILE", 32)
    t, k, c = 72, 4, 512
    if block < c:
        monkeypatch.setattr(short_conv, "_BLOCK_BYTES", 32 * block * 2)
    by_the_calls = all(cut % 128 == 0 for cut in cuts) and block == c
    assert short_conv._cut(t, c, cuts).parts == (cuts if by_the_calls else ())
    x, w, bias, dy = _causal_inputs(t, k, c)
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    left, right = jax.random.normal(ks[0], (B_, t, at)), jax.random.normal(ks[1], (B_, t, more))
    d_left, d_right = jax.random.normal(ks[2], left.shape), jax.random.normal(ks[3], right.shape)
    wide = jnp.concatenate([left, x, right], axis=-1)
    within = lambda wide, w, bias: causal_conv_within(
        wide, w, bias, at, cuts, interpret=True if path == "kernels" else None)

    def loss(wide, w, bias):
        l, *ys, r = within(wide, w, bias)
        return jnp.vdot(l, d_left) + jnp.vdot(jnp.concatenate(ys, -1), dy) + jnp.vdot(r, d_right)

    got_l, *ys, got_r = within(wide, w, bias)
    assert [y.shape[2] for y in ys] == [hi - lo for lo, hi in zip((0, *cuts), (*cuts, c))]
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(left))
    np.testing.assert_array_equal(np.asarray(got_r), np.asarray(right))
    d_wide, dw, dbias = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(wide, w, bias)
    np.testing.assert_array_equal(np.asarray(d_wide[..., :at]), np.asarray(d_left))
    np.testing.assert_array_equal(np.asarray(d_wide[..., at + c:]), np.asarray(d_right))
    _assert_close((jnp.concatenate(ys, -1), d_wide[..., at:at + c], dw, dbias),
                  causal_slices(x, w, bias, dy), _edge_rows(t, 32, k))


@pytest.mark.parametrize("lost", ["behind", "ahead"])
def test_a_lost_hand_over_shows_in_the_edge_rows(lost, monkeypatch):
    """The comparison above sees what it is there for. With the rows before a
    run of rows zeroed, y and dx are off in the edge rows alone and the taps'
    gradient is off; with the rows after it zeroed (the backward's g), y is
    right and dx is off in the edge rows alone."""
    monkeypatch.setattr(short_conv, "_TILE", 32)
    real, edge = short_conv._rolled, short_conv._EDGE

    def lossy(v, k, ahead=False):
        at = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        beyond = (at >= v.shape[0] - edge) if ahead else (at < edge)
        return real(jnp.where(beyond & (ahead == (lost == "ahead")), 0.0, v), k, ahead)

    monkeypatch.setattr(short_conv, "_rolled", lossy)
    jax.clear_caches()  # the calls are under jits of their own, which do not see the patch
    x, w, bias, dy = _causal_inputs(64, 4, 256)
    y, dx, dw, _ = _causal_value_and_grads(_causal_kernels, x, w, bias, dy)
    want = causal_slices(x, w, bias, dy)
    edges, inner = _edge_rows(64, 32, 4), np.arange(3, 29)
    off = lambda g, v, rows: np.abs(np.asarray(g)[:, rows] - v[:, rows]).max()
    assert off(dx, want[1], edges) > 1e-2 and off(dx, want[1], inner) < 1e-4
    assert off(y, want[0], inner) < 1e-4
    if lost == "behind":
        assert off(y, want[0], edges) > 1e-2 and np.abs(np.asarray(dw) - want[2]).max() > 1e-2
    else:
        assert off(y, want[0], edges) < 1e-4
    jax.clear_caches()


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_the_mamba_pair_s_first_tokens_see_zeros(path, monkeypatch):
    """Token 0 has its own tap and the bias, token 1 one more; a batch row
    says nothing of what the row after it computes."""
    monkeypatch.setattr(short_conv, "_TILE", 32)
    x, w, bias, _ = _causal_inputs(64, 4, 256)
    fn = causal_conv_plain if path == "plain" else _causal_kernels
    y = fn(x, w, bias)
    np.testing.assert_allclose(y[:, 0], jax.nn.silu(bias + w[3] * x[:, 0]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y[:, 1], jax.nn.silu(bias + w[3] * x[:, 1] + w[2] * x[:, 0]),
                               rtol=1e-5, atol=1e-6)
    alone = fn(x[1:], w, bias)
    np.testing.assert_array_equal(np.asarray(alone[0]), np.asarray(y[1]))


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_the_mamba_pair_rounds_bf16_once(path):
    """bf16 in and out, every product and sum float32: y and dx are the
    float64 ones rounded once (half a bf16 step), dw and dbias float32."""
    x, w, bias, dy = _causal_inputs(64, 4, 512, dtype=jnp.bfloat16)
    dy = dy.astype(jnp.bfloat16).astype(jnp.float32)  # y's cotangent is in y's dtype
    fn = causal_conv_plain if path == "plain" else _causal_kernels
    y, dx, dw, dbias = _causal_value_and_grads(fn, x, w, bias, dy)
    want = causal_slices(x.astype(jnp.float32), w, bias, dy)
    assert (y.dtype, dx.dtype, dw.dtype, dbias.dtype) == (
        jnp.bfloat16, jnp.bfloat16, jnp.float32, jnp.float32)
    for got, v in ((y, want[0]), (dx, want[1])):
        np.testing.assert_allclose(np.asarray(got, np.float32), v, rtol=2 ** -8, atol=2e-5)
    np.testing.assert_allclose(dw, want[2], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(dbias, want[3], rtol=1e-4, atol=1e-4)


def test_off_a_tpu_the_mixer_s_plain_lines_run(monkeypatch):
    x, w, bias, _ = _causal_inputs(40, 4, 256)
    np.testing.assert_array_equal(np.asarray(causal_conv_within(x, w, bias)[1]),
                                  np.asarray(causal_conv_plain(x, w, bias)))
    with pytest.raises(ValueError):
        causal_conv_within(x, w, bias[:-1])
    with pytest.raises(ValueError):
        causal_conv_within(x, w, bias, 128)
    for cuts in ((0,), (256,), (128, 64), (64, 64)):
        with pytest.raises(ValueError):
            causal_conv_within(x, w, bias, 0, cuts)
    # and on one, a width that is no whole vector of lanes, or taps that
    # reach past the carried rows
    monkeypatch.setattr(short_conv, "_on_tpu", lambda: True)
    assert short_conv.conv_path(4352, 4) == short_conv.conv_path(6144, 4) == "pallas"
    assert short_conv.conv_path(4352 + 64, 4) == short_conv.conv_path(6144, 18) == "xla"
    assert short_conv.conv_path(6144, 10) == "pallas"  # the gated pair carries 16 rows,
    assert short_conv.conv_path(6144, 10, short_conv._EDGE) == "xla"  # the Mamba pair 8
