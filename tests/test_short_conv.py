"""ops/short_conv.py: the plain form and, in interpret mode, the two kernels
against a sum of shifted slices written out here: values and the gradients
of the three streams and of the taps, at a T of several tiles and at one that
is no multiple of the tile, at 3 and 4 taps; a row's first tokens see zeros."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import short_conv
from ray_tpu.ops.short_conv import gated_conv_plain, gated_short_conv

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
