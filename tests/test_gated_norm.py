"""ops/gated_norm.py: the mixer's plain lines and, in interpret mode, the two
kernels against RMSNorm(y * silu(z)) worked a group at a time in float64:
`out` and the gradients of y, z and the weight, at one, two and eight groups
of 128 and 512 lanes, at a T that is no whole tile and at one of several
tiles, z read inside a wider array, bf16 rounded once, a first group a
thousand times the last, and off a TPU the plain lines."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_norm as gn
from ray_tpu.ops.gated_norm import gated_norm, gated_norm_plain

B_, EPS = 2, 1e-5


def _inputs(t, c, seed=0, dtype=jnp.float32, scale=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    y = jax.random.normal(ks[0], (B_, t, c), jnp.float32)
    y = (y if scale is None else y * scale).astype(dtype)
    z = jax.random.normal(ks[1], (B_, t, c), jnp.float32).astype(dtype)
    w = 1 + 0.1 * jax.random.normal(ks[2], (c,), jnp.float32)
    dout = jax.random.normal(ks[3], (B_, t, c), jnp.float32)  # loss = <out, dout>
    return y, z, w, dout


def _norm_by_group(y, z, w, groups, edges_off_by=0):
    """The equations a group at a time (`edges_off_by`: every group's edges
    that many lanes to the right, the lanes rotated, for the test that a
    leak is caught)."""
    x = jnp.roll(y * z / (1 + jnp.exp(-z)), -edges_off_by, axis=-1)
    parts = [p / jnp.sqrt(jnp.mean(p * p, axis=-1, keepdims=True) + EPS)
             for p in jnp.split(x, groups, axis=-1)]
    return jnp.roll(jnp.concatenate(parts, axis=-1), edges_off_by, axis=-1) * w


def by_hand(y, z, w, dout, groups, **kw):
    """(out, dy, dz, dweight) of the equations in float64."""
    with jax.enable_x64(True):
        y, z, w, dout = (jnp.asarray(np.asarray(v, np.float64)) for v in (y, z, w, dout))
        out, vjp = jax.vjp(lambda y, z, w: _norm_by_group(y, z, w, groups, **kw), y, z, w)
        return tuple(np.asarray(v) for v in (out, *vjp(dout)))


def _value_and_grads(fn, y, z, w, dout):
    out, vjp = jax.vjp(fn, y, z, w)
    return (out, *vjp(dout.astype(out.dtype)))


def _form(path, groups):
    if path == "plain":
        return lambda y, z, w: gated_norm_plain(y, z, w, EPS, groups)
    return lambda y, z, w: gated_norm(y, z, w, EPS, groups, interpret=True)


def _assert_close(got, want, tol):
    for name, g, v in zip(("out", "dy", "dz", "dweight"), got, want):
        assert g.shape == v.shape, name
        err = np.abs(np.asarray(g, np.float64) - v).max() / np.abs(v).max()
        assert err < tol, (name, err)


# T = 40 is no whole tile (48 rows: the rows past it are zeros that norm to
# zeros); 300 is two tiles of 256, the weight's gradient summed over both
CASES = [(path, groups, width, 40) for path in ("plain", "kernels")
         for groups in (1, 2, 8) for width in (128, 512)]
CASES += [("kernels", 2, 128, 300), ("kernels", 8, 128, 528)]


@pytest.mark.parametrize("path,groups,width,t", CASES)
def test_against_the_norm_a_group_at_a_time(path, groups, width, t):
    """float32 throughout: interpret mode's reciprocal starts cruder than the
    chip's (ops/short_conv.py:_sigmoid) and ends 2e-5 off."""
    y, z, w, dout = _inputs(t, groups * width, seed=groups)
    got = _value_and_grads(_form(path, groups), y, z, w, dout)
    _assert_close(got, by_hand(y, z, w, dout, groups), 5e-5 if path == "kernels" else 1e-5)


@pytest.mark.parametrize("path", ["plain", "kernels"])
@pytest.mark.parametrize("at,more", [(0, 192), (256, 64), (128, 0)])
def test_z_is_read_inside_a_wider_array(path, at, more, monkeypatch):
    """z as a slice of [left | z | right], the mixer's input projection: the
    kernels read the lanes where they lie, the gradient reaches z's lanes and
    no other."""
    groups, c = 2, 256
    y, _, w, dout = _inputs(40, c, seed=at)
    wide = jax.random.normal(jax.random.PRNGKey(7), (B_, 40, at + c + more))
    calls = []
    if path == "kernels":
        real = gn._fwd_call
        monkeypatch.setattr(gn, "_fwd_call", lambda y, wide, *a, **kw: (
            calls.append(wide.shape[-1]), real(y, wide, *a, **kw))[1])

    def fn(y, wide, w):
        z = wide[..., at:at + c]
        return gated_norm(y, z, w, EPS, groups, within=(wide, at),
                          interpret=True if path == "kernels" else None)

    out, dy, d_wide, dw = _value_and_grads(fn, y, wide, w, dout)
    _assert_close((out, dy, d_wide[..., at:at + c], dw),
                  by_hand(y, wide[..., at:at + c], w, dout, groups), 5e-5)
    beside = np.r_[0:at, at + c:at + c + more]
    assert not np.asarray(d_wide)[..., beside].any()
    assert calls == ([wide.shape[-1]] if path == "kernels" else [])  # no slice handed to the call


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_bf16_in_and_out(path):
    """bf16 in and out, dweight float32. The kernels work float32 from y and z
    as read and round `out`, dy and dz once: the float64 ones within half a
    bf16 step. The plain lines round the gate, the norm and the weight's
    product each, and every step of their backward."""
    groups, c = 2, 1024
    y, z, w, dout = _inputs(64, c, dtype=jnp.bfloat16)
    dout = dout.astype(jnp.bfloat16).astype(jnp.float32)  # out's cotangent is in out's dtype
    out, dy, dz, dw = _value_and_grads(_form(path, groups), y, z, w, dout)
    assert (out.dtype, dy.dtype, dz.dtype, dw.dtype) == (
        jnp.bfloat16, jnp.bfloat16, jnp.bfloat16, jnp.float32)
    want = by_hand(y.astype(jnp.float32), z.astype(jnp.float32), w, dout, groups)
    if path == "kernels":
        for got, v in zip((out, dy, dz), want):
            np.testing.assert_allclose(np.asarray(got, np.float32), v, rtol=2 ** -8, atol=2e-5)
        np.testing.assert_allclose(dw, want[3], rtol=1e-4, atol=1e-4)
    else:
        _assert_close((out, dy, dz, dw), want, 0.03)
    if path == "kernels":  # and nearer than the lines that stood
        plain = _value_and_grads(_form("plain", groups), y, z, w, dout)
        err = lambda got: np.abs(np.asarray(got[0], np.float32) - want[0]).mean()
        assert err((out,)) < 0.6 * err(plain)


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_a_sum_that_leaks_across_a_group_s_edge_is_caught(path):
    """The first group's values a thousand times the last's, each group held
    to the by-hand norm on its own scale: a norm whose groups' edges lay one
    vector of lanes off would take 128 of a larger group's lanes into a
    smaller one's sum, and stands far outside what the forms are held to."""
    groups, width = 8, 256
    c = groups * width
    scale = jnp.repeat(jnp.logspace(1.5, -1.5, groups), width)
    y, z, w, dout = _inputs(40, c, seed=3, scale=scale)
    got = _value_and_grads(_form(path, groups), y, z, w, dout)
    want = by_hand(y, z, w, dout, groups)
    leaky = by_hand(y, z, w, dout, groups, edges_off_by=128)
    for g in range(groups):
        at = slice(g * width, (g + 1) * width)
        _assert_close([v[..., at] for v in got], [v[..., at] for v in want], 5e-5)
    last = slice(c - width, c)
    for v, off in zip(want, leaky):
        assert np.abs(off[..., last] - v[..., last]).max() > 0.1 * np.abs(v[..., last]).max()


def test_off_a_tpu_the_plain_lines_run(monkeypatch):
    y, z, w, _ = _inputs(40, 1024)
    assert gn.norm_path(512) == "xla"
    jaxpr = str(jax.make_jaxpr(lambda y, z, w: gated_norm(y, z, w, EPS, 2))(y, z, w))
    assert "pallas_call" not in jaxpr
    np.testing.assert_array_equal(np.asarray(gated_norm(y, z, w, EPS, 2)),
                                  np.asarray(gated_norm_plain(y, z, w, EPS, 2)))
    for bad in (lambda: gated_norm(y, z[..., :512], w, EPS, 2),
                lambda: gated_norm(y, z, w[:-1], EPS, 2), lambda: gated_norm(y, z, w, EPS, 3),
                lambda: gated_norm(y, z, w, EPS, 2, within=(z, 128)),
                lambda: gated_norm(y, z, w, EPS, 2, within=(z[:, :8], 0))):
        with pytest.raises(ValueError):
            bad()
    # and on one: groups that are no whole vectors of lanes, or a z that
    # starts inside one
    monkeypatch.setattr(gn, "_on_tpu", lambda: True)
    assert gn.norm_path(512) == gn.norm_path(128) == "pallas" and gn.norm_path(192) == "xla"
    wide = jnp.concatenate([z[..., :64], z], axis=-1)
    for fn in (lambda: gated_norm(y[..., :384], z[..., :384], w[:384], EPS, 2),
               lambda: gated_norm(y, wide[..., 64:], w, EPS, 2, within=(wide, 64))):
        assert "pallas_call" not in str(jax.make_jaxpr(fn)())
    assert "pallas_call" in str(jax.make_jaxpr(lambda: gated_norm(y, z, w, EPS, 2))())
