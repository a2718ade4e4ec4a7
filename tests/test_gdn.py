"""ops/gdn.py: the delta rule under one decay a head and step. The recurrence
against `kda.kda_plain` told the same decay on every channel; the chunked
form and the pallas pair in interpret mode against the recurrence, values,
the states and all five gradients, at decays from 1e-4 to 20 a step, with T
a whole number of chunks or not, and with one value head on a key head as
well as two; the norm of q and k inside against the norm before; a chunk's
hand-written backward against JAX's own; a lost hand-over and a wrong key
head caught."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gdn, kda

F32 = jnp.float32


def _operands(b, t, hk, hv, kd, vd, seed=0, decay=0.3, beta_shift=0.0, dtype=F32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    l2 = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q, k = (l2(jax.random.normal(key, (b, t, hk, kd))).astype(dtype) for key in ks[:2])
    v = jax.random.normal(ks[2], (b, t, hv, vd)).astype(dtype)
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, t, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)) + beta_shift)
    return q, k, v, g, beta


def by_head(q, k, v, *rest, **kw):
    """`gdn.gdn` with o by head, (b, T, Hv, V), as the recurrence gives it."""
    o, *others = gdn.gdn(q, k, v, *rest, **kw)
    assert o.shape == (*v.shape[:2], v.shape[2] * v.shape[3])
    return (o.reshape(v.shape), *others)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _grads(form, ops, w, argnums=range(5)):
    return jax.grad(lambda *o: (form(*o)[0].astype(F32) * w).sum(), argnums=tuple(argnums))(*ops)


@pytest.mark.parametrize("rep", [1, 2])
def test_the_recurrence_is_kda_s_with_one_decay_on_every_channel(rep):
    """`gdn_plain` against `kda.kda_plain` with q and k repeated along heads
    and g on every channel of the key: values, state and gradients."""
    ops = _operands(2, 24, 2, 2 * rep, 16, 8, seed=rep)
    q, k, v, g, beta = ops
    wide = lambda q, k, v, g, beta: kda.kda_plain(
        jnp.repeat(q, rep, 2), jnp.repeat(k, rep, 2), v,
        jnp.broadcast_to(g[..., None], (*g.shape, 16)), beta)
    got, state = gdn.gdn_plain(*ops)
    want, state_w = wide(*ops)
    assert _rel(got, want) < 1e-6 and _rel(state, state_w) < 1e-6
    w = jax.random.normal(jax.random.PRNGKey(3), want.shape)
    for name, a, b in zip("q k v g beta".split(), _grads(gdn.gdn_plain, ops, w),
                          _grads(wide, ops, w)):
        assert _rel(a, b) < 1e-5, name


@functools.partial(jax.jit, static_argnames=("chunk",))
def _both_forms(ops, w, chunk):
    with jax.default_matmul_precision("highest"):
        got, states, last = by_head(*ops, chunk=chunk)
        want, state = gdn.gdn_plain(*ops)
        return ((got, last.swapaxes(-1, -2), states,
                 _grads(lambda *o: by_head(*o, chunk=chunk), ops, w)),
                (want, state, _grads(gdn.gdn_plain, ops, w)))


@pytest.mark.parametrize("chunk,t,decay,beta_shift,rep", [
    (4, 16, 0.3, 0.0, 2), (16, 40, 0.3, 0.0, 1), (64, 128, 1e-4, 0.0, 2), (64, 128, 8.0, 0.0, 2),
    (64, 128, 1.0, 6.0, 1), (64, 128, 1.0, -6.0, 2), (64, 50, 0.3, 0.0, 2),
    (64, 128, 20.0, 3.0, 2)])
def test_chunked_form_is_the_recurrence(chunk, t, decay, beta_shift, rep):
    """Values, the state after the last token and all five gradients in
    float32, whatever the chunk, T whole chunks or padded, decays from 0.9999
    a step to e^-20 (Gamma's entries are at most 1: nothing overflows), beta
    near 0 and near 1, one value head a key head or two."""
    ops = _operands(2, t, 2, 2 * rep, 16, 8, seed=t + int(decay * 100), decay=decay,
                    beta_shift=beta_shift)
    w = jax.random.normal(jax.random.PRNGKey(9), (2, t, 2 * rep, 8))
    (got, last, states, ours), (want, state, plain) = _both_forms(ops, w, chunk)
    assert got.shape == want.shape and states.shape == (2, -(-t // chunk), 2 * rep, 8, 16)
    assert _rel(got, want) < 2e-5
    if t % chunk == 0:
        assert _rel(last, state) < 2e-5
    for name, a, b in zip("q k v g beta".split(), ours, plain):
        assert np.isfinite(np.asarray(a)).all(), name
        assert _rel(a, b) < (2e-4 if decay > 10 else 5e-5), name


def test_a_chunk_s_backward_is_jax_s_own():
    """`kda._chunk_bwd` under this decay, which the backward kernel runs,
    against jax.vjp of `_chunk_fwd` in float32, a state and its cotangent
    handed in; dg read off one lane, as the kernel reads it."""
    c, kd, vd = 32, 128, 128
    q, k, v, g, beta = (a[0, :, 0] for a in _operands(1, c, 1, 1, kd, vd, seed=3))
    beta = beta[:, None]
    St = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (vd, kd))
    decay = gdn._Scalar()
    with jax.default_matmul_precision("highest"):
        form = lambda q, k, v, g, beta, St: kda._chunk_fwd(
            q, k, v, jnp.broadcast_to(g[:, None], q.shape), beta, St, kd ** -0.5, kda._np_roll,
            kda._dot_highest, decay)
        (o, nxt), pull = jax.vjp(form, q, k, v, g, beta, St)
        do = jax.random.normal(jax.random.PRNGKey(6), o.shape)
        dS = jax.random.normal(jax.random.PRNGKey(7), nxt.shape)
        want = pull((do, dS))
        got = list(kda._chunk_bwd(q, k, v, jnp.broadcast_to(g[:, None], q.shape), beta, St, do, dS,
                                  kd ** -0.5, kda._np_roll, kda._dot_highest, decay))
    assert _rel(got[3], jnp.broadcast_to(got[3][:, :1], got[3].shape)) == 0.0
    got[3] = got[3][:, 0]
    for name, a, b in zip("dq dk dv dg dbeta dS".split(), got, want):
        assert _rel(a, b) < 1e-5, name


@pytest.mark.parametrize("dtype,chunk,t,rep,decay", [
    (F32, 64, 128, 2, 0.5), (jnp.bfloat16, 64, 100, 2, 0.5), (F32, 32, 96, 1, 0.5),
    (F32, 64, 128, 2, 1e-4), (F32, 64, 192, 2, 20.0)])
def test_kernels_in_interpret_mode_are_both_forms(dtype, chunk, t, rep, decay):
    """gdn_fwd and gdn_bwd under `interpret=True` against the recurrence and
    against the chunked form in jax.numpy: the output, the chunk states, the
    last state and dq, dk, dv, dg, dbeta, two value heads on a key head and
    one, T whole chunks and not, decays of 1e-4 to 20 a step."""
    ops = _operands(1, t, 2, 2 * rep, 128, 128, seed=11, decay=decay, dtype=dtype)
    tol = 2e-5 if dtype == F32 else 2e-2
    with jax.default_matmul_precision("highest"):
        want, state = gdn.gdn_plain(*ops)
        got, states, last = by_head(*ops, chunk=chunk, interpret=True)
        chunked, states_c, last_c = by_head(*ops, chunk=chunk)
        assert got.dtype == dtype and states.dtype == F32
        assert _rel(got, want) < tol and _rel(got, chunked) < tol / 4
        assert _rel(states, states_c) < tol / 4 and _rel(last, last_c) < tol / 4
        if t % chunk == 0:
            assert _rel(last.swapaxes(-1, -2), state) < tol
        w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
        ours = _grads(lambda *o: by_head(*o, chunk=chunk, interpret=True), ops, w)
        plain = _grads(gdn.gdn_plain, ops, w)
        for name, a, b in zip("q k v g beta".split(), ours, plain):
            assert a.dtype == (F32 if name in ("g", "beta") else dtype)
            assert np.isfinite(np.asarray(a, np.float32)).all(), name
            assert _rel(a, b) < (10 * tol if decay > 10 else tol), name


def test_the_norm_inside_the_kernels_is_the_norm_before_them():
    """With `l2_eps` the kernels take q and k as a convolution leaves them
    (no unit length, some steps all zeros) and give what `kda.l2norm` and
    then the kernels give, values and the gradients of what came in."""
    eps = 1e-6
    q, k, v, g, beta = _operands(1, 100, 2, 4, 128, 128, seed=5)
    ks = jax.random.split(jax.random.PRNGKey(31), 2)
    q = q * 3.0 * jnp.exp(jax.random.normal(ks[0], (1, 100, 2, 1)))
    k = (k * 0.2 * jnp.exp(jax.random.normal(ks[1], (1, 100, 2, 1)))).at[:, 7].set(0.0)
    ops = (q, k, v, g, beta)
    inside = lambda *o: by_head(*o, l2_eps=eps, interpret=True)
    before = lambda q, k, *rest: gdn.gdn_plain(kda.l2norm(q, eps), kda.l2norm(k, eps), *rest)
    with jax.default_matmul_precision("highest"):
        assert _rel(inside(*ops)[0], before(*ops)[0]) < 2e-5
        w = jax.random.normal(jax.random.PRNGKey(9), v.shape)
        for name, a, b in zip("q k v g beta".split(), _grads(inside, ops, w),
                              _grads(before, ops, w)):
            assert np.isfinite(np.asarray(a)).all(), name
            assert _rel(a, b) < 5e-5, name
        # and off the kernels the entry norms before the chunked form
        assert _rel(by_head(*ops, l2_eps=eps)[0], before(*ops)[0]) < 2e-5


@pytest.mark.parametrize("fault", ["carry", "key_head"])
def test_a_lost_hand_over_and_a_wrong_key_head_are_caught(fault, monkeypatch):
    """The comparison above fails of a kernel whose state is dropped at every
    chunk and of one in which every value head reads key head 0's raw
    products."""
    ops = _operands(1, 128, 2, 4, 128, 128, seed=11)
    with jax.default_matmul_precision("highest"):
        want, _ = gdn.gdn_plain(*ops)
        if fault == "carry":
            real = kda._chunk_fwd
            monkeypatch.setattr(kda, "_chunk_fwd", lambda q, k, v, g, beta, St, *a: real(
                q, k, v, g, beta, jnp.zeros_like(St), *a))
        else:
            q, k, v, g, beta = ops
            ops = (q, k, jnp.roll(v, 2, axis=2), jnp.roll(g, 2, axis=2), jnp.roll(beta, 2, axis=2))
        got = by_head(*ops, chunk=64, interpret=True)[0]
    assert _rel(got, want) > 0.05


def test_path_and_shapes():
    """Off a TPU, or at heads that are no vector's lanes, the chunked form;
    T = 8, what `TrainStep.train_init` traces, pads; value heads that no
    number of key heads divides are refused."""
    assert gdn.gdn_path(8192, 128, 128) == "xla"  # this box has no TPU
    ops = _operands(1, 8, 2, 4, 16, 16)
    o, states, last = gdn.gdn(*ops)
    assert o.shape == (1, 8, 64) and states.shape == (1, 1, 4, 16, 16) and last.shape == (1, 4, 16, 16)
    q, k, v, g, beta = ops
    with pytest.raises(ValueError):
        gdn.gdn(q, k, v[:, :, :3], g[..., :3], beta[..., :3])
