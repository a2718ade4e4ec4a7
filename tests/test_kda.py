"""ops/kda.py: the chunked form against the recurrence step by step over
chunk sizes, sequence lengths that are and are not whole chunks, strong and
weak decays and beta near 0 and 1; the pallas pair in interpret mode against
both, values and gradients, with the gate made inside and handed in; the
state carried across a call's chunks; a chunk's hand-written backward against
JAX's own."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

F32 = jnp.float32


def _operands(b, t, h, kd, vd, seed=0, decay=0.3, beta_shift=0.0, dtype=F32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    l2 = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q, k = (l2(jax.random.normal(key, (b, t, h, kd))).astype(dtype) for key in ks[:2])
    v = jax.random.normal(ks[2], (b, t, h, vd)).astype(dtype)
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, t, h, kd)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)) + beta_shift)
    return q, k, v, g, beta


def _by_head(form):
    """`kda.kda` or `kda.kda_gated` with o by head, (b, T, H, V), as the
    recurrence gives it: the entries hand o on as the kernels write it,
    (b, T, H * V)."""
    def heads(q, k, v, *rest, **kw):
        o, *others = form(q, k, v, *rest, **kw)
        assert o.shape == (*v.shape[:2], v.shape[2] * v.shape[3])
        return (o.reshape(v.shape), *others)

    return heads


kda_by_head, gated_by_head = _by_head(kda.kda), _by_head(kda.kda_gated)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _grads(form, ops, w, argnums):
    return jax.grad(lambda *o: (form(*o)[0].astype(F32) * w).sum(), argnums=argnums)(*ops)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _both_forms(ops, w, chunk):
    """(o, last state, five gradients) of the chunked form and of the
    recurrence: one compile a shape and chunk, whatever the values."""
    with jax.default_matmul_precision("highest"):
        got, states, last = kda_by_head(*ops, chunk=chunk)
        want, state = kda.kda_plain(*ops)
        return ((got, last.swapaxes(-1, -2), states,
                 _grads(lambda *o: kda_by_head(*o, chunk=chunk), ops, w, range(5))),
                (want, state, _grads(kda.kda_plain, ops, w, range(5))))


@pytest.mark.parametrize("chunk,t,decay,beta_shift", [
    (4, 16, 0.3, 0.0), (16, 40, 0.3, 0.0), (64, 128, 0.02, 0.0), (64, 128, 8.0, 0.0),
    (64, 128, 1.0, 6.0), (64, 128, 1.0, -6.0), (64, 50, 0.3, 0.0), (64, 128, 50.0, 3.0)])
def test_chunked_form_is_the_recurrence(chunk, t, decay, beta_shift):
    """Values, the state after the last token and all five gradients, in
    float32: 2e-5 of each one's largest entry whatever the chunk, with T a
    whole number of chunks or not (padded), decays from 0.98 a step to e^-50
    (no factor is the exp of something positive: nothing overflows), beta
    near 0 and near 1."""
    ops = _operands(2, t, 2, 16, 8, seed=t + int(decay * 100), decay=decay, beta_shift=beta_shift)
    w = jax.random.normal(jax.random.PRNGKey(9), (2, t, 2, 8))
    (got, last, states, ours), (want, state, plain) = _both_forms(ops, w, chunk)
    assert got.shape == want.shape and states.shape == (2, -(-t // chunk), 2, 8, 16)
    assert _rel(got, want) < 2e-5
    if t % chunk == 0:
        assert _rel(last, state) < 2e-5
    for name, a, b in zip("q k v g beta".split(), ours, plain):
        assert np.isfinite(np.asarray(a)).all(), name
        assert _rel(a, b) < (2e-4 if decay > 10 else 5e-5), name  # dg itself is e^-50 small there


def test_a_chunk_s_backward_is_jax_s_own():
    """`_chunk_bwd`, which the backward kernel runs, against jax.vjp of
    `_chunk_fwd` in float32, a state and its cotangent handed in."""
    c, kd, vd = 32, 128, 128
    q, k, v, g, beta = (a[0, :, 0] for a in _operands(1, c, 1, kd, vd, seed=3))
    beta = beta[:, None]
    St = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (vd, kd))
    with jax.default_matmul_precision("highest"):
        form = lambda *a: kda._chunk_fwd(*a, kd ** -0.5, kda._np_roll, kda._dot_highest)
        (o, nxt), pull = jax.vjp(form, q, k, v, g, beta, St)
        do = jax.random.normal(jax.random.PRNGKey(6), o.shape)
        dS = jax.random.normal(jax.random.PRNGKey(7), nxt.shape)
        want = pull((do, dS))
        got = kda._chunk_bwd(q, k, v, g, beta, St, do, dS, kd ** -0.5, kda._np_roll,
                             kda._dot_highest)
    for name, a, b in zip("dq dk dv dg dbeta dS".split(), got, want):
        assert _rel(a, b) < 1e-5, name


@pytest.mark.parametrize("dtype,chunk,t", [(F32, 64, 128), (jnp.bfloat16, 64, 100), (F32, 16, 48)])
def test_kernels_in_interpret_mode_are_both_forms(dtype, chunk, t):
    """kda_fwd and kda_bwd under `interpret=True` against the recurrence and
    against the chunked form in jax.numpy: the output, the chunk states, the
    last state and the five gradients. In float32 1e-5; with bf16 operands
    the kernels stand as near the recurrence as the chunked form does."""
    ops = _operands(1, t, 2, 128, 128, seed=11, decay=0.5, dtype=dtype)
    tol = 2e-5 if dtype == F32 else 2e-2
    with jax.default_matmul_precision("highest"):
        want, state = kda.kda_plain(*ops)
        got, states, last = kda_by_head(*ops, chunk=chunk, interpret=True)
        chunked, states_c, last_c = kda_by_head(*ops, chunk=chunk)
        assert got.dtype == dtype and states.dtype == F32
        assert _rel(got, want) < tol and _rel(got, chunked) < tol / 4
        assert _rel(states, states_c) < tol / 4 and _rel(last, last_c) < tol / 4
        w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
        ours = _grads(lambda *o: kda_by_head(*o, chunk=chunk, interpret=True), ops, w, range(5))
        plain = _grads(kda.kda_plain, ops, w, range(5))
        for name, a, b in zip("q k v g beta".split(), ours, plain):
            assert a.dtype == (F32 if name in ("g", "beta") else dtype)
            assert _rel(a, b) < tol, name


L2_EPS = 1e-6


def _before_the_norm(q, k):
    """q and k as a convolution leaves them: no unit length, a head's lengths
    a hundred apart, and some steps' k all zeros."""
    b, t, h, d = q.shape
    by_head = jnp.logspace(-1, 1, h)[:, None]
    ks = jax.random.split(jax.random.PRNGKey(31), 2)
    q = (q.astype(F32) * by_head * jnp.exp(jax.random.normal(ks[0], (b, t, h, 1)))).astype(q.dtype)
    k = (k.astype(F32) * by_head[::-1] * jnp.exp(jax.random.normal(ks[1], (b, t, h, 1)))
         ).astype(k.dtype)
    return q, k.at[:, 7].set(0).at[0, 70:73, 1].set(0)


@pytest.mark.parametrize("norm", ["handed_in", "inside"])
@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16])
def test_the_gate_made_inside_the_kernels(dtype, norm):
    """`kda_gated` under `interpret=True`: g = -exp(A_log) softplus(f +
    dt_bias) made in the kernels from f in the operands' dtype, against the
    recurrence on `gate_log_decay`'s g: values, the last state and the seven
    gradients, A_log's and dt_bias's summed over the chunks in the call.
    With the norm inside (`l2_eps`), q and k come in as a convolution leaves
    them: against the recurrence on operands normed in jax.numpy, and against
    today's order, the norm in jax.numpy and then the kernels: the forward
    to the bit, the gradients of q and k one rounding of the cotangent
    apart; T is no whole number of chunks, so the padded steps' k is zero,
    and so are some steps' inside the sequence."""
    b, t, h, d = 2, 100, 2, 128
    q, k, v, _, beta = _operands(b, t, h, d, d, seed=21, dtype=dtype)
    eps = None if norm == "handed_in" else L2_EPS
    if eps is not None:
        q, k = _before_the_norm(q, k)
    f = jax.random.normal(jax.random.PRNGKey(3), (b, t, h, d)).astype(dtype)
    a_log = jnp.log(jnp.array([1.5, 7.0]))
    dt_bias = -3.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(4), (h, d))
    ops = (q, k, v, f, a_log, dt_bias, beta)
    tol = 3e-5 if dtype == F32 else 2e-2
    normed = (lambda u: u) if eps is None else (lambda u: kda.l2norm(u, eps))
    plain = lambda q, k, v, f, a_log, dt_bias, beta: kda.kda_plain(
        normed(q), normed(k), v, kda.gate_log_decay(f, a_log, dt_bias), beta)
    inside = lambda *o, **kw: gated_by_head(*o, l2_eps=eps, **kw)
    with jax.default_matmul_precision("highest"):
        want, state = plain(*ops)
        got, states, last = inside(*ops, interpret=True)
        assert _rel(got, want) < tol and _rel(last.swapaxes(-1, -2), state) < tol
        # off the TPU the same call runs the chunked form on the same g
        assert _rel(inside(*ops)[0], want) < tol
        w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
        ours = _grads(lambda *o: inside(*o, interpret=True), ops, w, range(7))
        names = "q k v f A_log dt_bias beta".split()
        wanted = _grads(plain, ops, w, range(7))
        for name, a, b in zip(names, ours, wanted):
            assert a.shape == b.shape and np.isfinite(np.asarray(a, np.float32)).all(), name
            assert _rel(a, b) < tol, name
        if eps is None:
            return
        # today's order: XLA norms, the kernels take the normed pair
        outside = lambda q, k, *o, **kw: gated_by_head(normed(q), normed(k), *o, **kw)
        for a, b in zip((got, states, last), outside(*ops, interpret=True)):
            np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for name, a, b in zip(names, ours, _grads(lambda *o: outside(*o, interpret=True),
                                                  ops, w, range(7))):
            if name in ("q", "k"):  # the norm's vjp on a float32 cotangent, not a rounded one
                assert _rel(a, b) < (1e-6 if dtype == F32 else 1e-2), name
            else:
                np.testing.assert_array_equal(np.asarray(a, np.float32),
                                              np.asarray(b, np.float32), err_msg=name)
        # and the chunked form, which norms before it (any backend but a TPU), with its gradients
        for name, a, b in zip(names, _grads(inside, ops, w, range(7)), wanted):
            assert _rel(a, b) < tol, name
        # a row of zeros norms to zeros; its gradient is dn / sqrt(eps)
        assert not np.asarray(normed(k)[:, 7], np.float32).any()
        assert np.asarray(ours[1][:, 7], np.float32).any()


def test_the_state_is_carried_across_a_call_s_chunks():
    """The states the forward writes are the recurrence's after each chunk's
    steps, and a call whose chunks each start from nothing is far off."""
    ops = _operands(1, 96, 2, 16, 8, seed=5, decay=0.05)
    with jax.default_matmul_precision("highest"):
        _, states, _ = kda_by_head(*ops, chunk=32)
        for n in (1, 2):
            _, want = kda.kda_plain(*(a[:, :32 * n] for a in ops))
            assert _rel(states[:, n].swapaxes(-1, -2), want) < 2e-5
        assert not np.asarray(states[:, 0]).any()
        want, _ = kda.kda_plain(*ops)
        alone = jnp.concatenate([kda_by_head(*(a[:, at:at + 32] for a in ops), chunk=32)[0]
                                 for at in (0, 32, 64)], axis=1)
    assert _rel(alone[:, :32], want[:, :32]) < 2e-5 and _rel(alone, want) > 0.1


def test_which_path_a_shape_takes(monkeypatch):
    assert kda.kda_path(8192, 128, 128) == "xla"  # no TPU here
    monkeypatch.setattr(kda, "_on_tpu", lambda: True)
    assert kda.kda_path(8192, 128, 128) == kda.kda_path(100, 128, 128, 32) == "pallas"
    assert kda.kda_path(8192, 64, 128) == kda.kda_path(8192, 128, 128, 16) == "xla"
    with pytest.raises(ValueError, match="power of two"):
        kda_by_head(*_operands(1, 8, 1, 8, 8), chunk=6)
