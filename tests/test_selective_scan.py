"""ops/selective_scan.py: the kernel pair in interpret mode against the
recurrence step by step (`selective_scan_plain`) and its jax.vjp, every
gradient, over several chunks with decays that carry state across them; what
a lost carry looks like; which path a shape takes."""

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import selective_scan as ss


def _operands(b, t, c, n, dtype, seed=0):
    """Steps log-uniform in 1e-3 to 1e-1 and rates about -1 .. -N, as the
    model's initialisation gives them: a state of rate -1 at a step of 1e-3
    is carried over a thousand steps, across every chunk here."""
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    f32 = jnp.float32
    u = jax.random.normal(k[0], (b, t, c), f32).astype(dtype)
    delta = jnp.exp(jax.random.uniform(k[1], (b, t, c), f32, np.log(1e-3), np.log(1e-1)))
    A = -jnp.arange(1, n + 1, dtype=f32) * jnp.exp(0.2 * jax.random.normal(k[2], (c, n)))
    B = jax.random.normal(k[3], (b, t, n), f32).astype(dtype)
    C = jax.random.normal(k[4], (b, t, n), f32).astype(dtype)
    D = 1 + 0.1 * jax.random.normal(k[5], (c,))
    dy = jax.random.normal(k[6], (b, t, c), f32).astype(dtype)
    return (u, delta, A, B, C, D), dy


def _both(ops, dy):
    """((y, states), the six gradients) by the kernels and by the recurrence."""
    out = []
    for fn in (lambda *a: ss.selective_scan(*a, interpret=True), ss.selective_scan_plain):
        (y, states), pull = jax.vjp(fn, *ops)
        out.append(((y, states), pull((dy, jnp.zeros_like(states)))))
    return out


def _rel(a, b):
    a, b = (np.asarray(v, np.float64) for v in (a, b))
    return np.linalg.norm(a - b) / np.linalg.norm(b)


NAMES = ("u", "delta", "A", "B", "C", "D")


def test_the_pair_is_the_recurrence_in_float32(b=2, t=384, c=768, n=16):
    """Two batch rows of three chunks and two tiles of three vectors of
    lanes: y, the chunk states and all six gradients
    within float32's rounding of the recurrence's own; dB and dC, whose lane
    sums go through two bf16 parts, within 2^-17 of theirs."""
    ops, dy = _operands(b, t, c, n, jnp.float32)
    ((y, states), grads), ((y_ref, states_ref), grads_ref) = _both(ops, dy)
    assert states.shape == (b, t // ss._CHUNK, n, c) and float(jnp.abs(states[:, -1]).max()) > 0.1
    assert _rel(y, y_ref) < 1e-6 and _rel(states, states_ref) < 1e-6
    for name, g, g_ref in zip(NAMES, grads, grads_ref):
        assert g.shape == g_ref.shape and g.dtype == g_ref.dtype, name
        assert _rel(g, g_ref) < (2e-5 if name in "BC" else 2e-6), name


def test_the_pair_takes_the_stream_s_dtype():
    """bf16 u, B and C in, bf16 y and du out, float32 steps and their
    gradient, at eight states: against the recurrence on the same rounded operands the
    difference is the outputs' one rounding."""
    ops, dy = _operands(1, 256, 128, 8, jnp.bfloat16, seed=3)
    ((y, states), grads), ((y_ref, _), grads_ref) = _both(ops, dy)
    assert y.dtype == jnp.bfloat16 and states.dtype == jnp.float32
    assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.float32, jnp.float32, jnp.bfloat16,
                                        jnp.bfloat16, jnp.float32]
    assert _rel(y, y_ref) < 4e-3
    for name, g, g_ref in zip(NAMES, grads, grads_ref):
        assert _rel(g, g_ref) < 6e-3, name


def test_a_lost_carry_is_far_outside_the_tolerance():
    """The same operands with the state dropped at every chunk's edge (the
    chunks scanned one by one): y moves by tens of percent, so the test
    above would see a kernel that lost its carry."""
    u, delta, A, B, C, D = _operands(1, 384, 256, 16, jnp.float32)[0]
    D = jnp.zeros_like(D)  # the skip is no part of the state's path
    y, _ = ss.selective_scan(u, delta, A, B, C, D, interpret=True)
    cut = lambda v: v.reshape(3, 128, v.shape[-1])
    lost, _ = ss.selective_scan_plain(cut(u), cut(delta), A, cut(B), cut(C), D)
    assert _rel(lost.reshape(y.shape), y) > 0.1


def test_which_shapes_take_the_kernels(monkeypatch):
    assert ss.scan_path(16384, 5120, 16) == "plain"  # no TPU here
    monkeypatch.setattr(ss, "_on_tpu", lambda: True)
    assert ss.scan_path(16384, 5120, 16) == "pallas" and ss.scan_path(256, 128, 8) == "pallas"
    for shape in ((100, 5120, 16), (256, 192, 16), (256, 256, 12)):
        assert ss.scan_path(*shape) == "plain", shape
    assert ss.chunk_of(16384) == 128 and ss.chunk_of(96) == 96
    assert ss._tile(5120) == 512 and ss._tile(768) == 384 and ss._tile(128) == 128
