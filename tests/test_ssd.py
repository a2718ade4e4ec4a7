"""ops/ssd.py: the chunked form and, in interpret mode, the two kernels
against the recurrence itself, one time step after another: values and the
gradients of x, dt, A, B, C and D, at a T of several chunks, at two chunk
sizes, with a head slow enough to carry state across every chunk, at one,
two and eight groups of B and C (a grid step's heads a whole group, and a
part of one)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssd as ssd_mod
from ray_tpu.ops.ssd import ssd

B_, T, H, P, N = 2, 64, 8, 32, 16


def _inputs(groups=1, seed=0, H=H, P=P):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B_, T, H, P), jnp.float32)
    # head 0 decays by exp(-0.002) a step, the last by about exp(-2.5): one
    # carries state over the whole sequence, one forgets within a chunk
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B_, T, H)) - 1.0)
    A = -jnp.exp(jnp.linspace(np.log(0.005), np.log(6.0), H))
    Bm = jax.random.normal(ks[2], (B_, T, groups, N), jnp.float32) / np.sqrt(N)
    Cm = jax.random.normal(ks[3], (B_, T, groups, N), jnp.float32)
    D = 1.0 + 0.1 * jax.random.normal(ks[4], (H,))
    w = jax.random.normal(ks[5], (B_, T, H, P))  # the cotangent: loss = <y, w>
    return (x, dt, A, Bm, Cm, D), w


def step_by_step(x, dt, A, Bm, Cm, D, carry_every=None):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t;
    with `carry_every` the state is dropped at every such step (what a
    chunked form that lost its carry would compute)."""
    (H, P), g = x.shape[2:], Bm.shape[2]
    rep = lambda v: jnp.repeat(v, H // g, axis=2)
    Bh, Ch = rep(Bm), rep(Cm)

    def step(S, t):
        if carry_every:
            S = jnp.where(t % carry_every == 0, 0.0, S)
        S = (jnp.exp(dt[:, t] * A)[..., None, None] * S
             + jnp.einsum("bh,bhp,bhn->bhpn", dt[:, t], x[:, t], Bh[:, t]))
        return S, jnp.einsum("bhpn,bhn->bhp", S, Ch[:, t]) + D[:, None] * x[:, t]

    _, y = jax.lax.scan(step, jnp.zeros((B_, H, P, N)), jnp.arange(T))
    return y.swapaxes(0, 1)


def _value_and_grads(fn, args, w):
    return jax.value_and_grad(lambda *a: jnp.vdot(fn(*a), w), argnums=range(6))(*args)


# heads and their width beside the groups: 8 of 32 in two groups are a slab
# a group and a grid step; 16 of 64 in eight are a slab a group likewise (the
# cell of models/nemotron_h.py in small: a step's heads one group); 20 of 64
# in two are five slabs a group, which a step takes one at a time, so five
# steps share a group's columns and their parts of dB and dC add up
@pytest.mark.parametrize("path,chunk,groups,heads,width", [
    ("chunked", 16, 2, H, P), ("chunked", 8, 1, H, P), ("kernels", 8, 1, H, P),
    ("kernels", 16, 1, H, P), ("kernels", 16, 2, H, P), ("kernels", 16, 8, 16, 64),
    ("kernels", 32, 2, 20, 64), ("chunked", 16, 8, 16, 64)])
def test_against_the_recurrence(path, chunk, groups, heads, width):
    args, w = _inputs(groups, H=heads, P=width)
    interpret = True if path == "kernels" else None
    got = lambda *a: ssd(*a, chunk, interpret=interpret)[0]
    with jax.default_matmul_precision("highest"):
        y = got(*args)
        want = step_by_step(*args)
        # float32 throughout: what is left is the order of the sums
        np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)
        _, grads = _value_and_grads(got, args, w)
        _, wanted = _value_and_grads(step_by_step, args, w)
    for name, a, b in zip(("x", "dt", "A", "B", "C", "D"), grads, wanted):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3 * scale, err_msg=name)


def test_the_comparison_sees_the_carried_state():
    """A form that dropped the state at each chunk's edge is far outside the
    tolerance above, because head 0 carries across every chunk."""
    args, _ = _inputs()
    whole, cut = step_by_step(*args), step_by_step(*args, carry_every=8)
    assert float(jnp.abs(whole - cut)[:, :, 0].max()) > 0.1
    _, states = ssd(*args, 8)
    assert states.shape == (B_, T // 8, H, P, N)
    assert float(jnp.abs(states[:, -1, 0]).max()) > 0.1  # still held at the last chunk


@pytest.mark.parametrize("groups", [2, 8])
def test_a_head_reads_its_own_group(groups):
    """What every head reading group 0's B and C would compute is far from
    what the kernels give, in the output and in every later group's dB and
    dC (which would be zero: nobody read them)."""
    args, w = _inputs(groups, H=16, P=64)
    x, dt, A, Bm, Cm, D = args
    first = lambda v: jnp.broadcast_to(v[:, :, :1], v.shape)
    fn = lambda *a: ssd(*a, 16, interpret=True)[0]
    with jax.default_matmul_precision("highest"):
        _, grads = _value_and_grads(fn, args, w)
        y = fn(*args)
        wrong = step_by_step(x, dt, A, first(Bm), first(Cm), D)
        right = step_by_step(*args)
    heads = 16 // groups  # a group's
    np.testing.assert_allclose(y[:, :, :heads], wrong[:, :, :heads], rtol=2e-4, atol=2e-4)
    assert float(jnp.abs(y - wrong)[:, :, heads:].max()) > 0.5
    assert float(jnp.abs(y - right).max()) < 1e-3
    for d in grads[3:5]:  # dB, dC
        assert d.shape == Bm.shape
        per_group = jnp.abs(d).max((0, 1, 3))
        assert (np.asarray(per_group) > 0.05).all(), per_group


@pytest.mark.parametrize("groups", [1, 8])
def test_kernels_equal_the_chunked_form_in_bf16(groups):
    """Same roundings in both: bf16 operands, float32 sums and decays."""
    args, w = _inputs(groups, H=16 if groups > 1 else H, P=64 if groups > 1 else P)
    x, dt, A, Bm, Cm, D = args
    args = (x.astype(jnp.bfloat16), dt, A, Bm.astype(jnp.bfloat16), Cm.astype(jnp.bfloat16), D)
    (y, s), (yk, sk) = ssd(*args, 16), ssd(*args, 16, interpret=True)
    assert y.dtype == yk.dtype == jnp.bfloat16 and s.dtype == sk.dtype == jnp.float32
    # a last bit of bf16 at |y| up to 16 is 0.0625: the sums differ in order
    np.testing.assert_allclose(yk.astype(np.float32), y.astype(np.float32), rtol=2e-2, atol=0.07)
    np.testing.assert_allclose(sk, s, rtol=2e-2, atol=2e-2)


def test_short_sequence_is_one_chunk_and_ragged_is_refused():
    (x, dt, A, Bm, Cm, D), _ = _inputs()
    y, states = ssd(x[:, :8], dt[:, :8], A, Bm[:, :8], Cm[:, :8], D, 256)
    assert y.shape == (B_, 8, H, P) and states.shape[1] == 1
    with pytest.raises(ValueError, match="multiple of the chunk"):
        ssd(x[:, :24], dt[:, :24], A, Bm[:, :24], Cm[:, :24], D, 16)


def test_under_remat_bit_for_bit():
    """With the kernel's named outputs saved across jax.checkpoint, values
    and gradients are those of the plain call, and the forward kernel is
    traced once more only where nothing is saved."""
    args, w = _inputs()
    fn = lambda *a: ssd(*a, 16, interpret=True)[0]
    saved = jax.checkpoint(fn, policy=jax.checkpoint_policies.save_only_these_names(
        "ssm_y", "ssm_states"))
    plain, again = (jax.jit(lambda *a, f=f: _value_and_grads(f, a, w))(*args) for f in (fn, saved))
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(again)):
        assert (np.asarray(a) == np.asarray(b)).all()
    calls = lambda f: str(jax.make_jaxpr(lambda *a: _value_and_grads(f, a, w))(*args)).count(
        "name=ssd_fwd")
    unsaved = jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    assert (calls(fn), calls(saved), calls(unsaved)) == (1, 1, 2)


def test_path_and_tiles(monkeypatch):
    assert ssd_mod.ssd_path(4096, 64, 64, 1, 256) == "xla"  # no TPU here
    monkeypatch.setattr(ssd_mod, "_on_tpu", lambda: True)
    assert ssd_mod.ssd_path(4096, 64, 64, 1, 256) == "pallas"
    # groups whose heads are whole slabs: two of 32 heads, eight of 8 at a chunk of 128
    assert ssd_mod.ssd_path(4096, 64, 64, 2, 256) == "pallas"
    assert ssd_mod.ssd_path(8192, 64, 64, 8, 128) == "pallas"
    # what the kernels do not take runs the jax.numpy form: a group of one
    # head (half a slab), groups that do not divide the heads, heads a vector
    # wide, a chunk that is no whole vector, a ragged sequence
    for sizes in ((4096, 64, 64, 64, 256), (4096, 64, 64, 3, 256), (4096, 64, 128, 1, 256),
                  (4096, 64, 64, 1, 64), (4000, 64, 64, 1, 256), (4096, 3, 64, 1, 256)):
        assert ssd_mod.ssd_path(*sizes) == "xla", sizes
    assert ssd_mod.head_tile(64, 64) == (2, 8)
    # a grid step's heads lie in one group: all eight of a group of eight,
    # four slabs of a group of 32, one slab of a group of five slabs
    assert ssd_mod.head_tile(64, 64, 8) == (2, 8) and ssd_mod.head_tile(64, 64, 2) == (2, 8)
    assert ssd_mod.head_tile(20, 64, 2) == (2, 2) and ssd_mod.head_tile(64, 64, 16) == (2, 4)
    with pytest.raises(ValueError, match="do not fill slabs"):
        ssd_mod.head_tile(64, 64, 64)
    assert ssd_mod.head_tile(8, 32) == (4, 8) and ssd_mod.head_tile(6, 64) == (2, 6)
    with pytest.raises(ValueError, match="do not fill slabs"):
        ssd_mod.head_tile(4, 128)
