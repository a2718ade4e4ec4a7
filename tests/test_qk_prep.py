"""ops/qk_prep.py: in interpret mode, the two kernels against the norm and
rotate-half worked a head at a time in float64: the rows and the gradients of
x and the weight, normed alone, turned alone and both, a key-value head
written to one and to eight rows (dk summed over them), q's further factor, a
rotary table of its own with a factor on it, positions that start elsewhere,
a T that is no whole tile and one of several, bf16 rounded once; and
`LlamaAttention` off a TPU, at heads of 64 or 256 and with `attn_fn` set,
where its plain lines run. (Whole tiny models through the pair:
tests/test_qk_prep_models.py, a file of its own for the workers' balance.)"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.layers import rope_angles
from ray_tpu.models.llama import LlamaAttention, LlamaConfig
from ray_tpu.ops import attention
from ray_tpu.ops.qk_prep import qk_prep, rope_tables

B_, D_, EPS = 2, 128, 1e-6


def _inputs(t, heads, rep, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (B_, t, heads * D_), jnp.float32).astype(dtype)
    w = 1 + 0.1 * jax.random.normal(ks[1], (D_,), jnp.float32)
    dy = jax.random.normal(ks[2], (B_ * heads * rep, t, D_), jnp.float32)  # loss = <rows, dy>
    return x, w, dy


def _by_head(x, w, angles, rep, scale, rope_scale):
    """The equations a head at a time: (B, T, heads * 128) -> the rows."""
    b, t, c = x.shape
    x = x.reshape(b, t, c // D_, D_)
    if w is not None:
        x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w
    if angles is not None:
        cos, sin = (f(angles)[None, :, None, :] * rope_scale for f in (jnp.cos, jnp.sin))
        x1, x2 = x[..., :D_ // 2], x[..., D_ // 2:]
        x = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    x = jnp.repeat(x * scale, rep, axis=2)  # head h to rows h * rep .. h * rep + rep - 1
    return x.transpose(0, 2, 1, 3).reshape(-1, t, D_)


def by_hand(x, w, dy, angles, rep, scale=1.0, rope_scale=1.0):
    """(rows, dx, dweight) in float64; dweight None where nothing is normed."""
    with jax.enable_x64(True):
        f64 = lambda v: None if v is None else jnp.asarray(np.asarray(v, np.float64))
        x, w, dy, angles = f64(x), f64(w), f64(dy), f64(angles)
        if w is None:
            out, vjp = jax.vjp(lambda x: _by_head(x, None, angles, rep, scale, rope_scale), x)
            return np.asarray(out), np.asarray(vjp(dy)[0]), None
        out, vjp = jax.vjp(lambda x, w: _by_head(x, w, angles, rep, scale, rope_scale), x, w)
        return tuple(np.asarray(v) for v in (out, *vjp(dy)))


def kernels(x, w, dy, angles, rep, scale=1.0, rope_scale=1.0):
    tables = None if angles is None else rope_tables(angles, rope_scale)
    fn = lambda x, w: qk_prep(x, w, tables, rep=rep, eps=EPS, scale=scale, interpret=True)
    out, vjp = jax.vjp(fn, x, w)
    return (out, *vjp(dy.astype(out.dtype)))


def _angles(t, offset=0, inv_freq=None):
    return rope_angles(D_, 10000.0, jnp.arange(t) + offset, inv_freq)


def _assert_close(got, want, tol):
    for name, g, v in zip(("rows", "dx", "dweight"), got, want):
        if v is None:
            assert g is None, name
            continue
        assert g.shape == v.shape, (name, g.shape, v.shape)
        err = np.abs(np.asarray(g, np.float64) - v).max() / np.abs(v).max()
        assert err < tol, (name, err)


def _float32(t, heads, rep, norm, rotary, **how):
    """The pair in float32 against the float64 equations."""
    x, w, dy = _inputs(t, heads, rep, seed=t + heads)
    angles = _angles(t, how.pop("offset", 0), how.pop("inv_freq", None)) if rotary else None
    w = w if norm else None
    _assert_close(kernels(x, w, dy, angles, rep, **how), by_hand(x, w, dy, angles, rep, **how), 2e-5)


def _bf16(t, heads, rep, norm, rotary):
    """bf16 in and out, dweight float32: every product and sum float32 from x
    as read, the rows and dx rounded once, within half a bf16 step of the
    float64 ones."""
    x, w, dy = _inputs(t, heads, rep, dtype=jnp.bfloat16)
    dy = dy.astype(jnp.bfloat16).astype(jnp.float32)
    angles, w = _angles(t) if rotary else None, w if norm else None
    out, dx, dw = kernels(x, w, dy, angles, rep, scale=0.7)
    assert (out.dtype, dx.dtype) == (jnp.bfloat16, jnp.bfloat16)
    rows, dx64, dw64 = by_hand(x.astype(jnp.float32), w, dy, angles, rep, scale=0.7)
    for got, v in ((out, rows), (dx, dx64)):
        np.testing.assert_allclose(np.asarray(got, np.float32), v, rtol=2 ** -8, atol=2e-5)
    if norm:
        assert dw.dtype == jnp.float32
        np.testing.assert_allclose(dw, dw64, rtol=1e-4, atol=1e-4)


def _plain_lines_run(monkeypatch, on_tpu, head_dim, attn_fn):
    """`LlamaAttention` lowered for a TPU: the pair at heads of 128 on a TPU
    with the flash calls its own, the plain lines in every other case."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: on_tpu)
    fn = functools.partial(attention.flash_causal_attention, interpret=False) if attn_fn else None
    cfg = LlamaConfig.tiny(n_head=2, n_kv_head=1, n_embd=2 * head_dim, block_size=256, attn_fn=fn)
    layer = LlamaAttention(cfg, qk_norm=True)
    x = jax.ShapeDtypeStruct((1, 256, cfg.n_embd), jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    assert params["params"]["q_norm"]["weight"].shape == (head_dim,)  # the leaves are the plain form's
    loss = lambda p, x: layer.apply(p, x).astype(jnp.float32).sum()
    text = jax.jit(jax.grad(loss)).trace(params, x).lower(lowering_platforms=("tpu",)).as_text()
    engaged = on_tpu and head_dim == 128 and not attn_fn  # the pair is written for one vreg a head
    assert ("qk_prep_fwd" in text, "qk_prep_bwd" in text) == (engaged, engaged)
    assert ("flash_fwd" in text) == (on_tpu or attn_fn)


CASES = {
    "norm_and_rotary": (_float32, 40, 2, 1, True, True),
    "norm_alone": (_float32, 40, 2, 1, True, False),
    "rotary_alone": (_float32, 40, 2, 1, False, True),
    "rep_8_norm_and_rotary": (_float32, 40, 2, 8, True, True),
    "rep_8_rotary_alone": (_float32, 40, 1, 8, False, True),
    "rep_8_norm_alone": (_float32, 40, 1, 8, True, False),
    "q_scale": (_float32, 40, 2, 1, True, True, {"scale": 0.0883}),
    "q_scale_alone_with_rotary": (_float32, 40, 2, 1, False, True, {"scale": 3.0}),
    "inv_freq_and_rope_scale": (_float32, 40, 2, 1, True, True, {
        "inv_freq": tuple(1.0 / (500000.0 ** (np.arange(0, D_, 2) / D_) * (1 + np.arange(D_ // 2) % 3))),
        "rope_scale": 1.2079}),
    "pos_offset": (_float32, 40, 2, 2, True, True, {"offset": 8192}),
    "several_tiles": (_float32, 528, 2, 2, True, True),  # three tiles of 256: dweight summed over them
    "several_tiles_rotary_alone": (_float32, 300, 3, 1, False, True),
    "bf16_norm_and_rotary": (_bf16, 64, 2, 1, True, True),
    "bf16_rep_8": (_bf16, 64, 1, 8, True, True),
    "bf16_rotary_alone": (_bf16, 64, 2, 2, False, True),
    "engaged_on_a_tpu": (_plain_lines_run, True, 128, False),
    "plain_off_a_tpu": (_plain_lines_run, False, 128, False),
    "plain_at_heads_of_64": (_plain_lines_run, True, 64, False),
    "plain_at_heads_of_256": (_plain_lines_run, True, 256, False),
    "plain_with_attn_fn": (_plain_lines_run, True, 128, True),
}


@pytest.mark.parametrize("case", CASES)
def test_the_pair(case, monkeypatch):
    check, *args = CASES[case]
    how = args.pop() if isinstance(args[-1], dict) else {}
    if check is _plain_lines_run:
        check(monkeypatch, *args)
    else:
        check(*args, **how)


def test_what_it_refuses():
    x, w, _ = _inputs(40, 2, 1)
    tables = rope_tables(_angles(40))
    for bad in (lambda: qk_prep(x[..., :192], w, tables), lambda: qk_prep(x, w[:64], tables),
                lambda: qk_prep(x, w, rope_tables(_angles(48)))):
        with pytest.raises(ValueError):
            bad()


def heads_stay_where_written(text, b, t, heads, kv_heads, d=D_):
    """Of a cell's lowered step (`tests/test_mellum.py:_step_text`: StableHLO
    and jaxpr) that takes the pair: no float32 array of q's or k's size with
    the heads an axis of their own, (B, T, H, 128): the view the plain lines
    norm and turn, which under the TPU's tiling is no bitcast of what the
    projection wrote and which XLA wrote out in the other layout."""
    for h in {heads, kv_heads}:
        assert f"{b}x{t}x{h}x{d}xf32" not in text and f"f32[{b},{t},{h},{d}]" not in text, h
