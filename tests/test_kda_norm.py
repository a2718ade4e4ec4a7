"""ops/kda_norm.py: the mixer's plain lines and, in interpret mode, the two
kernels against RMSNorm_head(o) * w * sigmoid(z) worked a head at a time in
float64: y and the gradients of o, z and the weight, at 2 and 32 heads of 128
lanes, at a T that is no whole tile and at one of several tiles, bf16 rounded
once, a first head a thousand times the last, off a TPU and at a head of 16
the plain lines, and the two calls' names against every pattern the
benchmark's per-layer metrics read a trace with."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import trace
from ray_tpu.ops import kda_norm as kn
from ray_tpu.ops.kda_norm import kda_norm, kda_norm_plain

B_, EPS, W = 2, 1e-5, 128
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _inputs(t, heads, seed=0, dtype=jnp.float32, scale=None, width=W):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    o = jax.random.normal(ks[0], (B_, t, heads * width), jnp.float32)
    o = (o if scale is None else o * scale).astype(dtype)
    z = (2 * jax.random.normal(ks[1], (B_, t, heads * width), jnp.float32)).astype(dtype)
    w = 1 + 0.1 * jax.random.normal(ks[2], (width,), jnp.float32)
    dy = jax.random.normal(ks[3], (B_, t, heads * width), jnp.float32)  # loss = <y, dy>
    return o, z, w, dy


def _norm_by_head(o, z, w, edges_off_by=0):
    """The equations a head at a time (`edges_off_by`: every head's edges
    that many lanes to the right, the lanes rotated, for the test that a leak
    is caught)."""
    heads = o.shape[-1] // w.shape[0]
    x = jnp.roll(o, -edges_off_by, axis=-1)
    parts = [p / jnp.sqrt(jnp.mean(p * p, axis=-1, keepdims=True) + EPS)
             for p in jnp.split(x, heads, axis=-1)]
    normed = jnp.roll(jnp.concatenate(parts, axis=-1), edges_off_by, axis=-1)
    return normed * jnp.tile(w, heads) / (1 + jnp.exp(-z))


def by_hand(o, z, w, dy, **kw):
    """(y, do, dz, dweight) of the equations in float64."""
    with jax.enable_x64(True):
        o, z, w, dy = (jnp.asarray(np.asarray(v, np.float64)) for v in (o, z, w, dy))
        y, vjp = jax.vjp(lambda o, z, w: _norm_by_head(o, z, w, **kw), o, z, w)
        return tuple(np.asarray(v) for v in (y, *vjp(dy)))


def _value_and_grads(fn, o, z, w, dy):
    y, vjp = jax.vjp(fn, o, z, w)
    return (y, *vjp(dy.astype(y.dtype)))


def _form(path):
    if path == "plain":
        return lambda o, z, w: kda_norm_plain(o, z, w, EPS)
    return lambda o, z, w: kda_norm(o, z, w, EPS, interpret=True)


def _assert_close(got, want, tol):
    for name, g, v in zip(("y", "do", "dz", "dweight"), got, want):
        assert g.shape == v.shape, name
        err = np.abs(np.asarray(g, np.float64) - v).max() / np.abs(v).max()
        assert err < tol, (name, err)


# T = 40 is no whole tile (48 rows: the rows past it are zeros that norm to
# zeros); 300 and 528 are two and three tiles of 256, the weight's gradient
# summed over them and, at 32 heads, over eight iterations of four heads
CASES = [(path, heads, 40) for path in ("plain", "kernels") for heads in (2, 32)]
CASES += [("kernels", 2, 300), ("kernels", 3, 528), ("kernels", 32, 272)]


@pytest.mark.parametrize("path,heads,t", CASES)
def test_against_the_norm_a_head_at_a_time(path, heads, t):
    """float32 throughout: interpret mode's reciprocal starts cruder than the
    chip's (ops/short_conv.py:_sigmoid) and ends 2e-5 off."""
    o, z, w, dy = _inputs(t, heads, seed=heads)
    got = _value_and_grads(_form(path), o, z, w, dy)
    _assert_close(got, by_hand(o, z, w, dy), 5e-5 if path == "kernels" else 1e-5)


@pytest.mark.parametrize("path", ["plain", "kernels"])
@pytest.mark.parametrize("heads,t", [(2, 64), (32, 40)])
def test_bf16_in_and_out(path, heads, t):
    """bf16 in and out, dweight float32. The kernels work float32 from o and
    z as read and round y, do and dz once: the float64 ones within half a
    bf16 step. The plain lines round the norm, the weight's product and the
    gated product each, and every step of their backward."""
    o, z, w, dy = _inputs(t, heads, dtype=jnp.bfloat16)
    dy = dy.astype(jnp.bfloat16).astype(jnp.float32)  # y's cotangent is in y's dtype
    y, do, dz, dw = _value_and_grads(_form(path), o, z, w, dy)
    assert (y.dtype, do.dtype, dz.dtype, dw.dtype) == (
        jnp.bfloat16, jnp.bfloat16, jnp.bfloat16, jnp.float32)
    want = by_hand(o.astype(jnp.float32), z.astype(jnp.float32), w, dy)
    if path == "plain":  # whose dweight is a bf16 sum over tokens and heads
        _assert_close((y, do, dz), want[:3], 0.03)
        return _assert_close((dw,), want[3:], 0.1)
    for got, v in zip((y, do, dz), want):
        np.testing.assert_allclose(np.asarray(got, np.float32), v, rtol=2 ** -8, atol=2e-5)
    np.testing.assert_allclose(dw, want[3], rtol=1e-4, atol=1e-4)
    # and nearer than the lines that stood
    plain = _value_and_grads(_form("plain"), o, z, w, dy)
    err = lambda got: np.abs(np.asarray(got, np.float32) - want[0]).mean()
    assert err(y) < 0.7 * err(plain[0])


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_a_sum_that_leaks_across_a_head_s_edge_is_caught(path):
    """The first head's values a thousand times the last's, each head held to
    the by-hand norm on its own scale: a norm whose heads' edges lay a few
    lanes off would take a larger head's lanes into a smaller one's sum, and
    stands far outside what the forms are held to."""
    heads = 8
    scale = jnp.repeat(jnp.logspace(1.5, -1.5, heads), W)
    o, z, w, dy = _inputs(40, heads, seed=3, scale=scale)
    got = _value_and_grads(_form(path), o, z, w, dy)
    want = by_hand(o, z, w, dy)
    leaky = by_hand(o, z, w, dy, edges_off_by=8)
    for h in range(heads):
        at = slice(h * W, (h + 1) * W)
        _assert_close([v[..., at] for v in got[:3]], [v[..., at] for v in want[:3]], 5e-5)
    _assert_close(got[3:], want[3:], 5e-5)
    last = slice((heads - 1) * W, heads * W)
    for v, off in zip(want[:3], leaky[:3]):
        assert np.abs(off[..., last] - v[..., last]).max() > 0.1 * np.abs(v[..., last]).max()


def test_off_a_tpu_and_at_a_head_of_16_the_plain_lines_run(monkeypatch):
    o, z, w, _ = _inputs(40, 4)
    assert kn.norm_path(W) == "xla"
    assert "pallas_call" not in str(jax.make_jaxpr(lambda: kda_norm(o, z, w, EPS))())
    np.testing.assert_array_equal(np.asarray(kda_norm(o, z, w, EPS)),
                                  np.asarray(kda_norm_plain(o, z, w, EPS)))
    for bad in (lambda: kda_norm(o, z[..., :256], w, EPS), lambda: kda_norm(o, z, w[:-1], EPS),
                lambda: kda_norm(o[0], z[0], w, EPS)):
        with pytest.raises(ValueError):
            bad()
    # and on one: a head that is not one vector of lanes (`KimiLinearConfig.tiny`'s 16),
    # also where a test forces the kernels
    monkeypatch.setattr(kn, "_on_tpu", lambda: True)
    assert kn.norm_path(W) == "pallas" and kn.norm_path(16) == kn.norm_path(256) == "xla"
    o16, z16, w16, _ = _inputs(40, 4, width=16)
    for fn in (lambda: kda_norm(o16, z16, w16, EPS), lambda: kda_norm(o, z, w[:64], EPS),
               lambda: kda_norm(o16, z16, w16, EPS, interpret=True)):
        assert "pallas_call" not in str(jax.make_jaxpr(fn)())
    assert "pallas_call" in str(jax.make_jaxpr(lambda: kda_norm(o, z, w, EPS))())


CELL = "kimi_linear_l5_ep32.t8192"


def _patterns_read_in_the_cell():
    """{metric: pattern} of the per-layer metrics of BENCHMARK.json that are
    read in the cell that runs the pair (those that list it, and those that
    list no cells) and find device ops by a pattern."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = [m["name"] for m in json.load(f)["per_layer"] if CELL in m.get("workloads", [CELL])]
    found = {}
    for name in listed:
        with open(os.path.join(ROOT, "bench", "layer_metrics", f"{name}.json")) as f:
            found[name] = json.load(f).get("args", {}).get("pattern")
    return {name: pattern for name, pattern in found.items() if pattern}


@pytest.mark.parametrize("call", ["kda_norm_fwd", "kda_norm_bwd"])
def test_no_accepted_metric_s_pattern_reads_the_pair_s_calls(call):
    """A trace's line of either call, as bench/reducers.py matches it (the
    instruction's name, its opcode, ` -> `), is found by
    `kda_norm_share_pct`'s pattern and by no other metric's that the cell
    reports: the delta rule's `kda_(fwd|bwd)` among them."""
    patterns = _patterns_read_in_the_cell()
    assert {"kda_norm_share_pct", "kda_share_pct", "kda_fwd_roofline", "kda_bwd_roofline",
            "flash_mla_share_pct", "moe_gmm1024_d2304_share_pct"} <= set(patterns)
    for name in (call, f"{call}.3", f"jit__{call[4:]}_call_{call}.17"):
        line = trace.kind(f"%{name} = bf16[2,8192,4096]{{2,1,0:T(8,128)(2,1)}} custom-call("
                          f"bf16[2,8192,4096]{{2,1,0}} %o.1), custom_call_target=\"tpu_custom_call\"")
        assert line.endswith(" custom-call -> bf16[2,8192,4096]"), line
        found = [metric for metric, pattern in patterns.items() if re.search(pattern, line)]
        assert found == ["kda_norm_share_pct"], (line, found)
    assert re.search(patterns["kda_share_pct"], "kda_fwd custom-call -> (bf16[2,8192,4096])")
    assert not re.search(patterns["kda_norm_share_pct"], "kda_fwd custom-call -> bf16[2]")


@pytest.mark.parametrize("path,heads,t", [("plain", 2, 40), ("kernels", 2, 40),
                                          ("kernels", 32, 272), ("kernels", 3, 528)])
def test_under_silu_against_its_plain_lines_in_float64(path, heads, t):
    """The gate told `silu` (models/qwen3_next.py's mixer): RMSNorm_head(o) *
    w * z sigmoid(z), y and the three gradients against the equations a head
    at a time in float64, the plain lines and the kernels in interpret mode."""
    o, z, w, dy = _inputs(t, heads, seed=heads + 7)
    form = ((lambda o, z, w: kda_norm_plain(o, z, w, EPS, kn.SILU)) if path == "plain" else
            (lambda o, z, w: kda_norm(o, z, w, EPS, gate=kn.SILU, interpret=True)))
    got = _value_and_grads(form, o, z, w, dy)
    with jax.enable_x64(True):
        o64, z64, w64, dy64 = (jnp.asarray(np.asarray(v, np.float64)) for v in (o, z, w, dy))
        y, vjp = jax.vjp(lambda o, z, w: _norm_by_head(o, z, w) * z, o64, z64, w64)
        want = tuple(np.asarray(v) for v in (y, *vjp(dy64)))
    _assert_close(got, want, 5e-5 if path == "kernels" else 1e-5)


def test_under_sigmoid_the_calls_are_what_they_were():
    """Told nothing, and told `sigmoid`, the entry gives the same bits and
    the same two kernels (the cells' pinned steps hold their bodies); an
    activation it has no lines for is refused."""
    o, z, w, dy = _inputs(40, 2, dtype=jnp.bfloat16)
    plain = _value_and_grads(lambda o, z, w: kda_norm(o, z, w, EPS, interpret=True), o, z, w, dy)
    told = _value_and_grads(lambda o, z, w: kda_norm(o, z, w, EPS, gate=kn.SIGMOID, interpret=True),
                            o, z, w, dy)
    for a, b in zip(plain, told):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    text = str(jax.make_jaxpr(lambda: kda_norm(o, z, w, EPS, gate=kn.SILU, interpret=True))())
    assert "kda_norm_fwd" in text
    with pytest.raises(ValueError):
        kda_norm(o, z, w, EPS, gate="tanh")
