"""Chip smoke: the JaxTrainer path on a TPU v5e, through the entry points a
user calls. The quickest proof that the system still starts on the chip.

    python chip_smoke.py             one chip: ray_tpu.init() -> raylet lease
                                     {"TPU": 1} -> JaxTrainer worker ->
                                     TrainStep -> GPT-2-124M (bf16, B=16,
                                     T=1024), compile + 8 steps, flash kernel
                                     checked against the XLA reference
    python chip_smoke.py --chips 4   four chips, one worker leased {"TPU": 4}:
                                     the same model and batch on a
                                     {"dp": 2, "tp": 2} mesh against a
                                     one-device mesh, and no other phase

This process never initializes a JAX backend: a chip belongs to one process,
and that process is the worker whose lease holds it. Everything known about
the device travels back from that worker through train.report. Any failure
exits non-zero; there is no retry, no smaller model and no CPU path. The last
line of stdout is {"ok": true, "device": {...}} only after a run on a TPU.

The script stops every process it starts and says so: after shutdown it lists
what is left of its session (`processes_left_running`) and fails on any, or if
the worker that held the chip still exists in any state.

It also holds the program's own clock to its own: TrainStep's telemetry (step
seconds, tokens/s, MFU, from the completion of each step on the device) must
agree with the steps as timed here to block_until_ready, and goodput after
the compile call must show a busy device.

--rehearse runs the same control flow in a sandbox without a chip (tiny
model, CPU devices, the pallas kernel in interpret mode). It proves paths and
arguments, measures nothing, and never prints the "ok" line.

Not a benchmark: the step seconds printed here are a sighting, not a metric.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import math
import os
import statistics
import sys
import tempfile
import time

# Bounds the script holds the run to (bf16 inputs, fp32 accumulation).
ATTN_REL_TOL = 2e-2   # max|flash - xla| / max|xla|, output and dq, dk, dv
LAYER_REL_TOL = 3e-2  # a whole bf16 layer against its float32 lines: the latent layer read 0.009-0.018 (PR 63)
LOSS_REL_TOL = 2e-2   # |loss_mesh - loss_one_device| / loss_one_device, per step
TELEMETRY_REL_TOL = 2e-2  # the program's step seconds, tokens/s and MFU against the blocked steps
GOODPUT_FLOOR = 0.9   # productive share of the wall time after the compile call

# attn_shapes: the flash check runs at both head widths the benchmark's cells
# use; windowed_shapes: and under the two cells' windows at their window
# layers' shape, (B, T, H, D) and the window; bd_shapes: and over a doubled
# stream under the block-diffusion mask, (B, 2T, H, D) and the block length
FULL = {"model": None, "B": 16, "T": 1024,
        "attn_shapes": [(16, 1024, 12, 64), (1, 2048, 8, 128),
                        (2, 8192, 16, 256)],  # heads of two vregs: qwen3_next_80b_l5_ep32.t8192's
        "windowed_shapes": [((2, 8192, 32, 128), 1024), ((2, 8192, 32, 128), 2048)],
        "bd_shapes": [((1, 16384, 32, 128), 4)]}  # the cell's own: sdar_30b_a3b_l5_ep8.t8192
TINY = {
    "model": {"vocab_size": 257, "block_size": 256, "n_layer": 2, "n_head": 4,
              "n_embd": 64},
    "B": 4, "T": 256, "attn_shapes": [(2, 256, 2, 64), (1, 256, 2, 128), (1, 256, 2, 256)],
    "windowed_shapes": [((1, 512, 2, 128), 100), ((1, 512, 2, 128), 256)],
    "bd_shapes": [((1, 512, 2, 128), 4)],
}


# ------------------------------------------------------------- worker side


def _setup(config):
    """Device report, model config and batch; shared by both loops."""
    import jax
    import numpy as np

    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.ops.attention import attention_path
    from ray_tpu.train import _telemetry

    devs = jax.devices()
    kind = devs[0].device_kind
    if not config["rehearse"] and devs[0].platform != "tpu":
        raise RuntimeError(f"worker sees {devs[0].platform!r}, not a TPU")
    peak = _telemetry.peak_flops_per_device(kind)
    if not config["rehearse"] and peak is None:
        raise RuntimeError(f"no peak FLOP/s known for device kind {kind!r}")
    cache_dir = jax.config.jax_compilation_cache_dir
    report = {
        "worker_pid": os.getpid(),
        "platform": devs[0].platform,
        "device_kind": kind,
        "device_count": len(devs),
        "tpu_visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "peak_flops_per_device": peak,
        "attention_path": attention_path(config["T"]),
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_before": _cache_entries(cache_dir),
    }
    cfg = (
        GPT2Config.gpt2_124m() if config["model"] is None
        else GPT2Config(**config["model"])
    )
    report.update(
        model="gpt2_124m" if config["model"] is None else "tiny (rehearsal)",
        dtype=jax.numpy.dtype(cfg.dtype).name, B=config["B"], T=config["T"],
        flops_per_token=cfg.flops_per_token(config["T"]))
    rng = np.random.default_rng(config["seed"])
    tokens = rng.integers(0, cfg.vocab_size, (config["B"], config["T"] + 1))
    batch = {"idx": tokens[:, :-1].astype(np.int32),
             "targets": tokens[:, 1:].astype(np.int32)}
    return devs, report, cfg, batch


def _mem(device, key):
    return (device.memory_stats() or {}).get(key)  # None on CPU devices


def _cache_entries(cache_dir):
    return len(glob.glob(os.path.join(cache_dir, "*-cache"))) if cache_dir else 0


def _run_steps(ts, state, batch, n):
    """n calls of TrainStep.step, each timed to block_until_ready. Returns
    per-call (seconds, loss, compiled) — `compiled` is a jit cache miss —
    and the program's own telemetry summary after them, with its goodput
    since the first call (the compile call) returned."""
    import jax

    out = []
    for i in range(n):
        before = ts._step._cache_size()
        t0 = time.perf_counter()
        state, metrics = ts.step(state, batch)
        jax.block_until_ready((state, metrics))
        dt = time.perf_counter() - t0
        out.append((dt, float(metrics["loss"]), ts._step._cache_size() != before))
        if i == 0:
            warm, productive = time.perf_counter(), ts.telemetry.summary()["productive_time_s"]
    telemetry = ts.telemetry.summary()
    telemetry["n_devices"] = int(ts.mesh.devices.size)
    telemetry["goodput_after_warmup"] = (
        (telemetry["productive_time_s"] - productive) / (time.perf_counter() - warm))
    return state, out, telemetry


def _check_losses(losses):
    import math

    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[1]:
        raise RuntimeError(f"loss did not fall: {losses}")


def _check_flash_vs_xla(shape, seed, on_tpu, window=None, blocks=None):
    """flash_causal_attention against xla_causal_attention at one (B, T, H, D),
    same seed, under `window` where given, or with `blocks` over a doubled
    stream under the block-diffusion mask: the output and the three
    gradients, as max-abs error over the reference's max-abs value, beside
    the tiles the kernel chose. The reference holds a head's (T, T) scores
    in float32, so it goes a few heads of a batch row at a time where all
    at once would pass 1 GiB."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import (
        flash_causal_attention, flash_tiles, xla_causal_attention)

    shape = tuple(shape)
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, w = (jax.random.normal(key, shape, jnp.bfloat16)
                  for key in (kq, kk, kv, kw))

    def both(attn):
        """attn's output and three gradients under the loss sum(o * w), one
        compiled function for every call at one shape."""
        def run(q, k, v, w):
            def loss(q, k, v):
                return (attn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32)).sum()

            return (attn(q, k, v), *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

        return jax.jit(run)

    flash = both(functools.partial(flash_causal_attention, window=window, blocks=blocks,
                                   interpret=not on_tpu))(q, k, v, w)
    b, t, h, _ = shape
    at_once = max(1, min(h, (1 << 30) // (4 * t * t)))
    reference = both(functools.partial(xla_causal_attention, window=window, blocks=blocks))
    parts = [[reference(*(x[row:row + 1, :, first:first + at_once] for x in (q, k, v, w)))
              for first in range(0, h, at_once)] for row in range(b)]
    ref = [jnp.concatenate([jnp.concatenate([part[n] for part in row], axis=2) for row in parts])
           for n in range(4)]
    errs = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), flash, ref):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if a.shape != shape or not bool(jnp.isfinite(a).all()):
            raise RuntimeError(f"flash {name}: bad shape or non-finite values")
        errs[name] = float(jnp.abs(a - b).max() / jnp.abs(b).max())
    if max(errs.values()) > ATTN_REL_TOL:
        raise RuntimeError(f"flash vs xla at {shape}, window {window}, blocks {blocks}, "
                           f"beyond {ATTN_REL_TOL}: {errs}")
    return {"shape": list(shape), "window": window, "blocks": blocks, "rel_err": errs,
            "tiles": flash_tiles(h, t, shape[3], jnp.bfloat16, window, blocks=blocks)._asdict()}


def _check_ssd_vs_chunked(seed, on_tpu, groups=1):
    """ops/ssd.py's two kernels against the same chunked form in jax.numpy
    at a benchmark cell's widths (64 heads of 64, state 128; one group of B
    and C and chunks of 256, granite's, or eight groups and chunks of 128,
    nemotron's) on 1,024 positions, same seed: the output, the chunk states
    and the six gradients, as max-abs error over the reference's max-abs
    value."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssd

    b, t, h, p, n, chunk = ((1, 1024, 64, 64, 128, 256 if groups == 1 else 128) if on_tpu
                            else (1, 64, 4 * groups, 32, 16, 16))
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x, w = (jax.random.normal(k, (b, t, h, p), jnp.bfloat16) for k in ks[:2])
    bm, cm = (jax.random.normal(k, (b, t, groups, n), jnp.bfloat16) for k in ks[2:4])
    dt = jax.nn.softplus(jax.random.normal(ks[4], (b, t, h)) - 3.0)
    a = -jnp.exp(jax.random.uniform(ks[5], (h,), minval=0.0, maxval=2.77))
    d = jnp.ones((h,))

    def run(form):
        def loss(*args):
            y, states = form(*args)
            return (y.astype(jnp.float32) * w.astype(jnp.float32)).sum(), (y, states)

        grads, out = jax.jit(jax.grad(loss, argnums=range(6), has_aux=True))(x, dt, a, bm, cm, d)
        return (*out, *grads)

    kernels = run(lambda x, dt, a, bm, cm, d: ssd.ssd(
        x, dt, a, bm, cm, d, chunk, interpret=not on_tpu))
    chunked = run(lambda x, dt, a, bm, cm, d: ssd.ssd_chunked(
        x, dt, ssd.chunk_log_decay(dt, a, chunk), bm, cm, d, chunk))
    errs = {}
    for name, got, want in zip(("y", "states", "dx", "ddt", "dA", "dB", "dC", "dD"),
                               kernels, chunked):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        if got.shape != want.shape or not bool(jnp.isfinite(got).all()):
            raise RuntimeError(f"ssd {name}: bad shape or non-finite values")
        errs[name] = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    if max(errs.values()) > ATTN_REL_TOL:
        raise RuntimeError(f"ssd kernels vs chunked form beyond {ATTN_REL_TOL}: {errs}")
    return {"shape": [b, t, h, p, n], "groups": groups, "chunk": chunk, "rel_err": errs,
            "heads_a_slab_and_a_grid_step": list(ssd.head_tile(h, p, groups)),
            "ssd_path": ssd.ssd_path(t, h, p, groups, chunk)}


def _check_relu2_experts_vs_plain(seed, on_tpu):
    """ops/moe.py's `ExpertShare` with experts of two matrices under relu
    squared (`moe.RELU2`; on a TPU megablox's grouped matmuls at the
    benchmark's widths: a 2,688-wide stream, experts 1,856 wide, 8 held of
    128, top 6, on 4,096 tokens) against every token through every held
    expert in jax.numpy, weighted by the layer's own gates where chosen: the
    output and the gradients of the input and of both matrices, as max-abs
    error over the reference's max-abs value."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import RELU2, SIGMOID, ExpertShare

    d, ff, experts, held, k, tokens = ((2688, 1856, 128, 8, 6, 4096) if on_tpu
                                       else (64, 48, 8, 4, 2, 256))
    layer = ExpertShare(d, ff, experts, k, 0, held, router=SIGMOID, scaling=2.5, gate_eps=1e-20,
                        hand_up_choices=True, form=RELU2)
    kx, kp, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (1, tokens, d), jnp.bfloat16)
    w = jax.random.normal(kw, (1, tokens, d), jnp.float32)
    params = layer.init(kp, x)["params"]
    if sorted(params) != ["down", "expert_bias", "router", "up"]:
        raise RuntimeError(f"experts of two matrices hold {sorted(params)}")
    idx = layer.apply({"params": params}, x)[1]

    def plain(up, down, x):
        scores = jax.nn.sigmoid(x.astype(jnp.float32) @ params["router"]["kernel"])
        chosen = jnp.take_along_axis(scores, idx, -1)
        gates = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * 2.5
        y = jnp.zeros(x.shape, jnp.float32)
        for e in range(held):
            hidden = jnp.square(jax.nn.relu(jnp.dot(
                x, up[e].astype(x.dtype), preferred_element_type=jnp.float32)))
            out = jnp.dot(hidden.astype(x.dtype), down[e].astype(x.dtype),
                          preferred_element_type=jnp.float32)
            y = y + jnp.where(idx == e, gates, 0.0).sum(-1)[..., None] * out
        return y

    def run(form):
        def loss(up, down, x):
            y = form(up, down, x).astype(jnp.float32)
            return (y * w).sum(), y
        grads, y = jax.jit(jax.grad(loss, argnums=range(3), has_aux=True))(
            params["up"], params["down"], x)
        return (y, *grads)

    system = run(lambda up, down, x: layer.apply(
        {"params": {**params, "up": up, "down": down}}, x)[0])
    errs = {}
    for name, got, want in zip(("y", "d_up", "d_down", "dx"), system, run(plain)):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        if got.shape != want.shape or not bool(jnp.isfinite(got).all()):
            raise RuntimeError(f"relu2 experts {name}: bad shape or non-finite values")
        errs[name] = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    if max(errs.values()) > ATTN_REL_TOL:
        raise RuntimeError(f"relu2 experts vs the plain form beyond {ATTN_REL_TOL}: {errs}")
    return {"shape": [tokens, d, ff], "held_of": [held, experts], "top_k": k, "rel_err": errs}


def _check_gated_conv_vs_plain(seed, on_tpu):
    """ops/short_conv.py's two kernels against the plain sum of shifted
    slices at the benchmark's width (three streams of 2,048, 3 taps) on a
    quarter of its rows, same seed: the output and the gradients of the
    streams and of the taps, as max-abs error over the reference's max-abs
    value."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import short_conv

    b, t, d, k = (1, 4096, 2048, 3) if on_tpu else (2, 40, 128, 3)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    bcu = jax.random.normal(ks[0], (b, t, 3 * d), jnp.bfloat16)
    w = jax.random.uniform(ks[1], (k, d), jnp.float32, -0.6, 0.6)
    dy = jax.random.normal(ks[2], (b, t, d), jnp.bfloat16)

    def run(form):
        def loss(bcu, w):
            y = form(bcu, w)
            return (y.astype(jnp.float32) * dy.astype(jnp.float32)).sum(), y

        grads, y = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))(bcu, w)
        return (y, *grads)

    kernels = run(lambda bcu, w: short_conv.gated_short_conv(bcu, w, interpret=not on_tpu))
    plain = run(short_conv.gated_conv_plain)
    errs = {}
    for name, got, want in zip(("y", "d_bcu", "dw"), kernels, plain):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        if got.shape != want.shape or not bool(jnp.isfinite(got).all()):
            raise RuntimeError(f"gated conv {name}: bad shape or non-finite values")
        errs[name] = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    if max(errs.values()) > ATTN_REL_TOL:
        raise RuntimeError(f"gated conv kernels vs plain form beyond {ATTN_REL_TOL}: {errs}")
    return {"shape": [b, t, 3 * d], "taps": k, "rel_err": errs,
            "rows_a_tile": short_conv._tile(t), "conv_path": short_conv.conv_path(d, k)}


def _check_causal_conv_vs_plain(seed, on_tpu):
    """ops/short_conv.py's Mamba pair against the plain lines the mixer ran
    before it (the splits and `causal_conv_plain`) at the two cells' shapes,
    x where the cells have it (after 4,096 lanes of z, before 64 of dt) and y
    cut where they cut it (x | B | C), 4 taps, same seed: y and the gradients
    of x, the taps and the bias as max-abs error over the reference's max-abs
    value, and the neighbours' gradients, which pass through untouched; the
    same over the rows at the edges of the runs of rows alone (the first k-1
    of every run, which read the rows handed over, and the last k-1 before
    it, whose gradient reads them), where a lost hand-over would show and the
    whole array's maximum could hide it; and the ms of a forward call and of
    a forward and backward pair, each form."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import short_conv

    k, out = 4, []
    for b, t, at, c, more, cuts in (
            ((1, 4096, 4096, 4352, 64, (4096, 4224)), (2, 8192, 4096, 6144, 64, (4096, 5120)))
            if on_tpu else ((2, 40, 256, 256, 64, (128,)),)):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        wide = jax.random.normal(ks[0], (b, t, at + c + more), jnp.bfloat16)
        w = jax.random.uniform(ks[1], (k, c), jnp.float32, -0.5, 0.5)
        bias = 0.3 * jax.random.normal(ks[2], (c,), jnp.float32)
        d_out = jax.random.normal(ks[3], wide.shape, jnp.float32)
        cut = short_conv._cut(t, c, cuts)
        at_edge = np.flatnonzero((np.arange(t) % cut.rows < k - 1)
                                 | (np.arange(t) % cut.rows >= cut.rows - k + 1))

        def run(form):
            def loss(wide, w, bias):
                outs = jnp.concatenate(form(wide, w, bias), axis=-1)
                return (outs.astype(jnp.float32) * d_out).sum(), outs

            fwd = jax.jit(form)
            both = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))
            grads, outs = both(wide, w, bias)
            ms = {}
            for name, fn in (("fwd", fwd), ("fwd_and_bwd", both)):
                jax.block_until_ready(fn(wide, w, bias))
                t0 = time.perf_counter()
                jax.block_until_ready([fn(wide, w, bias) for _ in range(10)])
                ms[name] = 1e2 * (time.perf_counter() - t0)
            return (outs, *grads), ms

        def plain_lines(wide, w, bias):
            left, x, right = jnp.split(wide, [at, at + c], axis=-1)
            y = short_conv.causal_conv_plain(x, w, bias)
            return (left, *jnp.split(y, cuts, axis=-1), right)

        kernels, kernels_ms = run(lambda wide, w, bias: short_conv.causal_conv_within(
            wide, w, bias, at, cuts, interpret=None if on_tpu else True))
        plain, plain_ms = run(plain_lines)
        errs, edge_errs = {}, {}
        for name, got, want in zip(("y", "dx", "dw", "dbias"), kernels, plain):
            got, want = got.astype(jnp.float32), want.astype(jnp.float32)
            if got.shape != want.shape or not bool(jnp.isfinite(got).all()):
                raise RuntimeError(f"causal conv {name}: bad shape or non-finite values")
            if got.ndim == 3:
                beside = np.r_[0:at, at + c:at + c + more]
                if not bool((got[..., beside] == want[..., beside]).all()):
                    raise RuntimeError(f"causal conv {name}: the lanes beside x are not the plain "
                                       f"form's at {wide.shape}")
                got, want = got[..., at:at + c], want[..., at:at + c]
                edge_errs[name] = float(jnp.abs(got - want)[:, at_edge].max()
                                        / jnp.abs(want)[:, at_edge].max())
            errs[name] = float(jnp.abs(got - want).max() / jnp.abs(want).max())
        if max(*errs.values(), *edge_errs.values()) > ATTN_REL_TOL:
            raise RuntimeError(f"causal conv kernels vs the plain lines at {wide.shape} beyond "
                               f"{ATTN_REL_TOL}: {errs}, at the edges {edge_errs}")
        out.append({"shape": list(wide.shape), "x_at": [at, at + c], "y_cut_at": list(cuts),
                    "taps": k, "rel_err": errs, "rel_err_at_edges": edge_errs,
                    "cut": cut._asdict(), "kernels_ms": kernels_ms, "plain_ms": plain_ms,
                    "conv_path": short_conv.conv_path(c, k, short_conv._EDGE)})
    return out


def _check_gated_norm_vs_plain(seed, on_tpu):
    """ops/gated_norm.py's pair against the lines the mixer ran before it
    (`gated_norm_plain`: the gate in bf16, the norm over a (..., 8, 512) view)
    at nemotron3_nano_l9_ep16.t8192's shape, y (2, 8192, 4096) in 8 groups and
    z where the cell has it (the first 4,096 lanes of [z | xBC | dt]), same
    seed, the first group's values a thousand times the last's: the output
    and the gradients of y, z and the weight as max-abs error over the
    reference's max-abs value, the first and the last group each on its own
    (a sum that leaked across a group's edge would show in the small one)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import gated_norm

    b, t, c, more, groups = (2, 8192, 4096, 6208, 8) if on_tpu else (2, 40, 256, 192, 2)
    eps, width = 1e-5, c // groups
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    scale = jnp.repeat(jnp.logspace(1.5, -1.5, groups), width)
    y = (jax.random.normal(ks[0], (b, t, c)) * scale).astype(jnp.bfloat16)
    wide = jax.random.normal(ks[1], (b, t, c + more), jnp.bfloat16)
    weight = 1 + 0.1 * jax.random.normal(ks[2], (c,), jnp.float32)
    d_out = jax.random.normal(ks[3], (b, t, c), jnp.float32)

    def run(form):
        def loss(y, wide, weight):
            out = form(y, wide[..., :c], weight, wide)
            return (out.astype(jnp.float32) * d_out).sum(), out

        grads, out = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))(y, wide, weight)
        return (out, *grads)

    kernels = run(lambda y, z, weight, wide: gated_norm.gated_norm(
        y, z, weight, eps, groups, within=(wide, 0), interpret=None if on_tpu else True))
    plain = run(lambda y, z, weight, wide: gated_norm.gated_norm_plain(
        y, z, weight, eps, groups))
    errs = {}
    for name, got, want in zip(("out", "dy", "dz", "dweight"), kernels, plain):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        if got.shape != want.shape or not bool(jnp.isfinite(got).all()):
            raise RuntimeError(f"gated norm {name}: bad shape or non-finite values")
        if name == "dz":
            if bool(got[..., c:].any()):
                raise RuntimeError("gated norm: a gradient in the lanes beside z")
            got, want = got[..., :c], want[..., :c]
        for part, at in (("first", slice(0, width)), ("last", slice(c - width, c))):
            errs[f"{name}_{part}_group"] = float(
                jnp.abs(got[..., at] - want[..., at]).max() / jnp.abs(want[..., at]).max())
    if max(errs.values()) > ATTN_REL_TOL:
        raise RuntimeError(f"gated norm kernels vs the plain lines at {y.shape} beyond "
                           f"{ATTN_REL_TOL}: {errs}")
    return {"shape": list(y.shape), "groups": groups, "z_within": list(wide.shape),
            "rel_err": errs, "norm_path": gated_norm.norm_path(width)}


def _check_qk_prep_vs_plain(seed, on_tpu):
    """ops/qk_prep.py's pair against `LlamaAttention`'s plain lines (the norm
    a head, rotate-half, the repeat of the key-value heads and `_as_rows`) in
    float32 at `highest`, at the three engaged cells' shapes, q (32 heads)
    and k (4 heads to 32 rows), normed and not, same seed: the rows and the
    gradients of x and the weight as max-abs error over the reference's
    max-abs value."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.layers import apply_rope, rms_norm, rope_angles
    from ray_tpu.ops import attention, qk_prep

    eps, out = 1e-6, []
    shapes = ((2, 8192), (1, 16384)) if on_tpu else ((2, 40),)
    for (b, t), (heads, rep), norm in ((s, h, n) for s in shapes for h in ((32, 1), (4, 8))
                                       for n in (True, False)):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        x = jax.random.normal(ks[0], (b, t, heads * 128), jnp.bfloat16)
        weight = 1 + 0.1 * jax.random.normal(ks[1], (128,), jnp.float32)
        d_rows = jax.random.normal(ks[2], (b * heads * rep, t, 128), jnp.float32)
        ang = rope_angles(128, 10000.0, jnp.arange(t))

        def plain(x, weight):
            q = x.astype(jnp.float32).reshape(b, t, heads, 128)
            q = apply_rope(rms_norm(q, weight, eps) if norm else q, ang)
            return attention._as_rows(jnp.broadcast_to(
                q[:, :, :, None, :], (b, t, heads, rep, 128)).reshape(b, t, heads * rep, 128))

        def kernels(x, weight):
            return qk_prep.qk_prep(x, weight if norm else None, qk_prep.rope_tables(ang), rep=rep,
                                   eps=eps, interpret=not on_tpu)

        def run(form):
            def loss(x, weight):
                rows = form(x, weight)
                return (rows.astype(jnp.float32) * d_rows).sum(), rows

            with jax.default_matmul_precision("highest"):
                grads, rows = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))(x, weight)
            return (rows, *grads)

        errs = {}
        for name, got, want in zip(("rows", "dx", "dweight"), run(kernels), run(plain)):
            got, want = got.astype(jnp.float32), want.astype(jnp.float32)
            if got.shape != want.shape or not bool(jnp.isfinite(got).all()):
                raise RuntimeError(f"qk_prep {name}: bad shape or non-finite values")
            if norm or name != "dweight":
                errs[name] = float(jnp.abs(got - want).max() / jnp.abs(want).max())
        if max(errs.values()) > ATTN_REL_TOL:
            raise RuntimeError(f"qk_prep kernels vs the plain lines at {x.shape}, rep {rep}, "
                               f"norm {norm} beyond {ATTN_REL_TOL}: {errs}")
        out.append({"shape": list(x.shape), "rep": rep, "norm": norm, "rel_err": errs})
    return out


def _check_flash_mla_vs_plain(seed, on_tpu):
    """ops/attention.py's latent pair against the plain form in float32 at
    the benchmark's head widths (32 heads, scores 128 + 64 deep, values 128)
    on a quarter of a row of its tokens, same seed: the output and the five
    gradients (q's two parts, the heads' own keys, the key all heads share,
    the values), as max-abs error over the reference's max-abs value; and
    the tiles the rule gives the cell's own call."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention

    b, t, h, d, r = (1, 2048, 32, 128, 64) if on_tpu else (1, 128, 2, 128, 64)
    shapes = ((b, t, h, d), (b, t, h, r), (b, t, h, d), (b, t, r), (b, t, h, d), (b, t, h, d))
    *ops, do = (jax.random.normal(k, s, jnp.bfloat16)
                for k, s in zip(jax.random.split(jax.random.PRNGKey(seed), 6), shapes))

    def run(attn, ops):
        def loss(*ops):
            o = attn(*ops)
            return (o.astype(jnp.float32) * do.astype(jnp.float32)).sum(), o

        grads, o = jax.jit(jax.grad(loss, argnums=range(5), has_aux=True))(*ops)
        return (o, *grads)

    kernels = run(lambda *a: attention.flash_latent_attention(*a, interpret=not on_tpu), ops)
    with jax.default_matmul_precision("highest"):
        plain = run(attention.xla_latent_attention, [x.astype(jnp.float32) for x in ops])
    errs = {}
    for name, got, want in zip(("o", "dq", "dq_shared", "dk", "dk_shared", "dv"), kernels, plain):
        got = got.astype(jnp.float32)
        if got.shape != want.shape or not bool(jnp.isfinite(got).all()):
            raise RuntimeError(f"latent attention {name}: bad shape or non-finite values")
        errs[name] = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    if max(errs.values()) > ATTN_REL_TOL:
        raise RuntimeError(f"latent pair vs plain form beyond {ATTN_REL_TOL}: {errs}")
    return {"shape": [b, t, h, d, r], "rel_err": errs,
            "tiles_of_the_cell": list(attention.flash_tiles(32, 8192, d, jnp.bfloat16, shared=r))}


def _check_latent_layer_vs_plain(seed, on_tpu):
    """models/layers.py's `LatentAttention` in bf16 (its projections cut on
    their weights, ops/attention.py's latent pair between them) at the
    benchmark cell's shape, (2, 8192, 2048) through 32 heads, with the rotary
    (kanana's layers) and without (kimi_linear's one), against the plain lines
    of bench/families/kanana.py and kimi_linear.py in float32 at `highest`
    precision on the same leaves, same seed: the output and the gradients of
    the input and of the five leaves, as max-abs error over the reference's
    max-abs value."""
    import jax
    import jax.numpy as jnp

    from bench import families
    from ray_tpu.models.layers import LatentAttention

    root = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(root, "bench", "configs", "kanana2_30b_l5_ep8.json")) as f:
        sizes = json.load(f)
    if not on_tpu:
        sizes.update(sizes["rehearsal"])
    kanana = families.load("kanana")
    plain = {True: kanana._attention, False: families.load("kimi_linear")._latent}
    cfg = kanana.build(sizes, "bfloat16")
    b, t = (2, 8192) if on_tpu else (1, 128)
    kx, kp, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (b, t, cfg.n_embd), jnp.bfloat16)
    w = jax.random.normal(kw, (b, t, cfg.n_embd), jnp.float32)

    def run(form, params, x):
        def loss(params, x):
            y = form(params, x).astype(jnp.float32)
            return (y * w).sum(), y

        (d_params, dx), y = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))(params, x)
        return {"y": y, "dx": dx, **{f"d_{name}": leaf for name, leaves in d_params.items()
                                     for leaf in leaves.values()}}  # a leaf a name

    report = {"shape": [b, t, cfg.n_embd]}
    for rotary in (True, False):
        layer = LatentAttention(cfg, rotary=rotary)
        params = jax.tree.map(  # the norm's weight off one; scores far enough from flat
            lambda p: p + 0.05 * jax.random.normal(kw, p.shape) if p.ndim == 1 else 2.0 * p,
            layer.init(kp, x)["params"])
        system = run(lambda params, x: layer.apply({"params": params}, x), params, x)
        with jax.default_matmul_precision("highest"):
            want = run(lambda params, x: plain[rotary](x, params, sizes),
                       params, x.astype(jnp.float32))
        errs = {}
        for name, ref in want.items():
            got = system[name].astype(jnp.float32)
            if got.shape != ref.shape or not bool(jnp.isfinite(got).all()):
                raise RuntimeError(f"latent layer {name}: bad shape or non-finite values")
            errs[name] = float(jnp.abs(got - ref).max() / jnp.abs(ref).max())
        if max(errs.values()) > LAYER_REL_TOL:
            raise RuntimeError(f"latent layer (rotary {rotary}) vs the plain lines beyond "
                               f"{LAYER_REL_TOL}: {errs}")
        report["rotary" if rotary else "no_rotary"] = errs
    return report


def _rule_outputs_and_grads(form, ops, w):
    """(o, the last state, the seven gradients) of a delta rule's `form` on
    its seven operands under the loss sum(o * w): one compiled function."""
    import jax
    import jax.numpy as jnp

    def loss(*ops):
        o, last = form(*ops)
        return (o.astype(jnp.float32) * w.astype(jnp.float32)).sum(), (o, last)

    grads, out = jax.jit(jax.grad(loss, argnums=range(7), has_aux=True))(*ops)
    return (*out, *grads)


def _rule_errors(rule, names, got, want, what):
    """Each of `names` as max-abs error over the reference's max-abs value;
    raises past ATTN_REL_TOL or on a bad shape or a non-finite value."""
    import jax.numpy as jnp

    errs = {}
    for name, g, r in zip(names, got, want):
        g = g.astype(jnp.float32)
        if g.shape != r.shape or not bool(jnp.isfinite(g).all()):
            raise RuntimeError(f"{rule} {what} {name}: bad shape or non-finite values")
        errs[name] = float(jnp.abs(g - r).max() / jnp.abs(r).max())
    if max(errs.values()) > ATTN_REL_TOL:
        raise RuntimeError(f"{rule} {what} vs the recurrence beyond {ATTN_REL_TOL}: {errs}")
    return errs


def _check_kda_vs_plain(seed, on_tpu):
    """ops/kda.py's two kernels (bf16 operands, q and k as a convolution
    leaves them: the heads' l2 norms and the gate made inside the kernels)
    against the recurrence step by step in float32 at `highest` matmul
    precision on operands normed in float32, at the benchmark cell's shape
    (2, 8192, 32, 128), same seed: the output, the state after the last token
    and the seven gradients (q, k, v, the gate's pre-activation, A_log,
    dt_bias, beta), as max-abs error over the reference's max-abs value; and
    the same for the chunked form in jax.numpy on an eighth of the tokens
    (what a backend without the kernels runs, the norms before it)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.kimi_linear import L2_EPS
    from ray_tpu.ops import kda

    b, t, h, d = (2, 8192, 32, 128) if on_tpu else (1, 128, 2, 128)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    # silu of a unit normal, as the convolution's output: lengths of 4 to 7 a head
    q, k = (jax.nn.silu(jax.random.normal(key, (b, t, h, d))).astype(jnp.bfloat16)
            for key in ks[:2])
    v, f, w = (jax.random.normal(key, (b, t, h, d), jnp.bfloat16) for key in ks[2:5])
    a_log = jnp.log(jax.random.uniform(ks[5], (h,), minval=1.0, maxval=16.0))
    dt = jnp.exp(jax.random.uniform(ks[6], (h, d), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    beta = jax.nn.sigmoid(jax.random.normal(ks[7], (b, t, h)))
    ops = (q, k, v, f, a_log, dt_bias, beta)

    run = _rule_outputs_and_grads

    def gated(interpret):
        def form(*ops):
            o, _, last = kda.kda_gated(*ops, l2_eps=L2_EPS, interpret=interpret)
            return o.reshape(ops[2].shape), last.swapaxes(-1, -2)  # o comes (b, T, H x 128)
        return form

    def plain(q, k, v, f, a_log, dt_bias, beta):
        return kda.kda_plain(kda.l2norm(q, L2_EPS), kda.l2norm(k, L2_EPS), v,
                             kda.gate_log_decay(f, a_log, dt_bias), beta)

    def chunked(q, k, v, f, a_log, dt_bias, beta):
        o, _, last = kda.kda_chunked(kda.l2norm(q, L2_EPS), kda.l2norm(k, L2_EPS), v,
                                     kda.gate_log_decay(f, a_log, dt_bias), beta, kda.CHUNK)
        return o, last.swapaxes(-1, -2)

    names = ("o", "state", "dq", "dk", "dv", "df", "dA_log", "ddt_bias", "dbeta")

    errors = functools.partial(_rule_errors, "kda", names)

    as_f32 = lambda ops: [x.astype(jnp.float32) for x in ops]
    want = run(plain, as_f32(ops), w)
    report = {"shape": [b, t, h, d], "chunk": kda.CHUNK, "kda_path": kda.kda_path(t, d, d),
              "rel_err": errors(run(gated(not on_tpu), ops, w), want, "kernels")}
    n = max(t // 8, kda.CHUNK)
    part = [x[:, :n] if x.ndim > 2 else x for x in ops]
    report["rel_err_chunked_form"] = errors(
        run(chunked, part, w[:, :n]),
        run(plain, as_f32(part), w[:, :n]), "chunked form")
    return report


def _check_gdn_vs_plain(seed, on_tpu):
    """ops/gdn.py's two kernels (bf16 operands, q and k as a convolution
    leaves them: the heads' l2 norms made inside the kernels; the gate's
    softplus and beta's sigmoid the layer's lines before them) against the
    recurrence step by step in float32 at `highest` matmul precision on
    operands normed in float32, at the benchmark cell's head sizes, (2, 8192)
    tokens, 16 key heads and 32 value heads of 128, a decay drawn as
    models/qwen3_next.py's `a_log_init` draws A_log (dt_bias ones), same
    seed: the output, the state after the last token and the seven gradients
    (q, k, v, a, A_log, dt_bias, b), as max-abs error over the reference's
    max-abs value; and the same for the chunked form in jax.numpy on an
    eighth of the tokens (what a backend without the kernels runs)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.qwen3_next import L2_EPS, a_log_init
    from ray_tpu.ops import gdn, kda

    b, t, hk, hv, d = (2, 8192, 16, 32, 128) if on_tpu else (1, 128, 1, 2, 128)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    q, k = (jax.nn.silu(jax.random.normal(key, (b, t, hk, d))).astype(jnp.bfloat16)
            for key in ks[:2])
    v, w = (jax.random.normal(key, (b, t, hv, d), jnp.bfloat16) for key in ks[2:4])
    a, pre_beta = (jax.random.normal(key, (b, t, hv)) for key in ks[4:6])
    a_log, dt_bias = a_log_init(ks[6], (hv,)), jnp.ones((hv,), jnp.float32)
    ops = (q, k, v, a, a_log, dt_bias, pre_beta)

    run = _rule_outputs_and_grads

    def rates(a, a_log, dt_bias, pre_beta):
        return gdn.gate_log_decay(a, a_log, dt_bias), jax.nn.sigmoid(pre_beta)

    def kernels(interpret):
        def form(q, k, v, *rest):
            o, _, last = gdn.gdn(q, k, v, *rates(*rest), l2_eps=L2_EPS, interpret=interpret)
            return o.reshape(v.shape), last.swapaxes(-1, -2)  # o comes (b, T, Hv x 128)
        return form

    def plain(q, k, v, *rest):
        return gdn.gdn_plain(kda.l2norm(q, L2_EPS), kda.l2norm(k, L2_EPS), v, *rates(*rest))

    def chunked(q, k, v, *rest):
        o, _, last = gdn.gdn(q, k, v, *rates(*rest), l2_eps=L2_EPS)
        return o.reshape(v.shape), last.swapaxes(-1, -2)

    names = ("o", "state", "dq", "dk", "dv", "da", "dA_log", "ddt_bias", "db")

    errors = functools.partial(_rule_errors, "gdn", names)

    as_f32 = lambda ops: [x.astype(jnp.float32) for x in ops]
    want = run(plain, as_f32(ops), w)
    report = {"shape": [b, t, hk, hv, d], "chunk": gdn.CHUNK, "gdn_path": gdn.gdn_path(t, d, d),
              "decay_mean": float(jnp.exp(rates(*ops[3:])[0]).mean()),
              "rel_err": errors(run(kernels(not on_tpu), ops, w), want, "kernels")}
    if not on_tpu:  # where the kernels run `gdn.gdn` is the kernels: the chunked form is a CPU's
        n = max(t // 8, gdn.CHUNK)
        part = [x[:, :n] if x.ndim > 2 else x for x in ops]
        report["rel_err_chunked_form"] = errors(
            run(chunked, part, w[:, :n]), run(plain, as_f32(part), w[:, :n]), "chunked form")
    return report


def _check_kda_norm_vs_plain(seed, on_tpu):
    """ops/kda_norm.py's pair (bf16 o and z, the float32 weight of one head)
    against the mixer's plain lines in float32 (`kda_norm_plain`: RMSNorm
    over a (..., 32, 128) view, the weight, the sigmoid gate) at
    kimi_linear_l5_ep32.t8192's shape, o and z (2, 8192, 32 x 128), same
    seed, the first head's values a thousand times the last's: y and the
    gradients of o, z and the weight as max-abs error over the reference's
    max-abs value, the first and the last head each on its own beside the
    whole (a sum that leaked across a head's edge would show in the small
    one)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import kda_norm

    b, t, h, d = (2, 8192, 32, 128) if on_tpu else (2, 40, 4, 128)
    eps = 1e-5
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    scale = jnp.repeat(jnp.logspace(1.5, -1.5, h), d)
    o = (jax.random.normal(ks[0], (b, t, h * d)) * scale).astype(jnp.bfloat16)
    z = (2 * jax.random.normal(ks[1], (b, t, h * d))).astype(jnp.bfloat16)
    weight = 1 + 0.1 * jax.random.normal(ks[2], (d,), jnp.float32)
    dy = jax.random.normal(ks[3], (b, t, h * d), jnp.float32)

    def run(form, o, z):
        def loss(o, z, weight):
            y = form(o, z, weight)
            return (y.astype(jnp.float32) * dy).sum(), y

        grads, y = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))(o, z, weight)
        return (y, *grads)

    kernels = run(lambda o, z, w: kda_norm.kda_norm(
        o, z, w, eps, interpret=None if on_tpu else True), o, z)
    plain = run(lambda o, z, w: kda_norm.kda_norm_plain(o, z, w, eps),
                o.astype(jnp.float32), z.astype(jnp.float32))
    errs = {}
    for name, got, want in zip(("y", "do", "dz", "dweight"), kernels, plain):
        got = got.astype(jnp.float32)
        if got.shape != want.shape or not bool(jnp.isfinite(got).all()):
            raise RuntimeError(f"kda norm {name}: bad shape or non-finite values")
        errs[name] = float(jnp.abs(got - want).max() / jnp.abs(want).max())
        if name != "dweight":
            for part, at in (("first", slice(0, d)), ("last", slice((h - 1) * d, h * d))):
                errs[f"{name}_{part}_head"] = float(
                    jnp.abs(got[..., at] - want[..., at]).max() / jnp.abs(want[..., at]).max())
    if max(errs.values()) > ATTN_REL_TOL:
        raise RuntimeError(f"kda norm kernels vs the plain lines at {o.shape} beyond "
                           f"{ATTN_REL_TOL}: {errs}")
    return {"shape": list(o.shape), "heads": h, "rel_err": errs,
            "norm_path": kda_norm.norm_path(d)}


def _check_sscan_vs_recurrence(seed, on_tpu):
    """ops/selective_scan.py's two kernels (bf16 u, B and C, float32 steps)
    against the recurrence step by step in float32 on the same operands, at
    the benchmark cell's widths (5,120 channels of 16 states) over 1,024
    positions, eight chunks, with steps and rates as the model's
    initialisation gives them (states carried across every chunk), same
    seed: the output, the chunk states and the six gradients, as max-abs
    error over the reference's max-abs value. This holds what the cell's
    scalar loss averages away (a lost carry, decays in bf16: PERF.md section
    7)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import selective_scan as ss

    b, t, c, n = (1, 1024, 5120, 16) if on_tpu else (1, 256, 128, 16)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    u, w = (jax.random.normal(k, (b, t, c), jnp.bfloat16) for k in ks[:2])
    bm, cm = (jax.random.normal(k, (b, t, n), jnp.bfloat16) for k in ks[2:4])
    delta = jnp.exp(jax.random.uniform(ks[4], (b, t, c), minval=jnp.log(1e-3),
                                       maxval=jnp.log(1e-1)))
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (c, n))
    d = 1 + 0.1 * jax.random.normal(ks[5], (c,))

    def run(form, ops):
        def loss(*ops):
            y, states = form(*ops)
            return (y.astype(jnp.float32) * w.astype(jnp.float32)).sum(), (y, states)

        grads, out = jax.jit(jax.grad(loss, argnums=range(6), has_aux=True))(*ops)
        return (*out, *grads)

    ops = (u, delta, a, bm, cm, d)
    kernels = run(lambda *ops: ss.selective_scan(*ops, interpret=not on_tpu), ops)
    plain = run(ss.selective_scan_plain, [x.astype(jnp.float32) for x in ops])
    errs = {}
    for name, got, want in zip(("y", "states", "du", "ddelta", "dA", "dB", "dC", "dD"),
                               kernels, plain):
        got = got.astype(jnp.float32)
        if got.shape != want.shape or not bool(jnp.isfinite(got).all()):
            raise RuntimeError(f"sscan {name}: bad shape or non-finite values")
        errs[name] = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    if max(errs.values()) > ATTN_REL_TOL:
        raise RuntimeError(f"sscan kernels vs the recurrence beyond {ATTN_REL_TOL}: {errs}")
    return {"shape": [b, t, c, n], "chunk": ss.chunk_of(t), "rel_err": errs,
            "scan_path": ss.scan_path(t, c, n)}


def _check_gated_attention(seed, on_tpu):
    """models/llama.py's `LlamaAttention` as models/afmoe.py's blocks tell it
    (a norm over each head of q and k, the output gated by sigmoid(W_g x)),
    both kinds: under a window of 2,048 keys with rotary, and full with no
    position. On a TPU at the benchmark's cell, (2, 8192) tokens, 32 query
    and 4 key-value heads of 128, bf16 through the flash pairs, against
    bench/families/afmoe.py's plain form in float32 at `highest` precision on
    the same float32 weights: the output and the input's gradient, as
    max-abs error over the reference's max-abs value. Then one step of the
    configuration's rehearsal model through `TrainStep`: a finite loss, the
    gate's mean near one half."""
    import jax
    import jax.numpy as jnp

    from bench import families
    from ray_tpu.models.layers import LlamaAttention
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.train_step import TrainStep

    root = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(root, "bench", "configs", "trinity_mini_l5_ep16.json")) as f:
        sizes = json.load(f)
    rehearsal = {**sizes, **sizes["rehearsal"]}
    family = families.load(sizes["family"])
    b, t = 2, 8192
    if not on_tpu:
        sizes, t = rehearsal, 128
    cfg = family.build(sizes, "bfloat16")
    kx, kw, kp = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (b, t, cfg.n_embd), jnp.bfloat16)
    w = jax.random.normal(kw, (b, t, cfg.n_embd), jnp.float32)
    report = {"shape": [b, t, cfg.n_head, cfg.n_kv_head, cfg.head_dim], "rel_err": {}}
    with open(os.path.join(root, "bench", "configs", "qwen3_next_80b_l5_ep32.json")) as f:
        wide = json.load(f)  # heads of 256, a rotary over a head's first 64: its one full layer
    if not on_tpu:
        wide = {**wide, **wide["rehearsal"]}
    wide_family = families.load(wide["family"])
    wide_cfg = wide_family.build(wide, "bfloat16")
    report["shape_partial_rotary_256"] = [b, t, wide_cfg.n_head, wide_cfg.n_kv_head,
                                          wide_cfg.head_dim, wide_cfg.rotary_dim]
    for kind, sliding in (("sliding", True), ("full", False), ("partial_rotary_256", None)):
        if sliding is None:
            layer = LlamaAttention(wide_cfg, qk_norm=True, gate=True,
                                   rotary_dim=wide_cfg.rotary_dim)
            x, w = (a[..., :wide_cfg.n_embd] for a in (x, w))
            plain_form = lambda x, params: wide_family._gated_attention(x, params, wide)
        else:
            layer = LlamaAttention(cfg, window=cfg.sliding_window if sliding else None,
                                   qk_norm=True, rotary=sliding, gate=True)
            plain_form = lambda x, params, sliding=sliding: family._attention(
                x, params, sizes, sliding)
        params = layer.init(kp, x)["params"]
        params["wg"]["kernel"] = 4.0 * params["wg"]["kernel"]  # gates off one half

        def run(attn, x):
            out, pull = jax.vjp(attn, x)
            return out, pull(w.astype(out.dtype))[0]

        got = jax.jit(lambda x: run(lambda x: layer.apply({"params": params}, x), x))(x)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda x: run(
                lambda x: plain_form(x, params), x))(x.astype(jnp.float32))
        for name, a, ref in zip(("out", "dx"), got, want):
            a = a.astype(jnp.float32)
            if a.shape != ref.shape or not bool(jnp.isfinite(a).all()):
                raise RuntimeError(f"gated attention ({kind}) {name}: bad shape or non-finite values")
            report["rel_err"][f"{kind}_{name}"] = float(jnp.abs(a - ref).max() / jnp.abs(ref).max())
    if max(report["rel_err"].values()) > ATTN_REL_TOL:
        raise RuntimeError(f"gated attention vs float32 beyond {ATTN_REL_TOL}: {report}")
    ts = TrainStep(family.build(rehearsal, "bfloat16"),
                   make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
    idx = jax.random.randint(kx, (2, 129), 0, rehearsal["vocab_size"])
    _, m = ts.step(ts.init(kp), ts.shard_batch({"idx": idx[:, :-1], "targets": idx[:, 1:]}))
    report["rehearsal_step"] = {k: float(m[k]) for k in ("loss", "grad_norm", "attn_gate_mean")}
    if not (math.isfinite(report["rehearsal_step"]["loss"])
            and abs(report["rehearsal_step"]["attn_gate_mean"] - 0.5) < 0.05):
        raise RuntimeError(f"one step of the rehearsal model: {report['rehearsal_step']}")
    return report


def _windowed_flash_plan():
    """The tiles of the windowed flash calls of the benchmark's window layers,
    (2 x 32, 8192, 128) under windows of 1,024 and of 2,048, the sub-tiles
    their masked tiles are cut into and the scores a query can see over
    those a head's call computes (`flash_scores`), beside the causal call's
    at that shape, and the path `causal_attention` takes there."""
    import jax.numpy as jnp

    from ray_tpu.ops.attention import attention_path, flash_tiles

    h, t, d = 32, 8192, 128
    plan = {"shape": [2 * h, t, d], "attention_path": attention_path(t)}
    for name, window in (("causal", None), ("window_1024", 1024), ("window_2048", 2048)):
        tiles = flash_tiles(h, t, d, jnp.bfloat16, window)
        plan[name] = {"tiles": tiles._asdict(), **_needed_shares(tiles, t)}
    return plan


def _selected_flash_plan():
    """The tiles of the selected flash call of the benchmark's indexed layers,
    (1 x 32, 16384, 128) over 2,048 keys a query, the words a row of its
    packed mask has, and the rows a grid step of index_select takes with the
    columns a step of its passes' loops does."""
    import jax.numpy as jnp

    from ray_tpu.ops import indexer
    from ray_tpu.ops.attention import flash_tiles

    h, t, d, top_k = 32, 16384, 128, 2048
    return {"shape": [h, t, d], "select": top_k,
            "tiles": flash_tiles(h, t, d, jnp.bfloat16, select=top_k)._asdict(),
            "mask_width": indexer.mask_width(t), "index_select_rows": indexer._select_block(t),
            "index_select_chunk": indexer._select_chunk(t)}


def _check_selection(seed, on_tpu):
    """index_select at the benchmark's shape, (1, 16384, 16384) and 2,048
    keys a query, on scores that index_scores makes from seeded operands (16
    heads of 64): the packed mask of a few blocks of rows (the first, the two
    either side of row top_k, the middle, the last) against a `lax.top_k`
    selection of those rows, equal in every bit, and the compare-and-count
    passes each block ran."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import indexer

    t, top_k, heads, dim = (16384, 2048, 16, 64) if on_tpu else (512, 64, 4, 16)
    kq, kk, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    scores = indexer.index_scores(
        jax.random.normal(kq, (1, t, heads, dim), jnp.bfloat16),
        jax.random.normal(kk, (1, t, dim), jnp.bfloat16),
        jax.random.normal(kw, (1, t, heads), jnp.bfloat16), interpret=not on_tpu)
    mask, passes = indexer.index_select(scores, top_k, interpret=not on_tpu)
    rows, width = indexer._select_block(t), indexer.mask_width(t)

    @jax.jit
    def by_top_k(block, first_row):
        row = first_row + jnp.arange(rows)[:, None]
        seen = jnp.where(jnp.arange(t)[None, :] <= row, block + 0.0, -jnp.inf)  # -0.0 is 0.0
        best = jax.lax.top_k(seen, top_k)[1]  # equal scores: the lower position first
        wanted = jnp.arange(top_k)[None, :] < jnp.minimum(row + 1, top_k)
        chosen = jnp.zeros((rows, t), bool).at[jnp.arange(rows)[:, None], best].max(wanted)
        return indexer._pack(chosen, width)

    n_blocks = t // rows
    checked = sorted({0, max(top_k // rows - 1, 0), min(top_k // rows, n_blocks - 1),
                      n_blocks // 2, n_blocks - 1})
    for i in checked:
        want = by_top_k(scores[0, i * rows:(i + 1) * rows], i * rows)
        if not bool((mask[0, i * rows:(i + 1) * rows] == want).all()):
            raise RuntimeError(f"index_select differs from lax.top_k in rows {i * rows}..")
    passes = [int(n) for n in passes[0]]
    return {"shape": [1, t, t], "top_k": top_k, "rows_a_block": rows, "blocks_checked": checked,
            "equal_in_every_bit": True, "passes_by_block": passes,
            "passes_mean": sum(passes) / len(passes)}


def _cells():
    """(name, model configuration, one chip's StepShape) of each benchmark cell."""
    from bench import families
    from ray_tpu.models import remat

    root = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    for cell in cells:
        with open(os.path.join(root, "bench", "configs", cell["config"] + ".json")) as f:
            sizes = json.load(f)
        with open(os.path.join(root, "bench", "traffic", cell["traffic"] + ".json")) as f:
            mix = json.load(f)
        cfg = families.load(sizes["family"]).build(sizes, sizes["compute_dtype"])
        yield cell["name"], cfg, remat.step_shape((mix["batch"], mix["seq_len"]), sizes["mesh"])


def _remat_plans():
    """What each benchmark cell's step would save across its blocks' remat on
    this chip (models/remat.py): the rule is a pure function of the cell's
    shapes and the chip's bytes_limit, so one chip can say it for a cell on
    four."""
    import importlib

    from ray_tpu.models import remat

    limit = remat.chip_limit(None)
    plans = {}
    for name, cfg, shape in _cells():
        family = importlib.import_module(type(cfg).__module__)  # REMAT_RUNGS' own file
        plans[name] = {"shape": shape._asdict(), **family.remat_plan(cfg, shape, limit)._asdict()}
    return plans


# The cells whose attention layers take ops/qk_prep.py's pair as
# `_flash_calls_by_cell` lowers them, and how many of their layers do
# (`LlamaAttention.__call__` says when): every other cell 0. The four-chip
# cell is lowered there on one device, where no `attn_fn` stands and its eight
# rotary layers take the pair; under its own mesh the layer is handed
# `attn_fn` and runs the plain lines (tests/test_mellum.py pins that step).
QK_PREP_LAYERS = {"mellum2_12b_l4_ep4.t8192": 4, "keye_vl2_30b_l4_ep8.t16384": 4,
                  "trinity_mini_l5_ep16.t8192": 5, "mistral_7b_l8.fsdp4_t8192": 8,
                  "sdar_30b_a3b_l5_ep8.t8192": 5}


def _flash_calls_by_cell(on_tpu):
    """The pallas calls of each benchmark cell's own step by name, lowered
    here from shapes alone (nothing is placed on the chip; a cell on four
    chips as one chip's rows on one device: the kernels see the same
    operands). On a TPU every cell runs the flash path, and there each
    forward call has one backward call beside it, `...bwd_fused`, and none
    is `...bwd_dq` or `...bwd_dkv`; a layer that is a scan over a state
    (`mamba` in the configuration's `layer_types`) has ssd_bwd once in place
    of the flash pair, and ssd_fwd once where the step's remat plan saves
    the scan's outputs (`ssm_y`: in as many of those layers as the rung's
    depth says, models/remat.py, and so for every name below) and twice where
    it does not; a layer that is
    a gated short convolution (`conv`) has gated_conv_bwd once, and
    gated_conv_fwd once where the plan saves its output (`conv_y`), else
    twice; a layer that is an expert layer alone (`experts`) has none. A
    `mamba` layer's convolution (ops/short_conv.py) has causal_conv_bwd once
    and causal_conv_fwd twice: no plan names its output. A `mamba` layer of
    more than one group norms its gated output a group at a time
    (ops/gated_norm.py): gated_norm_bwd once and gated_norm_fwd twice. A
    layer that is a delta rule over a state (`kda`) has kda_bwd once, kda_fwd
    once where the plan saves the chunk states (`kda_states`), else twice,
    the head norm's pair after it (ops/kda_norm.py: kda_norm_bwd once and
    kda_norm_fwd twice, no plan names its output) and the convolution's pair
    as a `mamba` layer's; a `linear_attention` layer (models/qwen3_next.py:
    the delta rule under one decay a head and step) the same with gdn_fwd
    and gdn_bwd (`gdn_states`) in their place. A `mamba` layer of
    a family whose scan's decay differs by state (a configuration with
    `ssm_rank`: Mamba-1) has sscan_bwd once, and sscan_fwd once where the
    plan saves `sscan_y`, in place of ssd's pair; a gated memory unit
    (`gmu`) has no call of its own. A
    layer of `LlamaAttention` at heads of 128 that norms or turns q and k
    (QK_PREP_LAYERS) has qk_prep_bwd twice, q's and k's, and qk_prep_fwd
    twice where the plan saves `attn_q` and `attn_k`, else four times."""
    import collections
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import remat
    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import kernel_tally, make_mesh
    from ray_tpu.parallel.train_step import TrainStep

    calls, shares = {}, {}
    for name, cfg, shape in _cells():
        ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]), telemetry=False)
        state = jax.eval_shape(ts._init, jax.random.PRNGKey(0))
        tok = jax.ShapeDtypeStruct((shape.rows, shape.seq_len), jnp.int32)
        # the tiles of each kind of call, as the step's trace asks the rule for them
        with mock.patch.object(attention, "flash_tiles", wraps=attention.flash_tiles) as rule:
            text = ts._step.trace(state, {"idx": tok, "targets": tok}).lower(
                lowering_platforms=("tpu",)).as_text()
        shares[name] = _scores_needed_share(rule.call_args_list)
        found = kernel_tally(text)
        calls[name] = dict(sorted(found.items()))
        fwd = sum(n for k, n in found.items() if k.startswith("flash_") and k.endswith("_fwd"))
        # the plain causal call's name goes on with those of the two calls it
        # replaced (attention.LEGACY_NAMES); no call is one of those two
        kinds = collections.Counter()
        for k, n in found.items():
            kinds[k.removesuffix(attention.LEGACY_NAMES).rsplit("_bwd_", 1)[-1]] += n
        layer_kinds = list(getattr(cfg, "layer_types", ()))
        scans, convs = layer_kinds.count("mamba"), layer_kinds.count("conv")
        mixers_alone = layer_kinds.count("experts")
        deltas, units = layer_kinds.count("kda"), layer_kinds.count("gmu")
        rules = layer_kinds.count("linear_attention")  # ops/gdn.py's pair (models/qwen3_next.py)
        selective = scans if hasattr(cfg, "ssm_rank") else 0  # ops/selective_scan.py's pair
        kept = remat.traced(cfg).depth  # the layers that save a name run its kernel once
        scan_fwd = 2 * (scans - selective) - kept("ssm_y")
        conv_fwd = 2 * convs - kept("conv_y")
        by_group = scans if getattr(cfg, "ssm_groups", 1) > 1 else 0
        prepped = 2 * QK_PREP_LAYERS.get(name, 0)
        if on_tpu and not (fwd == kinds["fused"] == (cfg.n_layer - scans - convs - mixers_alone
                                                     - deltas - units - rules)
                           and found["gdn_fwd"] == 2 * rules - kept("gdn_states")
                           and found["gdn_bwd"] == rules
                           and found["sscan_fwd"] == 2 * selective - kept("sscan_y")
                           and found["sscan_bwd"] == selective
                           and found["kda_fwd"] == 2 * deltas - kept("kda_states")
                           and found["kda_bwd"] == deltas
                           and found["kda_norm_fwd"] == 2 * (deltas + rules)
                           and found["kda_norm_bwd"] == deltas + rules
                           and found["ssd_fwd"] == scan_fwd
                           and found["ssd_bwd"] == scans - selective
                           and found["gated_conv_fwd"] == conv_fwd
                           and found["gated_conv_bwd"] == convs
                           and found["causal_conv_fwd"] == 2 * (scans + deltas + rules)
                           and found["causal_conv_bwd"] == scans + deltas + rules
                           and found["gated_norm_fwd"] == 2 * by_group
                           and found["gated_norm_bwd"] == by_group
                           and found["qk_prep_bwd"] == prepped
                           and found["qk_prep_fwd"] == (
                               prepped and 2 * (prepped - kept("attn_q")))):
            raise RuntimeError(f"{name}: {cfg.n_layer} layers, {scans} of them scans and "
                               f"{convs} convolutions, calls {calls[name]}")
        if kinds["dq"] or kinds["dkv"]:
            raise RuntimeError(f"{name}: a backward call for one gradient alone: {calls[name]}")
    return calls, shares


def _scores_needed_share(asked):
    """Of the calls to `flash_tiles` that a step's trace made, by kind of
    flash call as its name says it: the scores a query can see over the
    scores the call computes (`attention.flash_scores`), a number of the
    call's shapes; 1 is a call that computes no score it masks."""
    import inspect

    from ray_tpu.ops import attention

    shares = {}
    for call in asked:
        args = inspect.signature(attention.flash_tiles).bind(*call.args, **call.kwargs).arguments
        tiles = attention.flash_tiles(*call.args, **call.kwargs)
        kind = ("flash_mla" if args.get("shared") else
                f"flash_bd{tiles.blocks}" if tiles.blocks else
                f"flash_sel{tiles.select}" if tiles.select else
                f"flash_win{tiles.window}" if tiles.window else "flash")
        shares[kind] = {"tile": [tiles.block_q, tiles.block_k],
                        "sub_tile": [tiles.sub_fwd, tiles.sub_bwd], **_needed_shares(tiles, args["t"])}
    return shares


def _needed_shares(tiles, t):
    """The scores a query can see over the scores a head's forward and
    backward call with `tiles` compute (`attention.flash_scores`)."""
    from ray_tpu.ops.attention import flash_scores

    shares = {}
    for call, backward in (("forward", False), ("backward", True)):
        computed, needed = flash_scores(tiles, t, backward)
        shares[call] = needed / computed
    return {"scores_needed": needed, "needed_share": shares}


def one_chip_loop(config):
    import jax

    from ray_tpu import train
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.train_step import TrainStep

    devs, report, cfg, batch = _setup(config)
    on_tpu = report["platform"] == "tpu"
    ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=devs[:1]))
    state = ts.init(jax.random.PRNGKey(config["seed"]))
    batch = ts.shard_batch(batch)
    lowered = ts._step.lower(state, batch).as_text()
    report["tpu_custom_calls_in_lowered_step"] = lowered.count("tpu_custom_call")
    if on_tpu and not report["tpu_custom_calls_in_lowered_step"]:
        raise RuntimeError("no tpu_custom_call in the lowered step")

    # the compile step plus 8 steps
    state, calls, telemetry = _run_steps(ts, state, batch, 9)
    losses = [loss for _, loss, _ in calls]
    _check_losses(losses)
    report.update(
        telemetry={"one_device": telemetry},
        compile_seconds=calls[0][0],
        step_seconds=[dt for dt, _, _ in calls[1:]],
        step_recompiled=[c for _, _, c in calls[1:]],
        losses=losses,
        peak_bytes_in_use=_mem(devs[0], "peak_bytes_in_use"),
    )
    if on_tpu and not report["peak_bytes_in_use"]:
        raise RuntimeError("device reports no peak_bytes_in_use")
    del state
    report["flash_vs_xla"] = [
        _check_flash_vs_xla(shape, config["seed"], on_tpu)
        for shape in config["attn_shapes"]] + [
        _check_flash_vs_xla(shape, config["seed"], on_tpu, window)
        for shape, window in config["windowed_shapes"]]
    report["flash_bd_vs_xla"] = [
        _check_flash_vs_xla(shape, config["seed"], on_tpu, blocks=blocks)
        for shape, blocks in config["bd_shapes"]]
    report["ssd_vs_chunked"] = _check_ssd_vs_chunked(config["seed"], on_tpu)
    report["ssd_vs_chunked_at_eight_groups"] = _check_ssd_vs_chunked(config["seed"], on_tpu, 8)
    report["relu2_experts_vs_plain"] = _check_relu2_experts_vs_plain(config["seed"], on_tpu)
    report["gated_conv_vs_plain"] = _check_gated_conv_vs_plain(config["seed"], on_tpu)
    report["causal_conv_vs_plain"] = _check_causal_conv_vs_plain(config["seed"], on_tpu)
    report["gated_norm_vs_plain"] = _check_gated_norm_vs_plain(config["seed"], on_tpu)
    report["qk_prep_vs_plain"] = _check_qk_prep_vs_plain(config["seed"], on_tpu)
    report["flash_mla_vs_plain"] = _check_flash_mla_vs_plain(config["seed"], on_tpu)
    report["latent_layer_vs_plain"] = _check_latent_layer_vs_plain(config["seed"], on_tpu)
    report["gated_attention_vs_plain"] = _check_gated_attention(config["seed"], on_tpu)
    report["kda_vs_plain"] = _check_kda_vs_plain(config["seed"], on_tpu)
    report["gdn_vs_plain"] = _check_gdn_vs_plain(config["seed"], on_tpu)
    report["kda_norm_vs_plain"] = _check_kda_norm_vs_plain(config["seed"], on_tpu)
    report["sscan_vs_recurrence"] = _check_sscan_vs_recurrence(config["seed"], on_tpu)
    report["windowed_flash"] = _windowed_flash_plan()
    report["selected_flash"] = _selected_flash_plan()
    report["index_select_vs_top_k"] = _check_selection(config["seed"], on_tpu)
    report["remat_plans"] = _remat_plans()
    report["flash_calls_by_cell"], report["flash_scores_needed_share"] = _flash_calls_by_cell(on_tpu)
    report["compile_cache_entries_after"] = _cache_entries(report["compile_cache_dir"])
    train.report(report)


def four_chip_loop(config):
    import jax

    from ray_tpu import train
    from ray_tpu.parallel.mesh import collective_tally, make_mesh
    from ray_tpu.parallel.train_step import TrainStep

    devs, report, cfg, batch = _setup(config)
    on_tpu = report["platform"] == "tpu"
    if len(devs) != 4:
        raise RuntimeError(f"worker leased 4 chips sees {len(devs)} devices")
    axes = {"dp": 2, "tp": 2}
    runs, telemetry = {}, {}
    for name, mesh in (
        ("mesh", make_mesh(axes, devices=devs)),
        ("one_device", make_mesh({"dp": 1}, devices=devs[:1])),
    ):
        ts = TrainStep(cfg, mesh)
        state = ts.init(jax.random.PRNGKey(config["seed"]))
        if name == "mesh":
            # the layout GPT2_SHARDING_RULES promise: c_attn kernel P(fsdp, tp)
            qkv = state["params"]["h_0"]["attn"]["c_attn"]["kernel"]
            got = [tuple(s.data.shape) for s in qkv.addressable_shards]
            want = (qkv.shape[0], qkv.shape[1] // axes["tp"])
            if len(got) != 4 or any(g != want for g in got):
                raise RuntimeError(f"qkv shards {got}, expected 4 x {want}")
            report["qkv_shard_shape"] = list(want)
            report["bytes_in_use_per_device"] = [
                _mem(d, "bytes_in_use") for d in devs]
            if on_tpu and not all(report["bytes_in_use_per_device"]):
                raise RuntimeError("a device reports no bytes in use")
        sharded = ts.shard_batch(batch)
        state, calls, telemetry[name] = _run_steps(ts, state, sharded, 5)
        if name == "mesh":
            text = ts._step.lower(state, sharded).compile().as_text()
            # the layout as the partitioner made it: collectives by kind and
            # result shape, so a chip call shows it without a trace
            tally = collective_tally(text)
            report["collectives_in_compiled_step"] = {
                str(c): n for c, n in sorted(tally.items(), key=lambda cn: -cn[0].nbytes * cn[1])}
            report["tpu_custom_calls_in_compiled_step"] = text.count("tpu_custom_call")
            if not any(c.kind == "all-reduce" for c in tally):
                raise RuntimeError("no all-reduce in the compiled mesh step")
            if on_tpu and not report["tpu_custom_calls_in_compiled_step"]:
                raise RuntimeError("no tpu_custom_call in the compiled mesh step")
        del state
        runs[name] = calls
    # compile step + four steps each; compare the four
    mesh_losses = [loss for _, loss, _ in runs["mesh"]]
    ref_losses = [loss for _, loss, _ in runs["one_device"]]
    _check_losses(mesh_losses)
    _check_losses(ref_losses)
    rel = [abs(a - b) / abs(b) for a, b in zip(mesh_losses, ref_losses)]
    if max(rel) > LOSS_REL_TOL:
        raise RuntimeError(
            f"mesh vs one-device losses beyond {LOSS_REL_TOL}: "
            f"{mesh_losses} vs {ref_losses}")
    report.update(
        mesh=axes,
        telemetry=telemetry,
        losses_mesh=mesh_losses, losses_one_device=ref_losses,
        loss_rel_diff=rel,
        compile_seconds={k: v[0][0] for k, v in runs.items()},
        step_seconds={k: [dt for dt, _, _ in v[1:]] for k, v in runs.items()},
        step_recompiled={k: [c for _, _, c in v[1:]] for k, v in runs.items()},
        peak_bytes_in_use_per_device=[
            _mem(d, "peak_bytes_in_use") for d in devs],
        compile_cache_entries_after=_cache_entries(report["compile_cache_dir"]),
    )
    train.report(report)


# ------------------------------------------------------------- driver side


def _print_worker_err_logs(session_dir, tail=6000):
    """The chip tool shows only the end of the output: on failure, put the
    end of each worker's .err log there."""
    for path in sorted(glob.glob(os.path.join(session_dir, "logs", "worker-*.err"))):
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - tail))
            text = f.read().decode(errors="replace").strip()
        if text:
            print(f"--- tail of {path}\n{text}", flush=True)


def _left_running(session_dir):
    """Processes of this run that are still there: everything the runtime
    starts (GCS, raylet, agent, fork server, workers) names its session
    directory on its command line."""
    left = []
    for path in glob.glob("/proc/[0-9]*/cmdline"):
        try:
            with open(path, "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue  # gone between the listing and the read
        if session_dir in cmd:
            left.append(f"{path.split('/')[2]} {cmd[:160]}")
    return left


def _check_telemetry(report, enforce):
    """The program's clock against this script's: each run's last step
    seconds, tokens/s and MFU as TrainStep's telemetry has them, over the
    same numbers from the steps timed here to block_until_ready."""
    seconds = report["step_seconds"]
    if not isinstance(seconds, dict):
        seconds = {"one_device": seconds}
    tokens = report["B"] * report["T"]
    for name, tel in report["telemetry"].items():
        rate = tokens / statistics.mean(seconds[name])
        own = {"step_time_s": seconds[name][-1], "tokens_per_s": rate}
        if report["peak_flops_per_device"]:
            own["mfu"] = (rate * report["flops_per_token"]
                          / (report["peak_flops_per_device"] * tel["n_devices"]))
        ratio = {k: tel[k] / v if k in tel else None for k, v in own.items()}
        print(json.dumps({"telemetry_over_blocked": {name: ratio},
                          "goodput_after_warmup": tel["goodput_after_warmup"]}),
              flush=True)
        if not enforce:
            continue
        if any(r is None or abs(r - 1) > TELEMETRY_REL_TOL for r in ratio.values()):
            raise SystemExit(
                f"chip_smoke: {name}: telemetry {tel} disagrees with the blocked "
                f"steps {own} by more than {TELEMETRY_REL_TOL}")
        if tel["goodput_after_warmup"] < GOODPUT_FLOOR:
            raise SystemExit(
                f"chip_smoke: {name}: goodput after the compile call is "
                f"{tel['goodput_after_warmup']}, under {GOODPUT_FLOOR}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny model on CPU devices; never prints the ok line")
    args = ap.parse_args()

    import ray_tpu
    from ray_tpu import api
    from ray_tpu.train import JaxConfig, JaxTrainer, RunConfig, ScalingConfig

    config = {**(TINY if args.rehearse else FULL),
              "seed": args.seed, "rehearse": args.rehearse}
    worker_env = {}
    if args.rehearse:
        ray_tpu.init(num_cpus=4, num_tpus=args.chips)
        worker_env = {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={args.chips}",
        }
    else:
        ray_tpu.init()  # resources autodetected: the node must find its chips
    passed = False
    try:
        tpus = ray_tpu.cluster_resources().get("TPU", 0)
        print(json.dumps({"node_resources": ray_tpu.cluster_resources()}), flush=True)
        if tpus < args.chips:
            raise SystemExit(
                f"chip_smoke: node advertises TPU={tpus}, need {args.chips}")
        if args.chips == 1:
            loop, scaling = one_chip_loop, ScalingConfig(num_workers=1, use_tpu=True)
        else:
            loop, scaling = four_chip_loop, ScalingConfig(
                num_workers=1, resources_per_worker={"TPU": 4})
        result = JaxTrainer(
            loop,
            train_loop_config=config,
            scaling_config=scaling,
            jax_config=JaxConfig(env=worker_env),
            run_config=RunConfig(
                name="chip_smoke",
                storage_path=tempfile.mkdtemp(prefix="chip_smoke_")),
        ).fit()
        report = result.metrics
        for key, value in report.items():
            print(json.dumps({key: value}), flush=True)
        _check_telemetry(report, enforce=not args.rehearse)
        if report["device_count"] != args.chips:
            raise SystemExit(
                f"chip_smoke: worker saw {report['device_count']} devices, "
                f"leased {args.chips}")
        jax = sys.modules.get("jax")
        if jax is not None and jax._src.xla_bridge.backends_are_initialized():
            raise SystemExit("chip_smoke: the parent initialized a JAX backend")
        print(json.dumps({"parent_imported_jax": jax is not None,
                          "parent_jax_backends_initialized": False}), flush=True)
        passed = True
    finally:
        session_dir = api._local_node.session_dir
        if not passed:
            _print_worker_err_logs(session_dir)
        t0 = time.perf_counter()
        ray_tpu.shutdown()
        left = _left_running(session_dir)
        print(json.dumps({"shutdown_seconds": time.perf_counter() - t0,
                          "processes_left_running": left}), flush=True)
    if left:
        raise SystemExit("chip_smoke: shutdown left processes running")
    # A killed worker has no command line any more, but holds its chips until
    # the kernel is done with it and it is reaped.
    if os.path.exists(f"/proc/{report['worker_pid']}"):
        raise SystemExit(
            f"chip_smoke: worker {report['worker_pid']} is not gone yet")
    device = {"platform": report["platform"], "kind": report["device_kind"],
              "count": report["device_count"]}
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}), flush=True)
        return
    if device["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: ran on {device['platform']!r}, not a TPU")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
