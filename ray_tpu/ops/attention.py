"""Fused causal attention: pallas flash kernel (TPU) with an XLA fallback.

FlashAttention-2-style tiling: the query axis is the pallas grid, K/V are
streamed block-by-block with an online softmax (running max + sum in VMEM
scratch, fp32). The backward pass recomputes attention per tile from the saved
logsumexp — O(T) memory instead of O(T^2). All matmuls run on the MXU with
fp32 accumulation.

The reference framework has no attention kernels at all (its data plane is
torch); this op is the building block its GPU stack gets from flash-attn, and
the ring-attention layer (ray_tpu/ops/ring_attention.py) composes it per-step
for sequence parallelism.

The three pallas calls are named flash_fwd, flash_bwd_dq and flash_bwd_dkv.
The name reaches the compiled instruction and the profiler's trace (wrapped by
the transformations it went through, e.g. transpose_jvp_flash_bwd_dq_), on one
chip and under a mesh alike, and is how the benchmark's per-kernel metrics
(bench/layer_metrics/flash_*) find each call: a kernel that is split, fused
or renamed takes a new name, and none is a substring of another.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pick_block(t: int, target: int = 128) -> int:
    if t % target == 0:
        return target
    for b in (64, 32, 16, 8):
        if t % b == 0:
            return b
    return t


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_q, block_k, seq_len):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # (block_q, d)
    d = q.shape[-1]

    m = jnp.full((block_q, 1), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((block_q, 1), dtype=jnp.float32)
    acc = jnp.zeros((block_q, d), dtype=jnp.float32)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(kj, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block_q, block_k)
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l, acc

    num_k_blocks = (qi + 1) * block_q // block_k  # causal: only blocks at/below diag
    m, l, acc = jax.lax.fori_loop(0, num_k_blocks, body, (m, l, acc))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # lse rides a (bh, 1, t) layout: block (1, 1, block_q) keeps Mosaic's
    # last-two-dims tiling rule satisfied (a (1, block_q) rank-2 block is not)
    lse_ref[0, 0] = (m + jnp.log(l))[:, 0]


def _flash_fwd(q, k, v, *, block_q, block_k, interpret):
    bh, t, d = q.shape
    scale = 1.0 / math.sqrt(d)
    grid = (bh, t // block_q)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k, seq_len=t
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale, block_q, block_k):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, None]
    delta = delta_ref[0, 0][:, None]
    d = q.shape[-1]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(kj, dq):
        k = k_ref[0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    num_k_blocks = (qi + 1) * block_q // block_k
    dq = jax.lax.fori_loop(0, num_k_blocks, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    *, scale, block_q, block_k, seq_len):
    kj = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    d = k.shape[-1]
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32) * scale
        do = do_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)][:, None]
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk, dv

    first_q_block = kj * block_k // block_q  # causal: q blocks at/after the diagonal
    num_q_blocks = seq_len // block_q
    dk, dv = jax.lax.fori_loop(
        first_q_block, num_q_blocks, body,
        (jnp.zeros((block_k, d), jnp.float32), jnp.zeros((block_k, d), jnp.float32)),
    )
    # q was pre-scaled, so ds^T @ q_scaled already carries the 1/sqrt(d) factor.
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd(res, g, *, block_q, block_k, interpret):
    q, k, v, o, lse = res
    do = g
    bh, t, d = q.shape
    scale = 1.0 / math.sqrt(d)
    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1
    )[:, None, :]  # (bh, 1, t) — same layout as lse

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_q=block_q, block_k=block_k),
        grid=(bh, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, block_q=block_q, block_k=block_k, seq_len=t
        ),
        grid=(bh, t // block_k),
        in_specs=[
            pl.BlockSpec((1, t, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, t, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, block_q, block_k, interpret):
    o, _ = _flash_fwd(q, k, v, block_q=block_q, block_k=block_k, interpret=interpret)
    return o


def _flash_fwd_rule(q, k, v, block_q, block_k, interpret):
    o, lse = _flash_fwd(q, k, v, block_q=block_q, block_k=block_k, interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(block_q, block_k, interpret, res, g):
    return _flash_bwd(res, g, block_q=block_q, block_k=block_k, interpret=interpret)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_causal_attention(q, k, v, *, block_q=None, block_k=None, interpret=False):
    """q/k/v: (B, H, T, D) → (B, H, T, D); fused causal attention."""
    b, h, t, d = q.shape
    block_q = block_q or _pick_block(t)
    block_k = block_k or _pick_block(t)
    # The kernel's causal lower bound num_k_blocks = (qi+1)*block_q//block_k
    # is 0 for early q blocks when block_q < block_k, leaving l==0 and o=NaN.
    if block_q < block_k or block_q % block_k:
        raise ValueError(
            f"block_q ({block_q}) must be a multiple of block_k ({block_k}) "
            "for the causal flash kernel: its causal bound "
            "(qi+1)*block_q//block_k floors, skipping keys otherwise"
        )
    if t % block_q or t % block_k:
        raise ValueError(f"seq len {t} must be divisible by block sizes")
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, d)
    o = _flash(qf, kf, vf, block_q, block_k, interpret)
    return o.reshape(b, h, t, d)


def xla_causal_attention(q, k, v):
    """Plain einsum-softmax reference path; XLA fuses it adequately on TPU."""
    d = q.shape[-1]
    t = q.shape[2]
    s = jnp.einsum("bhtd,bhsd->bhts", q, k, preferred_element_type=jnp.float32)
    s = s / math.sqrt(d)
    mask = jnp.tril(jnp.ones((t, t), dtype=bool))
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


def _on_tpu() -> bool:
    # No try/except: a worker that cannot reach its chip must fail here, not
    # train on the XLA path in silence.
    return jax.devices()[0].platform == "tpu"


def attention_path(seq_len: int) -> str:
    """Which path `causal_attention` takes for this sequence length on this
    process's backend: "flash" (the pallas kernel) or "xla"."""
    if _on_tpu() and seq_len >= 256 and seq_len % 128 == 0:
        return "flash"
    return "xla"


def causal_attention(q, k, v):
    """Layout-adapting entry: q/k/v (B, T, H, D) → (B, T, H, D).

    Uses the pallas flash kernel on TPU for sequences long enough to matter;
    XLA path elsewhere (CPU tests, tiny shapes). A Mosaic kernel cannot be
    partitioned by the compiler: under a multi-device mesh call it through
    `parallel.train_step.attn_for_mesh` (shard_map over batch and heads).
    """
    T = q.shape[1]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if attention_path(T) == "flash":
        o = flash_causal_attention(qt, kt, vt)
    else:
        o = xla_causal_attention(qt, kt, vt)
    return o.transpose(0, 2, 1, 3)
