"""Plain float32 pieces the references share. Nothing here is imported by
the program, and nothing here imports it."""

import functools
import math

import jax
import jax.numpy as jnp


def highest(fn):
    """Float32 matmuls in float32: on a TPU the default precision of a
    float32 matmul is a single bf16 pass."""
    @functools.wraps(fn)
    def wrapped(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return wrapped

# A query block sees every key, so its softmax is exact and whole: blocks
# bound memory (T x T scores in float32 are 8.6 GB a layer at T=8192 and 32
# heads), they do not change the arithmetic. One block up to this length.
QUERY_BLOCK = 512


def causal_attention(q, k, v):
    """q (B, T, H, D), k and v (B, T, G, D) with H a multiple of G (grouped
    queries: head h reads key-value head h // (H/G)). Returns (B, T, H, D)."""
    B, T, H, D = q.shape
    G = k.shape[2]
    q = q.reshape(B, T, G, H // G, D)
    key_pos = jnp.arange(T)

    def block(q_blk, start):
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q_blk, k) / math.sqrt(D)
        q_pos = start + jnp.arange(q_blk.shape[1])
        s = jnp.where(q_pos[:, None] >= key_pos[None, :], s, -jnp.inf)
        return jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(s, axis=-1), v)

    if T <= QUERY_BLOCK:
        out = block(q, 0)
    else:
        n = T // QUERY_BLOCK
        if n * QUERY_BLOCK != T:
            raise ValueError(f"sequence {T} is not a multiple of {QUERY_BLOCK}")
        blocks = q.reshape(B, n, QUERY_BLOCK, G, H // G, D).swapaxes(0, 1)
        # checkpoint: the backward pass recomputes a block's scores instead
        # of keeping n of them. Same values, bounded memory.
        out = jax.lax.map(
            lambda xs: jax.checkpoint(block)(xs[0], xs[1]),
            (blocks, jnp.arange(n) * QUERY_BLOCK))
        out = out.swapaxes(0, 1).reshape(B, T, G, H // G, D)
    return out.reshape(B, T, H, D)


def next_token_loss(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
