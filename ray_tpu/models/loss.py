"""The next-token loss every family's `TrainStep` runs."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def loss_fn(logits, targets):
    """Mean cross-entropy as logsumexp less the target's logit, which is
    `-log_softmax(logits)[target]` without the log-probabilities: those are
    a float32 array of the logits' size that a gather reads one element a
    row of, written in the forward pass and summed over again in the
    backward. The target's logit is a masked sum over the row, not a gather:
    XLA takes it in the log-sum's own pass over the logits, and its gradient
    is a select inside the backward matmuls' operands. A gather's gradient
    is a scatter, which XLA ran on a float32 copy of the softmax's gradient,
    written and laid out again, where a batch is one sequence of float32
    logits."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1) == targets[..., None]
    picked = jnp.where(hit, logits, 0).astype(jnp.float32).sum(axis=-1)
    return (lse - picked).mean()
