"""The losses the families' `TrainStep` runs: the next-token loss of every
family but one, and the weighted sum a family's own objective takes
(models/sdar.py), both over `token_losses`."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def token_losses(logits, targets):
    """Each token's cross-entropy as logsumexp less the target's logit, which is
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
    return lse - picked


def loss_fn(logits, targets):
    """Mean cross-entropy of the next token."""
    return token_losses(logits, targets).mean()


def weighted_loss(logits, targets, weight, count):
    """sum(weight * cross-entropy) / count: an objective that weighs its
    tokens (a diffusion step's 1/t on the masked ones, 0 on the rest) and
    says itself what it is a mean over."""
    return (token_losses(logits, targets) * weight).sum() / count
