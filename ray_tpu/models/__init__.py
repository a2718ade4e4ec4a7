"""Model zoo: TPU-native flax implementations used by the Train/bench stack.

What `TrainStep` (parallel/train_step.py) asks of a model configuration, the
contract's one home. It reads `family` (the `Family` below: a class attribute
that the family's own file sets at its foot, no dataclass field, since it is
no option), `block_size`, `attn_fn` and `use_flash_attention` (`model_for_mesh`
sets the first where a mesh needs its own attention), `flops_per_token(seq_len)`
where the family counts its FLOPs, and `lr_warmup_steps` where its recipe has
any (ROADMAP C15). Everything else a family is, the record says: its objective
too, where it is not the next token's.

A family's file holds a family: of this package it imports the package, `remat`,
`loss` and `layers` (what several families run), and no other family's file
(`gpt2_moe`, a variant of `gpt2`, imports it); tests/test_family.py holds it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax

from ray_tpu.models.loss import loss_fn


def next_token_objective(model, params, batch, step):
    """`Family.objective` of every family but one: the mean next-token loss of
    `model.apply(params, batch["idx"])` against batch["targets"] and the loss
    terms the layers sowed. The step's count is none of its."""
    family = model.config.family
    logits, sown = model.apply({"params": params}, batch["idx"], mutable=list(family.sown))
    aux = sum(jax.tree.leaves([sown.get(c, {}) for c in family.loss_terms]), 0.0)
    with jax.named_scope("loss"):
        return loss_fn(logits, batch["targets"]) + aux, sown


@dataclasses.dataclass(frozen=True)
class Family:
    """One model family, as the step that trains it needs to know it."""

    # (cfg, stream) -> nn.Module; `stream` is the residual stream's sharding
    # on the step's mesh (parallel/mesh.py:stream_sharding) or None
    module: Callable[[Any, Any], Any]
    rules: Any  # parallel/mesh.py:ShardingRules of its parameters
    # the collections its layers sow that the step takes out of the forward
    # pass, and of those the ones whose leaves are terms of the loss
    sown: Tuple[str, ...] = ()
    loss_terms: Tuple[str, ...] = ()
    # (cfg, sown, params after the update, tokens in the batch) -> the step's
    # metrics beside loss and grad_norm: scalars, from the reducers that live
    # beside the layers that sow. The telemetry's summary carries every one as
    # it is (train/_telemetry.py:StepRecorder.step_gauges).
    metrics: Optional[Callable[[Any, Any, Any, int], dict]] = None
    # (leaf name, rule): parameter leaves of that name are none of the
    # optimizer's (no moment, no decay), and after the update
    # `rule(params, sown) -> params` moves them from what the step sowed.
    # Their layer reads them under a stop_gradient: a leaf that optax masks
    # out of AdamW gets its gradient itself as its update.
    held_leaf: Optional[Tuple[str, Callable[[Any, Any], Any]]] = None
    # (model, params, batch, the step's count) -> (loss, sown): what the step
    # differentiates. It applies the model itself, takes out what `sown`
    # names and adds its loss terms under the scope `loss`; the count,
    # `state["step"]` on the device, is what an objective that draws noise
    # draws a step's from (models/sdar.py).
    objective: Callable[[Any, Any, Any, Any], Tuple[Any, Any]] = next_token_objective
