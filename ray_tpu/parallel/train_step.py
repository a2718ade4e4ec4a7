"""Sharded training step builder: one jit, every parallelism axis.

This is the TPU-native replacement for the reference's torch DDP/FSDP wrapper
stack (reference: python/ray/train/torch/train_loop_utils.py:453 prepare_model
→ DDP, :184 FSDP): instead of wrapping modules and calling NCCL imperatively,
we build a `jax.sharding.Mesh`, assign PartitionSpecs to params/optimizer
state/batch, and compile ONE train step under jit — XLA inserts the ICI
collectives (grad psums over dp, param all-gathers over fsdp, activation
collectives over tp, ring ppermutes over sp) from the shardings. Between the
state and the batch the program states one thing more: the residual stream
stays split as the batch is (`mesh.stream_sharding`, handed to the model by
`model_for_mesh`), so the partitioner moves weights and not activations.

Axes (any subset may be trivial/size-1, one rule set serves all):
  dp    batch;                 grads psum over it (DDP-equivalent)
  fsdp  param/optimizer shard; ZeRO-3-equivalent, also carries batch
  tp    Megatron tensor parallel over hidden/head dims
  sp    sequence/context parallel; attention runs a ppermute ring
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from jax.profiler import TraceAnnotation

from ray_tpu._private import flight_recorder
from ray_tpu.models import remat
from ray_tpu.parallel.mesh import (
    ShardingRules,
    batch_sharding,
    filtered_tree_shardings,
    stream_sharding,
)


def _batch_counts(batch) -> Tuple[Optional[int], Optional[int], Optional[int]]:
    """(tokens, examples, sequence length) of a batch dict for telemetry:
    the idx array's element count is the token count, its last dim the
    sequence length (works for (B, T) steps and (num_steps, B, T) scan
    stacks)."""
    idx = batch.get("idx")
    if idx is None or not getattr(idx, "shape", None) or not idx.shape[-1]:
        return None, None, None
    tokens = 1
    for d in idx.shape:
        tokens *= int(d)
    seq_len = int(idx.shape[-1])
    return tokens, tokens // seq_len, seq_len


def attn_for_mesh(mesh: Mesh, seq_axis: str = "sp"):
    """Attention callable for a config's attn_fn on a multi-device mesh:
    shard_map over batch (dp, fsdp) and heads (tp), so the pallas flash
    kernel — which the compiler cannot partition — stays in the step; with
    sp > 1 the per-shard body is ring attention over the sequence axis,
    local flash attention per chunk-pair."""
    from jax import shard_map

    from ray_tpu.ops.attention import causal_attention
    from ray_tpu.ops.ring_attention import ring_causal_attention

    def live(a):
        return a in mesh.axis_names and mesh.shape[a] > 1

    data = tuple(a for a in ("dp", "fsdp") if live(a))
    spec = P(  # (B, T, H, D)
        data if data else None,
        seq_axis if live(seq_axis) else None,
        "tp" if live("tp") else None,
        None,
    )
    body = (
        functools.partial(ring_causal_attention, axis_name=seq_axis)
        if live(seq_axis) else causal_attention
    )

    def mapped(body):
        return shard_map(
            body,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )

    whole = mapped(body)

    def attn(q, k, v, window=None):
        """`window`: a layer that sees its last `window` keys alone."""
        if window is None:
            return whole(q, k, v)
        if live(seq_axis):
            raise NotImplementedError("ring attention takes no window")
        return mapped(functools.partial(causal_attention, window=window))(q, k, v)

    return attn


def model_for_mesh(cfg, mesh: Optional[Mesh]):
    """Instantiate the model wired for this mesh: shard_map'd attention on
    more than one device (ring attention iff sp > 1) and the residual
    stream's sharding there (none on one device). The module is the one the
    config's family states (`cfg.family`, models/__init__.py)."""
    import dataclasses

    if mesh is not None and cfg.attn_fn is None and mesh.devices.size > 1 and (
        cfg.use_flash_attention or mesh.shape.get("sp", 1) > 1
    ):
        cfg = dataclasses.replace(cfg, attn_fn=attn_for_mesh(mesh))
    return cfg.family.module(cfg, None if mesh is None else stream_sharding(mesh))


class TrainStep:
    """Compiled (init, step) pair with sharded state.

    Usage:
        ts = TrainStep(GPT2Config.tiny(), mesh)
        state = ts.init(jax.random.PRNGKey(0))
        state, metrics = ts.step(state, batch)   # batch: dict idx/targets (B, T)
    """

    def __init__(
        self,
        model_cfg,  # any family's config: what is read off it, models/__init__.py
        mesh: Mesh,
        *,
        learning_rate: float = 3e-4,
        weight_decay: float = 0.1,
        beta2: float = 0.95,
        grad_clip: float = 1.0,
        rules: Optional[ShardingRules] = None,
        flops_per_step: Optional[float] = None,
        telemetry: bool = True,
    ):
        # What the family is, in its own file's words (models/__init__.py):
        # its module and rules, what its layers sow and what becomes of it,
        # the leaves the optimizer leaves alone.
        family = model_cfg.family
        if rules is None:
            rules = family.rules
        self.model_cfg = model_cfg
        self.mesh = mesh
        self.model = model_for_mesh(model_cfg, mesh)
        # A model whose work follows its weights (routed experts: the rows an
        # expert gets are the router's doing) says how many steps the rate
        # climbs over, `lr_warmup_steps`: at the full rate from step 0 AdamW
        # moves every weight by a third of its initial scale in 20 steps
        # and the routing of those steps is nobody's workload.
        warmup = getattr(model_cfg, "lr_warmup_steps", 0)
        if warmup:
            learning_rate = optax.linear_schedule(0.0, learning_rate, warmup)
        adamw = optax.adamw(
            learning_rate, b2=beta2, weight_decay=weight_decay,
            mask=lambda params: jax.tree.map(lambda p: p.ndim > 1, params),
        )
        held, move_held = family.held_leaf or (None, None)
        if held is not None:
            adamw = optax.masked(adamw, lambda params: jax.tree_util.tree_map_with_path(
                lambda path, _: path[-1].key != held, params))
        self.optimizer = optax.chain(optax.clip_by_global_norm(grad_clip), adamw)
        self.batch_sharding = batch_sharding(mesh)

        def train_init(rng):
            # Dummy batch for shape inference must still satisfy the mesh:
            # B divisible by dp*fsdp, T by sp (ring attention shard_maps
            # over them even during init).
            data = 1
            for a in ("dp", "fsdp"):
                if a in mesh.shape:
                    data *= mesh.shape[a]
            sp = mesh.shape.get("sp", 1)
            T = min(8 * sp, model_cfg.block_size)
            idx = jnp.zeros((max(2, data), T), dtype=jnp.int32)
            params = self.model.init(rng, idx)["params"]
            return {
                "params": params,
                "opt_state": self.optimizer.init(params),
                "step": jnp.zeros((), jnp.int32),
            }

        state_shape = jax.eval_shape(train_init, jax.random.PRNGKey(0))
        self.state_specs, self.state_shardings = filtered_tree_shardings(
            rules, state_shape, mesh
        )
        self._init = jax.jit(train_init, out_shardings=self.state_shardings)

        # The jitted functions are named for what they are (the trace's
        # "XLA Modules" line reads jit_train_step), and the loss (which the
        # family's objective opens: models/__init__.py) and the optimizer
        # (with the gradients' global norm) are scopes beside the ones flax
        # gives the model's modules: every instruction of the
        # compiled step carries its scope and its pass in its op_name, and a
        # device-trace window is reduced to ms a step by them
        # (train/_device_profile.py).
        def train_step(state, batch):
            (loss, sown), grads = jax.value_and_grad(family.objective, argnums=1, has_aux=True)(
                self.model, state["params"], batch, state["step"])
            with jax.named_scope("optimizer"):
                updates, opt_state = self.optimizer.update(
                    grads, state["opt_state"], state["params"]
                )
                params = optax.apply_updates(state["params"], updates)
                if move_held is not None:
                    params = move_held(params, sown)
            new_state = {
                "params": params,
                "opt_state": opt_state,
                "step": state["step"] + 1,
            }
            with jax.named_scope("optimizer"):
                grad_norm = optax.global_norm(grads)
            metrics = {"loss": loss, "grad_norm": grad_norm}
            if family.metrics is not None:
                metrics.update(family.metrics(model_cfg, sown, params, batch["idx"].size))
            return new_state, metrics

        self._step = jax.jit(
            train_step,
            out_shardings=(self.state_shardings, None),
            donate_argnums=(0,),
        )
        self._step_fn = train_step
        self._multi: Dict[int, Any] = {}
        self._tiled_cache = None
        # Optimizer steps enqueued so far: the `step` every span of a step
        # carries, in the profiler's trace and the flight recorder.
        self.dispatched_steps = 0
        # Step-level telemetry (train/_telemetry.py): device time per step
        # from the completion clock, compile time (jit cache misses are
        # known exactly here), MFU from the model config's own FLOP count at
        # the batch's sequence length (flops_per_step overrides), goodput,
        # HBM. Registered process-globally so session.report auto-attaches
        # the summary. RTPU_TRAIN_TELEMETRY=0 disables.
        self._flops_per_token = (
            None if flops_per_step is not None
            else getattr(model_cfg, "flops_per_token", None))
        self.telemetry = None
        if telemetry:
            from ray_tpu.train import _telemetry

            self.telemetry = _telemetry.StepRecorder(
                flops_per_step=flops_per_step,
                n_devices=mesh.devices.size,
            )
            _telemetry.set_current_recorder(self.telemetry)

    def init(self, rng) -> Dict[str, Any]:
        with self.mesh:
            return self._init(rng)

    def shard_batch(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        with TraceAnnotation("ray_tpu.train_step.shard_batch",
                             step=self.dispatched_steps + 1):
            return jax.device_put(batch, self.batch_sharding)

    def _dispatch(self, fn, state, batches, num_steps: int):
        """One call of a jitted step program, with everything the program
        records about it: the spans on the profiler's clock, the flight
        recorder's breadcrumb, and the hand-over to the recorder, whose
        watcher books the step when it completes. Returns at enqueue."""
        # No mesh context, on the first call or any other: in/out shardings
        # are explicit NamedShardings and the shard_map'd attention carries
        # its mesh, so neither tracing nor dispatch needs the ambient one.
        # The jit cache key includes the ambient mesh: entering it for the
        # first call only would compile every step twice.
        rec = self.telemetry
        self.dispatched_steps += num_steps
        step = self.dispatched_steps
        if rec is not None:
            # Device-trace hook (_telemetry.DeviceTraceController): inert
            # two-attribute check unless a jax.profiler window was armed; a
            # window that closes is reduced to a device profile off this thread.
            rec.device_trace.on_step_begin()
            started = rec.clock()
        with TraceAnnotation("ray_tpu.train_step.dispatch", step=step):
            flight_recorder.record("train.dispatch", step, num_steps)
            with TraceAnnotation("ray_tpu.train_step.jit", step=step):
                cache_before = fn._cache_size()
                out = fn(state, batches)
                # Compile detection by actual jit cache miss (not just
                # first-call): a new batch shape recompiles too, and every
                # compile must be booked as compile time, not step time.
                compiled = fn._cache_size() != cache_before
                if compiled:
                    # Contain the whole compile + first execution in THIS
                    # call: without the sync the backlog would drain into
                    # the next step's interval.
                    jax.block_until_ready(out)
                    if rec is not None:
                        # what the program just traced saves across remat
                        rec.remat_plan = remat.traced(self.model.config)
            if rec is not None:
                with TraceAnnotation("ray_tpu.train_step.record", step=step):
                    tokens, examples, seq_len = _batch_counts(batches)
                    flops = None
                    if self._flops_per_token is not None and tokens:
                        flops = self._flops_per_token(seq_len) * tokens
                    rec.dispatched(
                        out[1], started=started, step=step, steps=num_steps,
                        tokens=tokens, examples=examples, flops=flops,
                        compile_step=compiled)
        if rec is not None:
            rec.device_trace.on_step_end(out)
        return out

    def step(self, state, batch) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        return self._dispatch(self._step, state, batch, 1)

    def multi_step(self, state, batches, num_steps: int):
        """Run `num_steps` optimizer steps in ONE dispatch via lax.scan
        (XLA-idiomatic: python per-call dispatch costs ~1-3ms, a compiled
        scan body costs nothing — at short step times the scan is the
        difference between dispatch-bound and MXU-bound).

        `batches`: dict of arrays with a leading (num_steps, ...) axis
        (stacked micro-batches), or a single batch dict to reuse each step.
        Returns (state, metrics) with metrics stacked over steps."""
        key = num_steps
        fn = self._multi.get(key)
        if fn is None:
            def body(state, batch):
                new_state, m = self._step_fn(state, batch)
                return new_state, m

            def train_multi_step(state, batches):
                return jax.lax.scan(body, state, batches, length=num_steps)

            fn = jax.jit(
                train_multi_step,
                out_shardings=(self.state_shardings, None),
                donate_argnums=(0,),
            )
            self._multi[key] = fn
        # tile-or-not is decided per call from the actual layout (a cached
        # flag goes stale when batch layout or num_steps changes): a batch
        # is already stacked iff it carries the extra leading num_steps axis
        sample = next(iter(batches.values()))
        if sample.ndim < 3 or sample.shape[0] != num_steps:
            # reuse-one-batch convenience: tile once and cache — a per-call
            # broadcast adds a dispatch to every chunk. The cache holds
            # STRONG refs to the source arrays, so an id()-reuse after GC
            # can never produce a false hit.
            src = (num_steps,) + tuple(batches.values())
            cached = self._tiled_cache
            hit = (
                cached is not None
                and len(cached[0]) == len(src)
                and all(a is b for a, b in zip(cached[0], src))
            )
            if not hit:
                tiled = jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x[None], (num_steps,) + x.shape),
                    batches,
                )
                self._tiled_cache = (src, tiled)
            batches = self._tiled_cache[1]
        # one dispatch, one record: the scan body runs num_steps optimizer
        # steps inside XLA, so the per-call overhead is amortized
        return self._dispatch(fn, state, batches, num_steps)
