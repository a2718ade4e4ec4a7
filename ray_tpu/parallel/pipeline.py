"""Pipeline parallelism (pp) for the transformer stack, jax-idiomatic.

The reference's pipeline story is mechanism-level: compiled actor DAGs
moving tensors between stage actors over NCCL channels
(reference: python/ray/dag/compiled_dag_node.py:391,
experimental/channel/torch_tensor_nccl_channel.py:191). On TPU the idiomatic
equivalent *inside one jit* is a mesh axis: transformer blocks stack along a
leading layer dim sharded over the `pp` axis, and a GPipe microbatch
schedule runs as a `lax.scan` over clock ticks with `lax.ppermute` shifting
activations stage-to-stage over ICI. Autodiff through scan+ppermute gives
the pipeline backward pass for free (the transpose of a ppermute is the
reverse ppermute), so one `jax.value_and_grad` covers the whole 1F-then-1B
schedule without hand-written bubbles.

Layout: `pp` shards the stacked block params' leading (layer) dim; `dp`
shards the batch. Embedding and head run outside the pipeline region,
replicated over pp (a production deployment would pin them to the first and
last stage; at dryrun scale replication is clearer and costs one broadcast).

For cross-HOST pipelining where the stages cannot share one jit program,
the compiled-DAG socket channels (ray_tpu/experimental/channel.py
SocketChannel) carry the stage handoffs instead — this module is the
within-slice (ICI) path.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.gpt2 import GPT2Config, Block
from ray_tpu.models.loss import loss_fn


def _stack_layers(per_layer_params):
    """[{layer params}...] -> one pytree with a leading layer dim."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer_params)


def pipeline_apply(mesh: Mesh, block_apply, stacked, h, num_micro: int):
    """Run `h` through pp-sharded stacked blocks with a GPipe schedule.

    mesh must have a `pp` axis; `dp` (if present) shards the batch dim of h.
    block_apply(layer_params, x) -> x applies ONE block. stacked is the
    full [n_layer, ...] parameter stack (sharded on dim 0 over pp).
    """
    pp = mesh.shape["pp"]
    has_dp = "dp" in mesh.axis_names and mesh.shape["dp"] > 1
    dp_spec = "dp" if has_dp else None

    def run_stack(local_stack, x):
        # my stage's n_layer/pp blocks, sequentially (scan over layers)
        def body(xc, p):
            return block_apply(p, xc), None

        out, _ = jax.lax.scan(body, x, local_stack)
        return out

    def stage(local_stack, h_loc):
        r = jax.lax.axis_index("pp")
        Bl, T, D = h_loc.shape
        mb = Bl // num_micro
        hm = h_loc.reshape(num_micro, mb, T, D)
        ticks = num_micro + pp - 1

        outs0 = jnp.zeros_like(hm)
        recv0 = jnp.zeros_like(hm[0])

        def tick(carry, t):
            recv, outs = carry
            # stage 0 ingests microbatch t; later stages take the ppermuted
            # output of their predecessor from the previous tick
            ingest = hm[jnp.clip(t, 0, num_micro - 1)]
            x = jnp.where(r == 0, ingest, recv)
            y = run_stack(local_stack, x)
            recv_next = jax.lax.ppermute(
                y, "pp", [(i, i + 1) for i in range(pp - 1)]
            )
            # the last stage finishes microbatch t-(pp-1) at tick t
            out_idx = t - (pp - 1)
            valid = (out_idx >= 0) & (r == pp - 1)
            idx = jnp.clip(out_idx, 0, num_micro - 1)
            outs = jnp.where(valid, outs.at[idx].set(y), outs)
            return (recv_next, outs), None

        (_, outs), _ = jax.lax.scan(
            tick, (recv0, outs0), jnp.arange(ticks)
        )
        # replicate the last stage's result over pp so the (replicated)
        # head/loss downstream sees identical values on every pp rank
        outs = jnp.where(r == pp - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, "pp")
        return outs.reshape(Bl, T, D)

    specs_stack = jax.tree.map(lambda _: P("pp"), stacked)
    fn = shard_map(
        stage,
        mesh=mesh,
        in_specs=(specs_stack, P(dp_spec, None, None)),
        out_specs=P(dp_spec, None, None),
        check_vma=False,
    )
    return fn(stacked, h)


class PipelineTrainStep:
    """Compiled (init, step) for GPT-2 on a (dp, pp) mesh.

    The counterpart of parallel.train_step.TrainStep for the pipeline axis:
    same state dict shape ({params, opt_state, step}), same step contract
    (state, {idx, targets}) -> (state, metrics).
    """

    def __init__(
        self,
        model_cfg: GPT2Config,
        mesh: Mesh,
        *,
        num_microbatches: Optional[int] = None,
        learning_rate: float = 3e-4,
        weight_decay: float = 0.1,
        grad_clip: float = 1.0,
    ):
        if "pp" not in mesh.axis_names:
            raise ValueError("PipelineTrainStep needs a 'pp' mesh axis")
        pp = mesh.shape["pp"]
        if model_cfg.n_layer % pp:
            raise ValueError(
                f"n_layer={model_cfg.n_layer} not divisible by pp={pp}"
            )
        self.model_cfg = model_cfg
        self.mesh = mesh
        self.pp = pp
        self.num_micro = num_microbatches or 2 * pp
        def decay_mask(params):
            # Stacking adds a leading layer dim, so inside `blocks` a bias
            # is 2-D and a kernel 3-D; the decay rule must match the
            # unstacked TrainStep (decay kernels, not biases/norms).
            def f(path, p):
                keys = [getattr(k, "key", "") for k in path]
                return p.ndim > (2 if "blocks" in keys else 1)

            return jax.tree_util.tree_map_with_path(f, params)

        self.optimizer = optax.chain(
            optax.clip_by_global_norm(grad_clip),
            optax.adamw(
                learning_rate, weight_decay=weight_decay, mask=decay_mask,
            ),
        )
        cfg = model_cfg
        block = Block(cfg)
        embed_dim = cfg.n_embd

        def init_fn(rng):
            T = min(8, cfg.block_size)
            k_wte, k_wpe, k_blocks, k_lnf = jax.random.split(rng, 4)
            wte = jax.random.normal(
                k_wte, (cfg.vocab_size, embed_dim), jnp.float32
            ) * 0.02
            wpe = jax.random.normal(
                k_wpe, (cfg.block_size, embed_dim), jnp.float32
            ) * 0.02
            x = jnp.zeros((2, T, embed_dim), cfg.dtype)
            per_layer = [
                block.init(jax.random.fold_in(k_blocks, i), x)["params"]
                for i in range(cfg.n_layer)
            ]
            params = {
                "wte": wte,
                "wpe": wpe,
                "blocks": _stack_layers(per_layer),
                "ln_f": {
                    "scale": jnp.ones((embed_dim,), jnp.float32),
                    "bias": jnp.zeros((embed_dim,), jnp.float32),
                },
            }
            return {
                "params": params,
                "opt_state": self.optimizer.init(params),
                "step": jnp.zeros((), jnp.int32),
            }

        # shardings: stacked blocks on pp (dim 0), everything else
        # replicated; batch on dp
        state_shape = jax.eval_shape(init_fn, jax.random.PRNGKey(0))

        def spec_of(path, _leaf):
            keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
            return P("pp") if "blocks" in keys else P()

        self.state_specs = jax.tree_util.tree_map_with_path(
            spec_of, state_shape
        )
        self.state_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), self.state_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        self._init = jax.jit(init_fn, out_shardings=self.state_shardings)

        has_dp = "dp" in mesh.axis_names and mesh.shape["dp"] > 1
        self.batch_sharding = NamedSharding(
            mesh, P("dp" if has_dp else None, None)
        )

        def block_apply(p, x):
            return block.apply({"params": p}, x)

        def forward(params, idx):
            B, T = idx.shape
            h = (
                params["wte"].astype(cfg.dtype)[idx]
                + params["wpe"].astype(cfg.dtype)[jnp.arange(T)][None]
            )
            h = pipeline_apply(
                mesh, block_apply, params["blocks"], h, self.num_micro
            )
            mean = h.mean(-1, keepdims=True)
            var = ((h - mean) ** 2).mean(-1, keepdims=True)
            h = (h - mean) * jax.lax.rsqrt(var + 1e-5)
            h = h * params["ln_f"]["scale"] + params["ln_f"]["bias"]
            return h.astype(jnp.float32) @ params["wte"].T  # tied head

        self.forward = forward

        def step_fn(state, batch):
            def loss_of(params):
                logits = forward(params, batch["idx"])
                return loss_fn(logits, batch["targets"])

            loss, grads = jax.value_and_grad(loss_of)(state["params"])
            updates, opt_state = self.optimizer.update(
                grads, state["opt_state"], state["params"]
            )
            params = optax.apply_updates(state["params"], updates)
            return (
                {"params": params, "opt_state": opt_state,
                 "step": state["step"] + 1},
                {"loss": loss, "grad_norm": optax.global_norm(grads)},
            )

        self._step = jax.jit(
            step_fn,
            out_shardings=(self.state_shardings, None),
            donate_argnums=(0,),
        )
        self._traced = False

    def init(self, rng):
        with self.mesh:
            return self._init(rng)

    def shard_batch(self, batch):
        return jax.device_put(batch, self.batch_sharding)

    def step(self, state, batch):
        B = batch["idx"].shape[0]
        dp = self.mesh.shape.get("dp", 1)
        if B % dp or (B // dp) % self.num_micro:
            raise ValueError(
                f"batch size {B} must divide by dp={dp} and the per-shard "
                f"batch ({B // dp if B % dp == 0 else '?'}) by "
                f"num_microbatches={self.num_micro}; pass a compatible "
                "batch size or num_microbatches to PipelineTrainStep"
            )
        if self._traced:
            return self._step(state, batch)
        with self.mesh:
            out = self._step(state, batch)
        self._traced = True
        return out
