"""Decoder trained by block diffusion (SDAR: a Qwen3-MoE decoder, grouped
queries with an RMSNorm over each head of q and k, in every layer a
top-8-of-128 SwiGLU expert layer, adapted from its autoregressive weights by
continued training under a masked-diffusion loss over blocks).

A sequence x0 of T tokens is cut into blocks of `block_length`. A step draws
a noise level t_b for each block and masks each token of the block with
probability t_b; the model sees the noised block in both directions beside
the clean blocks before it and is asked for the masked tokens at their own
positions, weighted 1/t_b. Every block is trained at once: the model runs on
the doubled stream [x_t | x_0] of 2T positions, both halves at positions
0 .. T-1, under ops/attention.py's `block_diffusion_mask`; the head and the
loss run on the noised half alone.

The forward process and the loss are the masked-diffusion convention's (MDLM,
BD3-LM, LLaDA): t_b = eps + (1 - eps) u with u uniform, one level a
(sequence, block); a token is masked iff its own uniform draw is under t_b;
loss = sum over masked positions of CE_i / t_blk(i), over the count of
positions in noised blocks. Block 0 of every sequence is left clean and
carries no loss (the prompt's stand-in). The noise of a step is a function of
the step's count and the batch's shape alone (`noise`), drawn on the device:
no batch carries it, and whoever knows the recipe draws the same.

Built from models/layers.py's pieces: `LlamaAttention` told that its input is
a doubled stream (`blocks`), `RMSNorm`, ops/moe.py's `ExpertShare` (this
program computes the experts it holds, `first_expert`, `num_held`), the
untied head.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.models import Family, layers, remat
from ray_tpu.models.layers import LlamaAttention, RMSNorm
from ray_tpu.models.loss import weighted_loss
from ray_tpu.ops import moe
from ray_tpu.ops.moe import EXPERT_SHARE_SHARDING_PATTERNS, ExpertShare
from ray_tpu.parallel.mesh import ShardingRules, pin


@dataclasses.dataclass(frozen=True)
class SDARConfig:
    vocab_size: int = 151936
    block_size: int = 32768
    n_layer: int = 48
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    n_embd: int = 2048
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    expert_dim: int = 768
    num_experts: int = 128  # the router's width
    top_k: int = 8
    first_expert: int = 0
    num_held: Optional[int] = None  # experts computed here; None: all
    block_length: int = 4
    mask_token_id: int = 151669
    noise_eps: float = 1e-3  # the least noise level of a block
    noise_seed: int = 0
    dtype: Any = jnp.bfloat16
    use_flash_attention: bool = True
    attn_fn: Any = None  # a mesh's own attention: none takes a doubled stream yet
    # as models/mellum.py's: the rows an expert works on are the router's doing
    lr_warmup_steps: int = 2000

    family: ClassVar[Family]  # what TrainStep asks of it: set at the foot of this file

    @property
    def experts_held(self) -> int:
        return self.num_experts if self.num_held is None else self.num_held

    def layer_matmul_params(self) -> int:
        """q and o (d x heads x head_dim), k and v (d x kv heads x head_dim),
        the router, and the expert matrices a position meets at even routing
        (top_k experts, of which held / num_experts are here)."""
        d, hd = self.n_embd, self.head_dim
        attn = 2 * d * self.n_head * hd + 2 * d * self.n_kv_head * hd
        experts = self.top_k * self.experts_held / self.num_experts * 3 * d * self.expert_dim
        return int(attn + d * self.num_experts + experts)

    def flops_per_token(self, seq_len: int) -> int:
        """Of a data token, which costs two positions of the stream: 6 x the
        layers' matmul parameters twice and the head's once (it sees the
        noised half alone), + 12 x heads x head_dim x the keys its two
        queries see, T + L on average in every layer (`block_diffusion_mask`
        shows T^2 + T L pairs of the (2T)^2). The embedding multiplies
        nothing."""
        keys = seq_len + self.block_length
        return int(6 * (2 * self.n_layer * self.layer_matmul_params()
                        + self.vocab_size * self.n_embd)
                   + self.n_layer * 12 * self.n_head * self.head_dim * keys)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, block_size=128, n_layer=2, n_head=4, n_kv_head=2, head_dim=16,
                    n_embd=48, expert_dim=32, num_experts=8, top_k=2, mask_token_id=511)
        base.update(kw)
        return cls(**base)


def noise(cfg: SDARConfig, shape, step):
    """(masked (B, T) bool, weight (B, T) float32) of the step with count
    `step` on a (B, T) batch: the key is fold_in(PRNGKey(noise_seed), step),
    split in two; the first half draws u (B, blocks) uniform in [0, 1), a
    block's level t = eps + (1 - eps) u; the second draws r (B, T) uniform,
    and a token is masked iff r < its block's t and it lies after block 0.
    The weight is 1/t on a masked token and 0 elsewhere."""
    b, t = shape
    length = cfg.block_length
    levels, tokens = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(cfg.noise_seed), step))
    level = cfg.noise_eps + (1 - cfg.noise_eps) * jax.random.uniform(
        levels, (b, -(-t // length)), jnp.float32)
    level = jnp.repeat(level, length, axis=1)[:, :t]
    masked = (jax.random.uniform(tokens, (b, t), jnp.float32) < level) & (jnp.arange(t) >= length)
    return masked, jnp.where(masked, 1 / level, 0.0)


def noised_positions(cfg: SDARConfig, shape) -> int:
    """The positions of a (B, T) batch in noised blocks: what the loss is a
    mean over (1 where block 0 is all there is: a trace at init's length)."""
    b, t = shape
    return max(1, b * (t - cfg.block_length))


def mask_experts(cfg: SDARConfig, layer: int):
    """The `top_k` experts of `layer` that `placed_row` sends the mask token
    to: one in every num_experts / top_k of the router's width (number `layer`
    of each such run), so that a rank of expert parallelism over up to `top_k`
    ranks, which holds a run of neighbours, holds its even share of them."""
    run = cfg.num_experts // cfg.top_k
    return jnp.arange(cfg.top_k) * run + layer % run


def placed_row(cfg: SDARConfig, routers):
    """The mask token's initial row of the embedding, from the layers' router
    kernels as they were initialised: the sum of the columns of
    `mask_experts`, scaled to unit mean square like every other row. Those
    columns' logits on it are sqrt(d / (layers x top_k)), 7.2 at the
    published widths where every other expert's is a unit normal draw, so in
    every layer the mask token goes to `mask_experts`.

    Why a rule: half the noised half's positions hold that one token and, with
    weights that mix little, route alike in every layer, so a layer's rows on
    this chip are the others' even share and 4,096 for each of the mask
    token's 8 experts that the chip holds. Drawn like any row that is 0 to 4
    of them by a seed's luck: over 8 seeds on a v5e a layer's rows read 0.69
    to 1.63 of the even load and the seeds' rates spread 1.0% (my chip runs,
    PR 61, calls 3 and 4). The rule gives every rank of ep = 8 one of the 8
    in every layer: an average rank's load by construction. A deployment's
    trained row goes where training left it, and its slowest rank sets its
    step."""
    want = sum(router[:, mask_experts(cfg, layer)].sum(axis=1)
               for layer, router in enumerate(routers))
    return want * jax.lax.rsqrt(jnp.mean(want * want))


class SDARBlock(nn.Module):
    config: SDARConfig
    stream: Any = None  # the residual stream's sharding, or None (models/llama.py)
    products_kept: bool = True  # ops/moe.py:ExpertShare.products_kept

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = pin(x, self.stream)
        attn = LlamaAttention(cfg, qk_norm=True, blocks=cfg.block_length, name="attn")
        x = pin(x + attn(RMSNorm(cfg.rms_eps, name="attn_norm")(x)), self.stream)
        experts = ExpertShare(cfg.n_embd, cfg.expert_dim, cfg.num_experts, cfg.top_k,
                              cfg.first_expert, cfg.num_held, cfg.dtype,
                              products_kept=self.products_kept, name="moe")
        return pin(x + experts(RMSNorm(cfg.rms_eps, name="moe_norm")(x)), self.stream)


# What a block's remat saves after the first rung, with models/mellum.py's
# worths (ms of a step spared for a GiB held: the same layer at the same
# widths, read there in PR 33 and PR 45; not measured in this family's cell).
REMAT_RUNGS = ((("attn_q", "attn_k", "attn_v"), 37.6),
               (("moe_gate",), 4.1), (("moe_up",), 4.1), (("moe_out",), 5.8))


def remat_plan(cfg: SDARConfig, shape: remat.StepShape, limit) -> remat.RematPlan:
    """What the blocks of a step of this shape save across remat, under a
    chip's `limit` of bytes. The blocks work on the doubled stream, twice the
    batch's length, by models/mellum.py's reckoning of the same layer (the
    expert layer's backward in 6.5 buffers of a row an assignment); the head
    and its logits see the noised half alone."""
    shape = shape._replace(seq_len=2 * shape.seq_len)
    d, hd = cfg.n_embd, cfg.head_dim
    itemsize = jnp.dtype(cfg.dtype).itemsize
    layer = (2 * d * cfg.n_head * hd + 2 * d * cfg.n_kv_head * hd + d * cfg.num_experts
             + cfg.experts_held * 3 * d * cfg.expert_dim)
    positions = shape.rows * shape.seq_len
    name_bytes = remat.attention_bytes(shape, cfg.n_head, hd, itemsize)
    name_bytes.update(moe.named_bytes(positions, cfg.top_k, cfg.experts_held, cfg.num_experts,
                                      d, cfg.expert_dim, itemsize))
    held = remat.held_bytes(
        shape, params=cfg.n_layer * layer + 2 * cfg.vocab_size * d, width=d,
        vocab=cfg.vocab_size, n_layer=cfg.n_layer, itemsize=itemsize,
        block=int(6.5 * positions * cfg.top_k * d * itemsize))
    held = held._replace(logits=held.logits // 2, head=held.head // 2)
    return remat.plan(REMAT_RUNGS, name_bytes, cfg.n_layer, held, limit,
                      remat.FIRST_RUNG + (moe.ROUTE_PLAN,))


class SDAR(nn.Module):
    config: SDARConfig
    stream: Any = None  # parallel/mesh.py:stream_sharding of the step's mesh

    @nn.compact
    def __call__(self, idx, step=0):
        """idx (B, T) clean tokens -> (logits (B, T, vocab) of the noised
        half, weight (B, T)): what `objective` needs. `step` is the count the
        step's noise is drawn from; a caller that gives none sees step 0's."""
        cfg = self.config
        b, t = idx.shape
        with jax.named_scope("sdar.noise"):
            masked, weight = noise(cfg, idx.shape, step)
            stream = jnp.concatenate([jnp.where(masked, cfg.mask_token_id, idx), idx], axis=1)
            count = noised_positions(cfg, idx.shape)
            self.sow("diffusion", "masked_share", masked.sum().astype(jnp.float32) / count)
            self.sow("diffusion", "weight_max", weight.max())
        # unit variance an element, as models/mellum.py's and for its reason
        x = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype, name="tok_emb",
                     embedding_init=nn.initializers.normal(1.0))(stream)
        keep = remat.block_policy(remat_plan, cfg, idx.shape, self.stream)
        products = remat.traced(cfg).saved_in(*moe.KEPT_PRODUCTS)
        for i in range(cfg.n_layer):
            x = nn.remat(SDARBlock, policy=keep[i])(
                cfg, self.stream, products[i], name=f"h_{i}")(x)
        if self.is_initializing():  # the mask token's row follows the routers just drawn
            routers = [self.get_variable("params", f"h_{i}")["moe"]["router"]["kernel"]
                       for i in range(cfg.n_layer)]
            table = self.get_variable("params", "tok_emb")["embedding"]
            self.put_variable("params", "tok_emb", {
                "embedding": table.at[cfg.mask_token_id].set(placed_row(cfg, routers))})
        x = RMSNorm(cfg.rms_eps, name="final_norm")(x[:, :t])
        return layers.untied_head(self, cfg, x), weight


SDAR_SHARDING_RULES = ShardingRules(
    EXPERT_SHARE_SHARDING_PATTERNS + layers.UNTIED_HEAD_SHARDING_PATTERNS
    + layers.LLAMA_SHARDING_PATTERNS, default=P())

SOWN = ("moe_load", "diffusion")


def objective(model, params, batch, step):
    """`Family.objective`: the diffusion loss of the step with count `step`
    on the clean tokens batch["idx"]; the batch's next-token targets are
    none of its (a masked token is asked for at its own position)."""
    idx = batch["idx"]
    (logits, weight), sown = model.apply({"params": params}, idx, step, mutable=list(SOWN))
    with jax.named_scope("loss"), jax.named_scope("loss.diffusion"):
        return weighted_loss(logits, idx, weight, noised_positions(model.config, idx.shape)), sown


def step_metrics(cfg, sown, params, tokens):
    """`Family.metrics`: the expert layers', which saw two positions a token,
    and of the step's noise the share of the noised blocks' positions that
    were masked and the largest weight 1/t on any."""
    metrics = moe.step_metrics(cfg, sown, params, 2 * tokens)
    metrics.update({f"diffusion_{name}": leaves[0]
                    for name, leaves in sown["diffusion"].items()})
    return metrics


SDARConfig.family = Family(module=SDAR, rules=SDAR_SHARDING_RULES, sown=SOWN,
                           metrics=step_metrics, objective=objective)
