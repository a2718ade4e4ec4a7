"""Mixture-of-Experts FFN with expert parallelism, TPU-first.

The reference has no MoE/expert-parallel machinery at all (SURVEY §2.4:
"Expert parallel (EP/MoE): Absent") — this is green-field, built the way
TPU MoE is actually done (Switch/Mixtral-style, the Mesh-TensorFlow dense
dispatch/combine formulation used by t5x/flaxformer): top-k routing with a
static per-expert capacity, dispatch/combine as einsums so everything is
static-shaped and XLA lowers the expert-sharded contractions to
all-to-alls over the 'ep' mesh axis — no ragged ops, no host control flow.

Layout: expert weights carry a leading E dim sharded on 'ep'
(``MOE_SHARDING_RULES``); tokens stay sharded on dp/sp. Under pjit the
dispatch einsum becomes the a2a scatter and the combine einsum the a2a
gather, riding ICI.

All of the above is `MoE`'s alone. `ExpertShare`, further down, is one
chip's share of a dropless expert layer (no capacity, nothing dropped), its
experts of one of two forms (`ExpertForm`): SwiGLU, or two matrices under an
activation.
"""

from __future__ import annotations

import dataclasses
import functools
import operator
from typing import Any, Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    # capacity per expert = ceil(top_k * tokens * capacity_factor / E)
    capacity_factor: float = 1.25
    # Switch-style load-balance auxiliary loss weight
    aux_loss_weight: float = 0.01


def top_k_routing(
    probs: jnp.ndarray, k: int, capacity: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """probs (B,S,E) → (dispatch (B,S,E,C) bool-ish, combine (B,S,E,C)).

    Tokens beyond an expert's capacity are dropped (their combine weight is
    zero → they pass through the residual only), earlier sequence positions
    win — the standard static-capacity contract.
    """
    B, S, E = probs.shape
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # (B,S,k)
    # renormalize the kept gates so they sum to 1 per token
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9
    )
    dispatch = jnp.zeros((B, S, E, capacity), dtype=probs.dtype)
    combine = jnp.zeros((B, S, E, capacity), dtype=probs.dtype)
    # tokens already admitted per (batch, expert)
    used = jnp.zeros((B, E), dtype=jnp.int32)
    for i in range(k):
        mask_i = jax.nn.one_hot(gate_idx[..., i], E, dtype=jnp.int32)  # (B,S,E)
        # position of each token within its expert's buffer
        pos_i = jnp.cumsum(mask_i, axis=1) - 1 + used[:, None, :]
        keep = mask_i * (pos_i < capacity)
        used = used + keep.sum(axis=1)
        pos_oh = jax.nn.one_hot(pos_i, capacity, dtype=probs.dtype)  # (B,S,E,C)
        sel = keep.astype(probs.dtype)[..., None] * pos_oh
        dispatch = dispatch + sel
        combine = combine + sel * gate_vals[..., i, None, None]
    return dispatch, combine


def load_balance_loss(probs: jnp.ndarray, dispatch: jnp.ndarray) -> jnp.ndarray:
    """Switch aux loss: E * Σ_e (token fraction_e · mean prob_e)."""
    E = probs.shape[-1]
    tokens_per_expert = dispatch.sum(axis=(1, 3))  # (B,E)
    total = jnp.maximum(tokens_per_expert.sum(axis=-1, keepdims=True), 1.0)
    fraction = tokens_per_expert / total
    mean_prob = probs.mean(axis=1)  # (B,E)
    return E * (fraction * mean_prob).sum(axis=-1).mean()


class MoE(nn.Module):
    """Drop-in FFN replacement: (B,S,C) → (B,S,C) plus an aux loss that the
    caller adds to the objective (collected via self.sow 'losses')."""

    d_model: int
    d_ff: int
    moe: MoEConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, deterministic=True):
        B, S, C = x.shape
        E, k = self.moe.num_experts, self.moe.top_k
        capacity = max(
            1, int(-(-k * S * self.moe.capacity_factor // E))
        )
        # Router always in fp32: tiny matmul, big numerical leverage.
        gate_logits = nn.Dense(
            E, dtype=jnp.float32, param_dtype=jnp.float32, name="router"
        )(x.astype(jnp.float32))
        probs = jax.nn.softmax(gate_logits, axis=-1)
        dispatch, combine = top_k_routing(probs, k, capacity)
        aux = load_balance_loss(probs, dispatch) * self.moe.aux_loss_weight
        self.sow("losses", "moe_aux", aux)

        wi = self.param(
            "wi",
            nn.initializers.lecun_normal(),
            (E, C, self.d_ff),
            jnp.float32,
        )
        wo = self.param(
            "wo",
            nn.initializers.lecun_normal(),
            (E, self.d_ff, C),
            jnp.float32,
        )
        dispatch = dispatch.astype(self.dtype)
        combine = combine.astype(self.dtype)
        xd = x.astype(self.dtype)
        # scatter tokens to experts (a2a over 'ep' under pjit)
        expert_in = jnp.einsum(
            "bsec,bsm->ebcm", dispatch, xd, preferred_element_type=self.dtype
        )
        h = jnp.einsum(
            "ebcm,emf->ebcf",
            expert_in,
            wi.astype(self.dtype),
            preferred_element_type=jnp.float32,
        )
        h = nn.gelu(h.astype(self.dtype), approximate=True)
        out = jnp.einsum(
            "ebcf,efm->ebcm",
            h,
            wo.astype(self.dtype),
            preferred_element_type=jnp.float32,
        ).astype(self.dtype)
        # gather back (the reverse a2a)
        return jnp.einsum(
            "bsec,ebcm->bsm", combine, out, preferred_element_type=jnp.float32
        ).astype(x.dtype)


# --------------------------------------------------------------------------
# One chip's share of a dropless expert layer
# --------------------------------------------------------------------------
#
# `MoE` above gives every expert a static capacity and drops what overflows.
# `ExpertShare` drops nothing: the k assignments of every token are sorted
# by expert, the experts held here work on their ragged groups of rows with
# grouped matmuls, and each token gets its rows back weighted by its gates.
# The router is as wide as the layer (every expert, held here or not); the
# gates are renormalised over all k chosen; what the experts held elsewhere
# would add is left out, and nothing here stands in for them. An expert is
# of one of two forms (`ExpertForm`): SwiGLU, W_down (silu(W_gate u) * W_up
# u), three matrices, or two matrices under an activation, W_down act(W_up
# u), here relu squared: the same stages with a product fewer.


def route_plan(idx, first_expert, num_held):
    """idx (N, k): the experts each token chose, of the whole layer. Returns
    where each assignment goes when those to experts first_expert ..
    first_expert + num_held - 1 are sorted by expert and put first:

        order (N*k,)       the assignment (token * k + j) at each sorted row
        inv (N, k)         the sorted row of each assignment
        held (N, k)        whether the assignment's expert is held here
        group_sizes (num_held,)   rows of each held expert, in order
        by_token           two (N*k,) arrays: the assignments held here in
                           token order, then the others, and the sorted
                           row of each: the sorted rows' way back to tokens

    Rows from group_sizes.sum() on belong to experts held elsewhere."""
    n, k = idx.shape
    local = idx - first_expert
    held = (local >= 0) & (local < num_held)
    key = jnp.where(held, local, num_held).reshape(n * k)
    order = jnp.argsort(key, stable=True)
    inv = jnp.argsort(order)
    group_sizes = (key[:, None] == jnp.arange(num_held)[None, :]).sum(0, dtype=jnp.int32)
    # a third sort, with the rows as its load: looking them up afterwards
    # would be a gather of single integers, slow on a TPU
    _, *by_token = jax.lax.sort(((key == num_held).astype(jnp.int32), jnp.arange(n * k), inv),
                                num_keys=1, is_stable=True)
    return order, inv.reshape(n, k), held, group_sizes, tuple(by_token)


def token_order(plan, rows):
    """The first `rows` sorted rows (every row routed here lies within them)
    in token order, from a `route_plan`. For each of `rows` slots:

        source (rows,)   the sorted row that stands there
        mine (rows,)     its assignment (token * k + j), N * k where the
                         slot is nobody's

    A token's rows are adjacent, in the order of its k choices, and the slots
    past group_sizes.sum() are nobody's. No row of C moves here."""
    _, inv, _, group_sizes, (by_token, rows_by_token) = plan
    here = jnp.arange(rows) < group_sizes.sum()
    return jnp.where(here, rows_by_token[:rows], 0), jnp.where(here, by_token[:rows], inv.size)


# Tokens a grid step of `sum_by_token`'s kernel sums into, and the rows it
# reads. A step's product is tokens x rows x C whatever meets in it, and the
# steps are N / tokens + rows / rows' at most, so the MXU's work is (rows *
# tokens + N * rows') * C a pass: small blocks do less of it in more steps.
_SUM_TOKENS, _SUM_ROWS = 128, 128


def _token_sum_kernel(block_ref, chunk_ref, live_ref, tok_ref, *refs, weighted):
    """One grid step: the rows of chunk chunk_ref[g] that belong to the
    tokens of block block_ref[g], each times its weight, added to the block's
    float32 sums: a product on the MXU against a (tokens, rows) matrix that
    holds a row's weight at its token and 0 elsewhere. The weight of a row
    is its token's gate for the row's place j among the token's k choices,
    taken from the block's own (tokens, k) gates."""
    j_ref, gates_ref, y_ref, o_ref, acc_ref = refs if weighted else (None, None, *refs)
    g = pl.program_id(0)
    block = block_ref[g]

    @pl.when((g == 0) | (block_ref[jnp.maximum(g - 1, 0)] != block))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live_ref[g] == 1)
    def _():
        tokens, rows = acc_ref.shape[0], tok_ref.shape[-1]
        ids = block * tokens + jax.lax.broadcasted_iota(jnp.int32, (tokens, rows), 0)
        own = tok_ref[...] == ids
        if weighted:
            at = jnp.zeros((tokens, rows), jnp.float32)
            for j in range(gates_ref.shape[1]):
                at = jnp.where(own & (j_ref[...] == j), gates_ref[:, j:j + 1], at)
        else:
            at = jnp.where(own, 1.0, 0.0)
        y = y_ref[...]
        if y.dtype != jnp.bfloat16:
            acc_ref[...] += jnp.dot(at, y.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST,
                                    preferred_element_type=jnp.float32)
            return
        # A float32 weight is three bf16 parts exactly, a bf16 row times each
        # is exact in float32 and the MXU sums in float32: no weight is
        # rounded to one bf16 pass. A weight of 1 is its first part alone.
        for _ in range(3 if weighted else 1):
            part = at.astype(jnp.bfloat16)
            acc_ref[...] += jnp.dot(part, y, preferred_element_type=jnp.float32)
            at = at - part.astype(jnp.float32)

    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "dtype", "interpret"))
def _token_sum_call(y, mine, gates, *, n, dtype, interpret):
    """The kernel over y (rows, C) already in token order, `mine` the
    assignment at each row: (n, C) in `dtype`. The grid walks the pairs of a
    block of tokens and a chunk of rows that meet, block by block: both run
    the same way, so there are N / tokens + rows / rows' pairs at most, and
    a block with no row takes one step and writes zeros. The pairs are three
    short arrays of integers made here from the rows' tokens and prefetched,
    as megablox's group metadata is; an output block stays in VMEM over its
    pairs. Traced once, under this `jit`, and called a layer and pass."""
    rows, c = y.shape
    k = 1 if gates is None else gates.shape[1]
    tb, r = _SUM_TOKENS, _SUM_ROWS
    blocks, chunks = n // tb, rows // r
    tokens = mine // k
    # rows before each block of tokens, then the chunks a block's rows lie in
    start = (tokens[None, :] < (jnp.arange(blocks + 1) * tb)[:, None]).sum(1, dtype=jnp.int32)
    first = jnp.minimum(start[:-1] // r, chunks - 1)
    last = jnp.minimum(jnp.maximum(start[1:] - 1, start[:-1]) // r, chunks - 1)
    count = last - first + 1
    ends = jnp.cumsum(count)
    g = jnp.arange(blocks + chunks, dtype=jnp.int32)
    block = jnp.minimum((ends[None, :] <= g[:, None]).sum(1, dtype=jnp.int32), blocks - 1)
    within = g - (ends - count)[block]
    chunk = jnp.minimum(first[block] + within, last[block])
    # the steps past the last pair do nothing, nor does a block with no row
    live = ((within < count[block]) & (start[1:] > start[:-1])[block]).astype(jnp.int32)

    by_chunk = pl.BlockSpec((None, 1, r), lambda g, block, chunk, live: (chunk[g], 0, 0))
    operands, specs = [tokens.reshape(chunks, 1, r)], [by_chunk]
    if gates is not None:
        operands += [(mine % k).reshape(chunks, 1, r), gates]
        specs += [by_chunk, pl.BlockSpec((tb, k), lambda g, block, chunk, live: (block[g], 0))]
    return pl.pallas_call(
        functools.partial(_token_sum_kernel, weighted=gates is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(blocks + chunks,),
            in_specs=specs + [pl.BlockSpec((r, c), lambda g, block, chunk, live: (chunk[g], 0))],
            out_specs=pl.BlockSpec((tb, c), lambda g, block, chunk, live: (block[g], 0)),
            scratch_shapes=[pltpu.VMEM((tb, c), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n, c), dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=48 << 20),
        interpret=interpret, name="moe_token_sum",
    )(block, chunk, live, *operands, y)


def sum_by_token(y, back, n, gates=None, dtype=jnp.float32, *, interpret=None):
    """For each of n tokens the sum of its rows of y (rows, C), each times
    its weight: (n, C) in `dtype`, every product and every sum float32.
    `back` is the buffer's `token_order`; the weight of a row is its
    assignment's of `gates` (n, k) float32; where gates is None it is 1 and
    `back` names tokens, not assignments (k = 1). What y holds at a slot
    that is nobody's, defined or not, stays out (such a slot reads the first
    sorted row at weight 0, and that row is somebody's wherever any is), and
    a token with no row gets exact zeros.

    Written from the buffer's side: one gather of the buffer's `rows` rows
    into token order, then sums of adjacent rows, at most k a token. Both
    directions of the expert layer that end at the tokens are this
    (`combine_rows` forward, `dispatch_rows` backward); from the tokens' side
    they gathered a row for each of the N * k assignments and zeroed those
    held elsewhere (mellum's cell: 131,072 rows a pass from a buffer of
    49,152, too large for XLA to hold in VMEM as it holds the other cells').

    On a TPU and at whole blocks the sums are `_token_sum_kernel`'s;
    elsewhere XLA's sorted segment sum, a scatter-add (serial on a TPU: what
    the kernel is there for). `interpret` forces the kernel (True: in
    interpret mode), for the tests."""
    from ray_tpu.ops.attention import _on_tpu

    source, mine = back
    rows = y[source]
    whole = n % _SUM_TOKENS == 0 and y.shape[0] % _SUM_ROWS == 0
    if interpret is not None or (_on_tpu() and whole):
        return _token_sum_call(rows, mine, gates, n=n, dtype=jnp.dtype(dtype),
                               interpret=bool(interpret))
    rows, k = rows.astype(jnp.float32), 1 if gates is None else gates.shape[1]
    if gates is not None:  # nobody's slots fall into a segment that is cut off
        rows = rows * gates.reshape(n * k)[jnp.minimum(mine, n * k - 1)][:, None]
    return jax.ops.segment_sum(rows, mine // k, n + 1, indices_are_sorted=True)[:n].astype(dtype)


# Gathers of rows along the plan, with backward passes that move no more
# rows than the buffer has either. Towards the buffer (`dispatch_rows`
# forward, `combine_rows` backward) a gather in the sorted rows' own order;
# towards the tokens (`combine_rows` forward, `dispatch_rows` backward)
# `sum_by_token` over the buffer's rows in token order. What autodiff would
# write as a scatter-add of N*k rows (serial on a TPU) is never one. Rows of
# experts held elsewhere are never computed: `held` and the token order keep
# whatever lies there out of both directions.


@jax.custom_vjp
def dispatch_rows(x, order, held, back):
    """x (N, C) -> (rows, C): the token of each sorted row. `order` may be
    cut to the buffer's first rows: the rows of experts held here come
    first, so what is cut is nobody's. `back`: the buffer's `token_order`."""
    return x[order // held.shape[1]]


def _dispatch_fwd(x, order, held, back):
    return dispatch_rows(x, order, held, back), (held, back)


def _dispatch_bwd(res, g):
    held, (source, mine) = res
    back = source, mine // held.shape[1]  # a weight of 1 whatever the choice's place
    return sum_by_token(g, back, held.shape[0], dtype=g.dtype), None, None, None


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def combine_rows(y, gates, order, inv, held, back, dtype=jnp.float32):
    """y (rows, C) sorted rows, gates (N, k) float32 -> (N, C): each token's
    held rows, weighted by its gates and summed in float32, in `dtype`.
    `inv` clipped into the buffer, `back` its `token_order`. A caller that
    would round the sums to the stream's dtype anyway asks for it here: the
    values are the same, and the gradient then comes back in that dtype, so
    the backward pass gathers rows half as wide."""
    return sum_by_token(y, back, gates.shape[0], gates, dtype)


def _combine_fwd(y, gates, order, inv, held, back, dtype):
    return combine_rows(y, gates, order, inv, held, back, dtype), (y, gates, order, inv, held)


def _combine_bwd(dtype, res, g):
    y, gates, order, inv, held = res
    k = inv.shape[1]
    # in the sorted rows' own order: one gather of the buffer's rows
    g_rows = g[order // k].astype(jnp.float32)
    sorted_gates = jnp.where(held, gates, 0).reshape(-1)[order]
    d_y = (g_rows * sorted_gates[:, None]).astype(y.dtype)
    d_gate_rows = (y.astype(jnp.float32) * g_rows).sum(-1)
    d_gates = jnp.where(held, d_gate_rows[inv], 0)
    return d_y, d_gates, None, None, None, None


combine_rows.defvjp(_combine_fwd, _combine_bwd)


# Rows a tile of the grouped matmul takes, and the tiles of its other two
# dimensions (megablox's (m, k, n) tiling). Measured on the v5e at the
# benchmark's shapes, 32,768 rows of 131,072 in 16 groups, (2304, 896) and
# (896, 2304) (my chip run, PR 29): (512, 1024, 1024) 1.28-1.30 ms forward
# and 2.9-3.5 ms with both gradients; (512, 512, 512) 1.33-1.42 and 3.2-3.5;
# (256, 1024, 1024) 1.38-1.52 and 3.1-3.7. XLA's own `jax.lax.ragged_dot`
# at the same shapes: 3.97-5.53 ms and 10.7-10.8 ms.
#
# At experts wider than a tile and no multiple of it, 8 groups of 2,048 rows
# in a buffer of 24,576, (2048, 1792) and (1792, 2048) (my chip run, PR 41):
# (512, 1024, 1024) 0.80-0.81 ms forward and 1.66-1.67 ms for both gradients,
# the 768-wide remainder tile and all; an 896-wide tile on the 1,792 (two even
# tiles) 0.75 ms forward and 1.81-1.84 for the gradients, which megablox runs
# under the same tuple with the widths changing places; (512, 512, 512) 1.01-
# 1.04 and 1.95; 1,792 or 2,048 in one tile does not fit VMEM. So one tiling
# for every width the cells have.
_GMM_TILING = (512, 1024, 1024)


def _megablox_fits(rows):
    from ray_tpu.ops.attention import _on_tpu

    return _on_tpu() and rows % _GMM_TILING[0] == 0


def grouped_matmul(lhs, rhs, group_sizes):
    """lhs (M, K) rows sorted by group, rhs (G, K, N), group_sizes (G,):
    row r of group g times rhs[g]; operands in lhs's dtype, float32
    accumulation, out in lhs's dtype. Rows past group_sizes.sum() belong to
    no group: they are not worked on, and what the result holds there is
    not defined.

    On a TPU the kernel is megablox's (jax.experimental.pallas.ops.tpu:
    `gmm` forward and for the gradient to the rows, `tgmm` for the gradient
    to the matrices; the grid follows the groups' tiles, so the time follows
    the rows routed here and not M). Megablox gives its calls no name of
    the repo's; the compiler names them gmm and tgmm, which is how the
    benchmark's moe_gmm metrics find them. Elsewhere, and where M is no
    multiple of the row tile, `jax.lax.ragged_dot`."""
    rhs = rhs.astype(lhs.dtype)
    if _megablox_fits(lhs.shape[0]):
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, _GMM_TILING)
    out = jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=jnp.float32)
    return out.astype(lhs.dtype)


def _grouped_matmul_grads(lhs, rhs, group_sizes, g):
    """The gradients of `grouped_matmul(lhs, rhs, group_sizes)` to lhs and to
    rhs from g, the gradient of its result, with no forward call beside
    them: on a TPU `_megablox_grads`, elsewhere `jax.lax.ragged_dot`'s
    transposes."""
    if _megablox_fits(lhs.shape[0]):
        return _megablox_grads(lhs, rhs, group_sizes, g)
    return jax.vjp(lambda lhs, rhs: grouped_matmul(lhs, rhs, group_sizes), lhs, rhs)[1](g)


def _megablox_grads(lhs, rhs, group_sizes, g, interpret=False):
    """What the rule of `megablox.ops.gmm` runs for g, written out because
    `jax.vjp` of it would trace the forward call too: `gmm` on the transposed
    matrices for the rows, `tgmm` for the matrices, in lhs's dtype
    (tests/test_moe.py holds it to that rule's own results, bit for bit, in
    interpret mode: a jax whose rule changes shows there)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm  # the kernels, no rule

    d_lhs = gmm(g, rhs.astype(lhs.dtype), group_sizes, lhs.dtype, _GMM_TILING,
                transpose_rhs=True, interpret=interpret)
    d_rhs = tgmm(lhs.swapaxes(0, 1), g, group_sizes, lhs.dtype, _GMM_TILING,
                 num_actual_groups=rhs.shape[0], interpret=interpret)
    return d_lhs, d_rhs.astype(rhs.dtype)


# Rows of the buffer the experts work in, over the rows an even routing
# would send here (rounded up to the row tile). At initialisation the
# benchmark's deepest layer sends 0.21 to 0.36 of its assignments to a
# quarter of the experts, by seed (my chip run, PR 29): 1.25 would send
# one seed in four through the buffer of every row.
_ROW_HEADROOM = 1.5


class ExpertForm(NamedTuple):
    """What an expert computes of a row u: W_down hidden(W_m u for each m of
    `matrices`), d_model -> d_ff -> d_model. The weights' tree holds a leaf
    for each of `matrices` and "down", and no other."""

    matrices: Tuple[str, ...]
    hidden: Callable  # the products of `matrices`, in order -> the down matrix's operand

    @property
    def products(self) -> Tuple[str, ...]:
        """Names of the layer's products for a block's checkpoint policy
        (models/remat.py), a matrix each, the down matrix's last: under
        `nn.remat` the ones the policy keeps are held from the forward pass,
        the others are made again by `_experts_in_buffer`'s forward rule. A
        layer whose family's plan keeps none names none
        (`ExpertShare.products_kept`)."""
        return tuple(f"moe_{m}" for m in self.matrices) + ("moe_out",)


SWIGLU = ExpertForm(("gate", "up"), lambda gate, up: nn.silu(gate) * up)
RELU2 = ExpertForm(("up",), lambda up: jnp.square(nn.relu(up)))

KEPT_PRODUCTS = SWIGLU.products  # ("moe_gate", "moe_up", "moe_out")
ROUTE_PLAN = "moe_plan"  # the choices and `route_plan`'s arrays, under one name


def buffer_rows(n, top_k, num_held, num_experts):
    """Rows of the buffer with headroom for a layer of n tokens, or n * top_k
    where that is no smaller: then every assignment is the one buffer."""
    tile = _GMM_TILING[0]
    room = -(-int(_ROW_HEADROOM * n * top_k * num_held / num_experts) // tile) * tile
    return min(room, n * top_k)


def named_bytes(n, top_k, num_held, num_experts, d_model, d_ff, itemsize, form=SWIGLU):
    """Bytes a layer of n tokens of what `ExpertShare` names for a block's
    checkpoint policy (models/remat.py): ROUTE_PLAN, the choices and the
    plan's arrays (five int32 and a bool an assignment), and the form's
    products, rows of the buffer with headroom in the compute dtype (nothing
    where the one buffer is every assignment: that layer names no product)."""
    rows = buffer_rows(n, top_k, num_held, num_experts)
    rows = rows if rows < n * top_k else 0
    widths = (d_ff,) * len(form.matrices) + (d_model,)  # the products' order
    return {ROUTE_PLAN: n * top_k * (5 * 4 + 1),
            **{name: rows * width * itemsize for name, width in zip(form.products, widths)}}


# The held experts on a buffer of the first `rows` sorted rows (every row
# routed here lies within them), in the three stages whose results the
# backward pass reads: the form's first matrices' products of the rows
# gathered (gate and up, or up alone), the down matrix's product of what the
# form makes of them, and the sums back to the tokens.


def _gate_up(form, rows, plan, weights, x):
    """x (N, C) -> the products of the form's first matrices, (rows, d_ff)
    each: the gate and the up product, or the up product alone."""
    order, _, held, group_sizes, _ = plan
    with jax.named_scope("moe.experts"):
        taken = dispatch_rows(x, order[:rows], held, token_order(plan, rows))
        return tuple(grouped_matmul(taken, weights[m], group_sizes) for m in form.matrices)


def _down(form, plan, weights, *products):
    """The first stage's products -> the down product, (rows, C)."""
    with jax.named_scope("moe.experts"):
        return grouped_matmul(form.hidden(*products), weights["down"], plan[3])


def _combine(rows, dtype, plan, out, gates):
    """The down product -> (N, C) in `dtype`."""
    order, inv, held, _, _ = plan
    with jax.named_scope("moe.combine"):
        return combine_rows(out, gates, order[:rows], jnp.minimum(inv, rows - 1), held,
                            token_order(plan, rows), dtype)


def _expert_rows(form, rows, dtype, plan, weights, x, gates):
    """x (N, C) -> (N, C) in `dtype`: the three stages on one buffer."""
    out = _down(form, plan, weights, *_gate_up(form, rows, plan, weights, x))
    return _combine(rows, dtype, plan, out, gates)


def _fits(plan, room):
    """Whether a buffer of `room` rows holds every row routed here."""
    return plan[3].sum() <= room


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _experts_in_buffer(form, rooms, dtype, kept, plan, weights, x, gates):
    """`_expert_rows` on the smaller of two buffers (rooms: rows of the one
    with headroom, rows of the one for every assignment) that holds the rows
    routed here: both are compiled, one runs. The backward pass makes the
    same choice. `kept`: whether anything holds the forward's products for
    it (`ExpertShare.products_kept`). Where it does, the backward reads in
    the buffer with headroom the products the forward wrote (three of a
    SwiGLU, two of an expert of two matrices) and runs no forward grouped
    matmul; where nothing does, and in a step that
    overflowed either way, it differentiates `_expert_rows` on the buffer it
    takes, its forward again. Differentiating the `cond` itself would have
    the branch that runs write zeros for everything the other one would have
    kept."""
    return _experts_in_buffer_fwd(form, rooms, dtype, kept, plan, weights, x, gates)[0]


def _experts_in_buffer_fwd(form, rooms, dtype, kept, plan, weights, x, gates):
    """With nothing kept, one `cond` over the two buffers and the operands
    as the only residuals: a block's remat then has nothing of this layer's
    to make again, and the backward rule makes what it needs inside the
    branch it takes.

    With products kept, the three stages run on the buffer with headroom
    whatever the step routed, outside any `cond`: each product is a value of
    the rule's own that a policy can keep and the next stage starts from
    (with the gate and up products kept and the down product not, remat runs
    the down matmul again and no other), and the sums read the down product
    where it was written (as an operand of a `cond` it is gathered from HBM:
    0.4 to 0.75 ms a layer more in three cells, PERF.md section 6, PR 45). A
    step that overflowed makes all that of the rows that fit, and nobody
    reads it: its branch of the `cond` starts again from x, the other hands
    the sums on. So such a step pays the headroom buffer's work on top of
    the parent's (PERF.md section 6, PR 45, has it measured): the price of
    the step that fits, which is every step of every cell, reading its
    products with no `cond` between."""
    room = rooms[0]
    if not kept:
        y = jax.lax.cond(_fits(plan, room), functools.partial(_expert_rows, form, room, dtype),
                         functools.partial(_expert_rows, form, rooms[1], dtype),
                         plan, weights, x, gates)
        return y, (plan, weights, x, gates)
    order, inv, held, group_sizes, by_token = plan
    # the groups cut to the buffer: as they are wherever the rows fit
    ends = jnp.minimum(jnp.cumsum(group_sizes), room)
    within = order, inv, held, jnp.diff(ends, prepend=0), by_token
    *names, out_name = form.products
    products = tuple(checkpoint_name(product, name) for product, name in zip(
        _gate_up(form, room, within, weights, x), names))
    out = checkpoint_name(_down(form, within, weights, *products), out_name)
    y = jax.lax.cond(
        _fits(plan, room), lambda plan, weights, x, gates, y: y,
        lambda plan, weights, x, gates, y: _expert_rows(
            form, rooms[1], dtype, plan, weights, x, gates),
        plan, weights, x, gates, _combine(room, dtype, within, out, gates))
    return y, (plan, weights, x, gates, *products, out)


def _experts_in_buffer_bwd(form, rooms, dtype, kept, res, g):
    room = rooms[0]

    def read(plan, weights, x, gates, *kept_products):
        """Each stage's transpose at the value the forward computed, last
        stage first; the rows are gathered again (a gather, cheaper than
        their bytes held)."""
        *products, out = kept_products
        order, inv, held, group_sizes, _ = plan
        at, back = order[:room], token_order(plan, room)
        with jax.named_scope("moe.combine"):
            d_out, d_gates = _combine_bwd(
                dtype, (out, gates, at, jnp.minimum(inv, room - 1), held), g)[:2]
        with jax.named_scope("moe.experts"):
            hidden, pull = jax.vjp(form.hidden, *products)
            d_hidden, d_down = _grouped_matmul_grads(hidden, weights["down"], group_sizes, d_out)
            d_products = pull(d_hidden)
            taken = dispatch_rows(x, at, held, back)
            by, d_weights = zip(*(
                _grouped_matmul_grads(taken, weights[m], group_sizes, d_product)
                for m, d_product in zip(form.matrices, d_products)))
            d_x = _dispatch_bwd((held, back), functools.reduce(operator.add, by))[0]
        return {**dict(zip(form.matrices, d_weights)), "down": d_down}, d_x, d_gates

    def again(rows, plan, weights, x, gates, *unread):
        return jax.vjp(functools.partial(_expert_rows, form, rows, dtype, plan),
                       weights, x, gates)[1](g)

    fitting = read if kept else functools.partial(again, room)
    return (None, *jax.lax.cond(_fits(res[0], room), fitting,
                                functools.partial(again, rooms[1]), *res))


_experts_in_buffer.defvjp(_experts_in_buffer_fwd, _experts_in_buffer_bwd)


SOFTMAX, SIGMOID = "softmax", "sigmoid"
SELECTION_BIAS = "expert_bias"  # the leaf's name: no gradient moves it (SELECTION_BIAS_HELD)


class ExpertShare(nn.Module):
    """(B, T, C) -> (B, T, C): the part of a top-k-of-`num_experts` expert
    layer that experts first_expert .. first_expert + num_held - 1 compute
    (all of them where num_held is None), each expert of `form` (SWIGLU, or
    two matrices under an activation: RELU2). Sows the (B, T, k) choices
    over the whole layer into "choices" (bench/families/__init__.py) and the
    held experts' row counts into "moe_load" (TrainStep's telemetry).

    `router` is the form of the scores. SOFTMAX: a softmax over all experts,
    the top k of it, gates renormalised over the chosen. SIGMOID
    (DeepSeek-V3's, and LFM2's `use_expert_bias`): s = sigmoid of each logit;
    the k experts are the top k of s + b, with b a bias a layer that stands
    in the selection alone (under stop_gradient: the loss never moves it;
    TrainStep does, from the counts of every expert's tokens, which are sown
    into "moe_router"); the gates are s at the chosen, over their sum +
    `gate_eps`, times `scaling`. The epsilon is the layer's own number, as
    its source writes it (LFM2's 1e-6, the default; DeepSeek-V3's 1e-20):
    one form, not two. `hand_up_choices`: return (y, choices) and sow nothing
    into "choices", for a caller that sows several layers' as one entry.

    No assignment is dropped, whatever the imbalance: every one of the
    tokens x k rows may be routed here. The buffer that the rows are
    gathered into has room for `_ROW_HEADROOM` times the even-routing load;
    a step that routes more here takes the same path over a buffer of all
    tokens x k rows instead (`jax.lax.cond`: both are compiled, one runs).
    The grouped matmuls work on the rows routed here either way; the
    buffer's size is what the gathers, the elementwise work and the sums back
    to the tokens follow: every pass over rows of C, forward and backward,
    moves the buffer's rows and no more (`sum_by_token`), so a layer that
    holds a quarter of the experts walks 0.375 of the tokens x k assignments
    and one that overflowed walks them all. Sows what it walked into
    "moe_load" beside the row counts (`telemetry/moe_rows_summed_share`)."""

    d_model: int
    d_ff: int
    num_experts: int
    top_k: int
    first_expert: int = 0
    num_held: Optional[int] = None
    dtype: Any = jnp.bfloat16
    router: str = SOFTMAX
    scaling: float = 1.0
    hand_up_choices: bool = False
    gate_eps: float = 1e-6
    # Whether anything holds this layer's products (KEPT_PRODUCTS) from its
    # forward pass to its backward: everything does where nothing is
    # rematerialised; under a block's `nn.remat` the family hands down what
    # its plan says (models/remat.py). False: the layer names none and takes
    # the form that makes them inside its backward (`_experts_in_buffer`).
    products_kept: bool = True
    form: ExpertForm = SWIGLU

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        n, k = B * T, self.top_k
        num_held = self.num_experts if self.num_held is None else self.num_held
        with jax.named_scope("moe.route"):
            # Router always in fp32: tiny matmul, big numerical leverage.
            logits = nn.Dense(self.num_experts, use_bias=False, dtype=jnp.float32,
                              param_dtype=jnp.float32, name="router")(x.astype(jnp.float32))
            if self.router == SOFTMAX:
                probs = jax.nn.softmax(logits, axis=-1)
                idx = jax.lax.top_k(probs, k)[1]
            else:
                probs = jax.nn.sigmoid(logits)
                bias = self.param(SELECTION_BIAS, nn.initializers.zeros,
                                  (self.num_experts,), jnp.float32)
                idx = jax.lax.top_k(probs + jax.lax.stop_gradient(bias), k)[1]
            # integers, a few MB a layer: a block's remat keeps the choices and
            # the plan's sorts by name (models/remat.py), and runs the router's
            # matmul and its scores again for the gates' gradient, no more
            idx = checkpoint_name(idx, ROUTE_PLAN)
            # the chosen probabilities read through a one-hot product: the
            # gradient of top_k's own values is a scatter, serial on a TPU
            chosen = idx[..., None] == jnp.arange(self.num_experts)
            top_p = jnp.where(chosen, probs[..., None, :], 0.0).sum(-1)
            if self.router == SOFTMAX:
                gates = (top_p / top_p.sum(-1, keepdims=True)).reshape(n, k)
            else:
                gates = (top_p / (top_p.sum(-1, keepdims=True) + self.gate_eps)
                         * self.scaling).reshape(n, k)
                self.sow("moe_router", "rows", chosen.sum((0, 1, 2), dtype=jnp.int32))
            if not self.hand_up_choices:
                self.sow("choices", "experts", idx)
            plan = checkpoint_name(
                route_plan(idx.reshape(n, k), self.first_expert, num_held), ROUTE_PLAN)
            self.sow("moe_load", "rows", plan[3])

        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=(0,))
        weights = {name: self.param(name, init, shape, jnp.float32)
                   for name, shape in (*((m, (num_held, C, self.d_ff)) for m in self.form.matrices),
                                       ("down", (num_held, self.d_ff, C)))}

        operands = (weights, x.reshape(n, C).astype(self.dtype), gates)
        room = buffer_rows(n, k, num_held, self.num_experts)
        if room < n * k:
            y = _experts_in_buffer(
                self.form, (room, n * k), x.dtype, self.products_kept, plan, *operands)
            fits = _fits(plan, room)
            walked, read = jnp.where(fits, room, n * k), fits & self.products_kept
        else:
            y, walked, read = _expert_rows(self.form, n * k, x.dtype, plan, *operands), n * k, False
        self.sow("moe_load", "walked", jnp.asarray(walked, jnp.int32))
        self.sow("moe_load", "read", jnp.asarray(read, jnp.bool_))
        y = y.reshape(B, T, C)
        return (y, idx) if self.hand_up_choices else y


def moe_load_metrics(loads, tokens, top_k):
    """What a routed family reports of a step's "moe_load" collection (a layer's
    (num_held,) count of rows, the rows of the buffer it took, and whether
    its backward was the one that reads kept products): the assignments
    computed here, their share of all tokens * top_k * layers, the fullest
    held expert's rows over the mean's, and the rows that the sums back to
    the tokens walked over tokens * top_k, mean over the layers (the buffer's
    headroom over the held share while every layer fits it, 0.375 at a
    quarter held; 1 for a layer that took the buffer of every assignment:
    whether a step ran the path `sum_by_token` gains on). And of the layers,
    the share that were handed `products_kept` by their family's plan and
    took the buffer with headroom: planned and fitted, which is the form of
    the backward rule that ran. It does not see what the policy then did:
    that a kept product is not made again is held by the lowered steps'
    grouped-matmul counts (tests/test_mellum.py and the families')."""
    from flax import traverse_util

    sown = traverse_util.flatten_dict(loads)
    rows, walked, read = (jnp.stack([v[0] for path, v in sown.items() if path[-1] == name]
                                    ).astype(jnp.float32) for name in ("rows", "walked", "read"))
    held = rows.sum()  # rows: (layers, num_held)
    return {"moe_rows_held": held,
            "moe_held_share": held / (tokens * top_k * rows.shape[0]),
            "moe_load_max_over_mean": rows.max() / jnp.maximum(rows.mean(), 1.0),
            "moe_rows_summed_share": walked.mean() / (tokens * top_k),
            "moe_kept_read_share": read.mean()}


# The selection bias of a SIGMOID router moves by this much a step, towards
# the experts that got fewer tokens than the mean: DeepSeek-V3's rule
# (arXiv:2412.19437, section 2.1.2) with its rate. LFM2's source says
# `use_expert_bias` and gives no rule.
SELECTION_BIAS_RATE = 1e-3


def move_selection_bias(params, router_rows):
    """`params` with every SIGMOID layer's selection bias moved by
    SELECTION_BIAS_RATE * sign(mean load - load_i), from the step's own
    "moe_router" collection (`router_rows`: a count of tokens an expert, all
    experts of the layer, under the layer's own module path)."""
    from flax import traverse_util

    flat = traverse_util.flatten_dict(params)
    for path, rows in traverse_util.flatten_dict(router_rows).items():
        load = rows[0].astype(jnp.float32)  # sown once: a tuple of one
        at = path[:-1] + (SELECTION_BIAS,)
        flat[at] = flat[at] + SELECTION_BIAS_RATE * jnp.sign(load.mean() - load)
    return traverse_util.unflatten_dict(flat)


def router_metrics(params, router_rows):
    """What a routed family reports of its SIGMOID layers: the largest selection
    bias, and the fullest expert's tokens over the mean's, over all
    `num_experts` experts of a layer (held here or not)."""
    from flax import traverse_util

    rows = jnp.stack([r[0] for r in traverse_util.flatten_dict(router_rows).values()]
                     ).astype(jnp.float32)  # (layers, num_experts)
    biases = [b for path, b in traverse_util.flatten_dict(params).items()
              if path[-1] == SELECTION_BIAS]
    return {"moe_bias_abs_max": jnp.max(jnp.abs(jnp.stack(biases))),
            "moe_router_load_max_over_mean": (rows.max(-1) / jnp.maximum(rows.mean(-1), 1.0)).max()}


def step_metrics(cfg, sown, params, tokens):
    """A routed family's `Family.metrics` (models/__init__.py): what its
    `ExpertShare` layers sowed, reduced; the router's pair where the layers
    select under a bias (a SIGMOID router alone sows "moe_router")."""
    metrics = moe_load_metrics(sown["moe_load"], tokens, cfg.top_k)
    if "moe_router" in sown:
        metrics.update(router_metrics(params, sown["moe_router"]))
    return metrics


# A SIGMOID router's family states this as its `Family.held_leaf`: the bias is
# a leaf of the parameters and none of the optimizer's (no moment is kept for
# it, nothing decays it), and what moves it is the step's own routing.
SELECTION_BIAS_HELD = (
    SELECTION_BIAS, lambda params, sown: move_selection_bias(params, sown["moe_router"]))


EXPERT_SHARE_SHARDING_PATTERNS = [  # either form's: one of two matrices has no `gate`
    (r"moe/router/kernel", P()),
    (r"moe/(gate|up)$", P("ep", "fsdp", "tp")),
    (r"moe/down$", P("ep", "tp", "fsdp")),
]

# Expert weights sharded over 'ep' (leading E dim), inner dims reuse the
# dense tp/fsdp layout; router replicated.
MOE_SHARDING_PATTERNS = [
    (r"moe/router/kernel", P()),
    (r"moe/router/bias", P()),
    (r"moe/wi", P("ep", "fsdp", "tp")),
    (r"moe/wo", P("ep", "tp", "fsdp")),
]
