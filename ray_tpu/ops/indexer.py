"""Which keys a query attends to, chosen by a learned indexer (the lightning
indexer of DeepSeek-V3.2's sparse attention): scores of every causal pair
from a few small heads, and an exact top-k of each query's row.

    index_scores(q, k, w)   I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s]),
                            j over the indexer's heads, one shared key head
    index_select(I, top_k)  S_t = the min(top_k, t + 1) positions s <= t of
                            largest I[t, s], ties to the lower position, as
                            bits: two packed masks (`mask_width`)

The selection is a set of integers: nothing here has a gradient, and the
caller hands in operands under `stop_gradient`.

Exact top-k without a sort. A row of T scores is 16,384 long at the
benchmark's shape and k is 2,048: `lax.top_k` there is a sort of every row.
Instead the k-th largest score of a row is found bit by bit: float32 scores
map to int32 keys of the same order, and 32 passes over the row, each a
compare and a count, fix the key's bits from the top. Entries above it are
taken; of the entries equal to it (the k-th itself, and any tie) the lowest
positions are taken until the row has k, found the same way over the bits
of the position. On a TPU both are pallas calls, `index_scores` and
`index_select`, a block of rows a grid step with the whole row in VMEM;
elsewhere the same arithmetic runs as XLA ops.

The packed mask. Bit b of word [t, c] says whether query t attends to key
b * W + c, with W = `mask_width(T)` = max(128, T / 32) words a row: the keys
of a tile of the attention kernels are then one lane range of one bit, and a
tile's mask is a shift and an and (ops/attention.py). The backward kernel
that owns keys and loops over queries takes the same of the transposed
relation: bit b of word [s, c] says whether query b * W + c attends to key s.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import MIB, NEG_INF, attention_path

INT_MIN = -(1 << 31)

# Rows of scores a grid step of index_select holds in VMEM with its
# temporaries (four arrays of a row each), and the tile of index_scores.
_SELECT_BYTES = 4 * MIB
_SCORE_TILE = 512


def mask_width(t: int) -> int:
    """Words a row of the packed mask: a multiple of 128 lanes, and at most
    32 bits a word."""
    width = max(128, t // 32)
    if t % width or t // width > 32:
        raise ValueError(f"seq len {t} does not pack into 32-bit words of {width} lanes")
    return width


# --------------------------------------------------------------------------
# scores
# --------------------------------------------------------------------------


def _scores_kernel(q_ref, k_ref, w_ref, o_ref, *, block_q, block_k):
    """One (block_q, block_k) tile: every head's q k^T on the MXU, ReLU, the
    query's weight of that head, summed in float32. A tile wholly above the
    diagonal is not computed."""
    i, j = pl.program_id(1), pl.program_id(2)
    heads = q_ref.shape[1]

    @pl.when(j * block_k <= i * block_q + block_q - 1)
    def _():
        k = k_ref[0]
        w = w_ref[0]
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for h in range(heads):
            s = jax.lax.dot_general(q_ref[0, h], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc = acc + w[:, h:h + 1] * jnp.maximum(s, 0.0)
        o_ref[0] = acc

    @pl.when(j * block_k > i * block_q + block_q - 1)
    def _():
        o_ref[0] = jnp.full((block_q, block_k), NEG_INF, jnp.float32)


def _score_tile(t):
    return max(b for b in range(128, min(t, _SCORE_TILE) + 1, 128) if t % b == 0)


def _pallas_scores(q, k, w, interpret):
    b, t, heads, dim = q.shape
    block = _score_tile(t)
    return pl.pallas_call(
        functools.partial(_scores_kernel, block_q=block, block_k=block),
        grid=(b, t // block, t // block),
        in_specs=[pl.BlockSpec((1, heads, block, dim), lambda n, i, j: (n, 0, i, 0)),
                  pl.BlockSpec((1, block, dim), lambda n, i, j: (n, j, 0)),
                  pl.BlockSpec((1, block, heads), lambda n, i, j: (n, i, 0))],
        out_specs=pl.BlockSpec((1, block, block), lambda n, i, j: (n, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, t, t), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="index_scores",
    )(q.transpose(0, 2, 1, 3), k, w.astype(jnp.float32))


def _xla_scores(q, k, w):
    s = jnp.einsum("bthe,bse->bhts", q, k, preferred_element_type=jnp.float32)
    return jnp.einsum("bhts,bth->bts", jnp.maximum(s, 0.0), w.astype(jnp.float32))


def index_scores(q, k, w, *, interpret=False):
    """q (B, T, J, E) the indexer's query heads, k (B, T, E) its one key
    head, w (B, T, J) a query's weight of each head -> (B, T, T) float32.
    Entries above the diagonal are not defined (index_select does not read
    them)."""
    if interpret or attention_path(q.shape[1]) == "flash":
        return _pallas_scores(q, k, w, interpret)
    return _xla_scores(q, k, w)


# --------------------------------------------------------------------------
# selection
# --------------------------------------------------------------------------


def _count(cond):
    """Entries of each row that hold, (R, 1) float32 (exact up to 2**24)."""
    return jnp.sum(jnp.where(cond, 1.0, 0.0), axis=1, keepdims=True)


def _select_rows(scores, first_row, top_k):
    """scores (R, T) float32, rows first_row .. first_row + R - 1 of a
    sequence -> (R, T) bool: for row t the min(top_k, t + 1) entries s <= t
    of largest score, ties to the lower s. Plain jnp on whole arrays: the
    body of the pallas kernel and the XLA path alike."""
    rows, t = scores.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (rows, t), 1)
    row = first_row + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    want = jnp.minimum(row + 1, top_k).astype(jnp.float32)
    # int32 keys in the scores' order: a negative float's bits, but for the
    # sign, run the other way
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    bits = jnp.where(bits == INT_MIN, jnp.int32(0), bits)  # -0.0 ties with 0.0
    keys = jnp.where(bits >= 0, bits, bits ^ jnp.int32(0x7FFFFFFF))
    keys = jnp.where(pos <= row, keys, jnp.int32(INT_MIN))

    # the k-th largest key: the largest v with `want` keys >= v, a bit at a
    # time from the sign down
    at_least = jnp.where(_count(keys >= 0) >= want, jnp.int32(0), jnp.int32(INT_MIN))

    def key_bit(n, at_least):
        trial = at_least | (jnp.int32(1) << (30 - n))
        return jnp.where(_count(keys >= trial) >= want, trial, at_least)

    kth = jax.lax.fori_loop(0, 31, key_bit, at_least)
    above = keys > kth
    tied = keys == kth
    # of the entries equal to it, the lowest positions until the row is
    # full: the position of the last one taken, a bit at a time
    short = want - _count(above)

    def pos_bit(n, last):
        trial = last | (jnp.int32(1) << (n_bits - 1 - n))
        return jnp.where(_count(tied & (pos < trial)) < short, trial, last)

    n_bits = max(1, (t - 1).bit_length())
    last = jax.lax.fori_loop(0, n_bits, pos_bit, jnp.zeros((rows, 1), jnp.int32))
    return above | (tied & (pos <= last))


def _pack(sel, width):
    """(R, T) bool -> (R, width) int32: bit b of word c is entry b * width + c."""
    packed = jnp.zeros((sel.shape[0], width), jnp.int32)
    for b in range(sel.shape[1] // width):
        packed = packed | (sel[:, b * width:(b + 1) * width].astype(jnp.int32) << b)
    return packed


def _select_kernel(s_ref, o_ref, *, rows, top_k, width):
    first_row = pl.program_id(1) * rows
    o_ref[0] = _pack(_select_rows(s_ref[0], first_row, top_k), width)


def _select_block(t):
    """Rows a grid step of index_select takes: a multiple of 8 that divides t."""
    rows = max(8, min(t, _SELECT_BYTES // (4 * t)) // 8 * 8)
    while t % rows:
        rows -= 8
    return rows


def _pallas_select(scores, top_k, interpret):
    b, t, _ = scores.shape
    rows, width = _select_block(t), mask_width(t)
    return pl.pallas_call(
        functools.partial(_select_kernel, rows=rows, top_k=top_k, width=width),
        grid=(b, t // rows),
        in_specs=[pl.BlockSpec((1, rows, t), lambda n, i: (n, i, 0))],
        out_specs=pl.BlockSpec((1, rows, width), lambda n, i: (n, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, t, width), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the block twice (the pipeline's two buffers) and the kernel's
            # temporaries: keys, positions, a compare's result, a count's terms
            vmem_limit_bytes=max(16 * MIB, 10 * rows * t * 4)),
        interpret=interpret,
        name="index_select",
    )(scores)


def _xla_select(scores, top_k):
    width = mask_width(scores.shape[1])
    return jax.vmap(lambda s: _pack(_select_rows(s, 0, top_k), width))(scores)


def _bits(packed, n):
    """(B, R, W) words -> (B, R, n * W) of 0 and 1: entry b * W + c is bit b
    of word c."""
    bit = jnp.arange(n, dtype=jnp.int32)
    return ((packed[:, :, None, :] >> bit[None, None, :, None]) & 1).reshape(
        packed.shape[0], packed.shape[1], n * packed.shape[2])


def transpose_packed(packed):
    """The packed mask of the transposed relation: from bit b of [t, c] =
    (query t, key b * W + c) to bit b of [s, c] = (query b * W + c, key s).
    A bit of the queries at a time: those W rows unpacked, turned, and
    shifted into their bit of every key's words."""
    _, t, width = packed.shape

    def queries_of_bit(q_bit, words):
        rows = jax.lax.dynamic_slice_in_dim(packed, q_bit * width, width, axis=1)
        return words | (_bits(rows, t // width).swapaxes(1, 2) << q_bit)

    return jax.lax.fori_loop(0, t // width, queries_of_bit, jnp.zeros_like(packed))


def unpack(packed):
    """(B, T, W) packed mask -> (B, T, T) bool, entry [t, s]: query t attends
    to key s."""
    return _bits(packed, packed.shape[1] // packed.shape[2]) != 0


def index_select(scores, top_k: int, *, interpret=False):
    """scores (B, T, T) float32 -> the packed mask (B, T, W) int32 of each
    query's min(top_k, t + 1) best keys at or before it."""
    if interpret or attention_path(scores.shape[1]) == "flash":
        return _pallas_select(scores, top_k, interpret)
    return _xla_select(scores, top_k)
