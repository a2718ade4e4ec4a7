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
map to int32 keys of the same order, and passes over the row, each a compare
and a count, fix the key's bits from the top, 32 at most. Entries above it
are taken; of the entries equal to it (the k-th itself, and any tie) the
lowest positions are taken until the row has k, found the same way over the
bits of the position. The whole-array form (`_select_rows`: every pass over
every column) is the XLA path and the kernel's oracle. On a TPU both are
pallas calls, `index_scores` and `index_select`. The selection takes a block
of rows a grid step and does the passes its rows and its data need, and no
others: it reads the columns up to the block's last row in lane-aligned
chunks (the rest of the whole-row block the pipeline copied is not touched);
it ends the key's search at the first trial value that exactly as many keys
as each row wants lie at or above, which is then the set, ties and all; it
searches a position's bits only in a block where some row is left with more
keys tied at its threshold than it wants; and a block whose rows all keep
every key they see runs no pass. What it ran it says in a second result, the
passes a block.

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

# A grid step of index_select holds that many bytes of whole rows of scores
# (the pipeline's block; the kernel reads its causal columns alone) and as
# many of their keys. The rows of a block search together and stop together:
# more rows share a pass's fixed cost (a cross-lane sum, a reduce to a scalar
# and a branch) and stop later; at 256 rows a row's state no longer fits the
# vector registers (TPU v5e at (1, 16384, 16384), ms a call: 4.7 / 3.6 / 3.2
# / 5.1 at 2, 4, 8 and 16 MiB; PERF.md section 6, PR 39). And the columns a
# step of a pass's loop takes: longer steps lose less to the loop, shorter
# ones read less past the diagonal (3.4 / 3.2 / 3.2 / 3.4 ms at 512 to 4,096).
_SELECT_BYTES = 8 * MIB
_SELECT_CHUNK = 1024
# The tile of index_scores.
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


def _keys(scores):
    """float32 -> int32 in the scores' order: a negative float's bits, but
    for the sign, run the other way, and one up, so that -0.0 ties with 0.0
    and no score's key is INT_MIN (what a column a row cannot see holds)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    sign = bits >> 31  # 0, or -1 where the float is negative
    return (bits ^ (sign & jnp.int32(0x7FFFFFFF))) - sign


def _count(cond):
    """Entries of each row that hold, (R, 1) float32 (exact up to 2**24)."""
    return jnp.sum(jnp.where(cond, 1.0, 0.0), axis=1, keepdims=True)


def _position_bits(t):
    return max(1, (t - 1).bit_length())


def _select_rows(scores, top_k):
    """scores (T, T) float32 -> (T, T) bool: for row t the min(top_k, t + 1)
    entries s <= t of largest score, ties to the lower s. Plain jnp on whole
    arrays, every pass over every column whatever the data are: the XLA
    path, and what the kernel is held to bit for bit."""
    t = scores.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (scores.shape[0], 1), 0)
    want = jnp.minimum(row + 1, top_k).astype(jnp.float32)
    keys = jnp.where(pos <= row, _keys(scores), jnp.int32(INT_MIN))

    # the k-th largest key: the largest v with `want` keys >= v, a bit at a
    # time from the sign down (INT_MIN + 2**31 wraps to 0)
    def key_bit(n, at_least):
        trial = at_least + (jnp.int32(1) << (31 - n))
        return jnp.where(_count(keys >= trial) >= want, trial, at_least)

    kth = jax.lax.fori_loop(0, 32, key_bit, jnp.full(row.shape, INT_MIN, jnp.int32))
    above = keys > kth
    tied = keys == kth
    # of the entries equal to it, the lowest positions until the row is
    # full: the position of the last one taken, a bit at a time
    short = want - _count(above)
    n_bits = _position_bits(t)

    def pos_bit(n, last):
        trial = last | (jnp.int32(1) << (n_bits - 1 - n))
        return jnp.where(_count(tied & (pos < trial)) < short, trial, last)

    last = jax.lax.fori_loop(0, n_bits, pos_bit, jnp.zeros(row.shape, jnp.int32))
    return above | (tied & (pos <= last))


def _pack(sel, width):
    """(R, T) bool -> (R, width) int32: bit b of word c is entry b * width + c."""
    packed = jnp.zeros((sel.shape[0], width), jnp.int32)
    for b in range(sel.shape[1] // width):
        packed = packed | (sel[:, b * width:(b + 1) * width].astype(jnp.int32) << b)
    return packed


def _xla_select(scores, top_k):
    width = mask_width(scores.shape[1])
    return jax.vmap(lambda s: _pack(_select_rows(s, top_k), width))(scores)


def _select_kernel(s_ref, o_ref, n_ref, keys_ref, *, chunk, top_k):
    """The selection of one block of rows over the columns those rows can
    see, and no others: lane-aligned chunks of the scores up to the diagonal
    become int32 keys in `keys_ref`; a pass counts over those chunks alone;
    the key's bits are searched until every row has exactly the count it
    wants (`keys >= trial` is then its set) or bit 0 is done; only a block
    left with more keys tied at a row's threshold than the row wants
    searches the position of the last one taken. Planes of the mask past the
    diagonal are zeros. `n_ref` takes the passes the block ran. A row's
    state is (rows, 128) with every lane alike, so that the loops compare
    registers with registers."""
    rows, t = keys_ref.shape
    width = o_ref.shape[-1]
    lanes = (rows, 128)
    first_row = pl.program_id(1) * rows
    row = first_row + jax.lax.broadcasted_iota(jnp.int32, lanes, 0)
    want = jnp.minimum(row + 1, top_k).astype(jnp.float32)
    n_chunks = (first_row + rows + chunk - 1) // chunk

    def over(state, n):
        """A row's state beside n columns: its 128 lanes again and again."""
        return jnp.concatenate([state] * (n // 128), axis=1)

    def col(keys, first):
        return first + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)

    def make_keys(seen):
        def of_chunk(c, _):
            cols = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            keys = _keys(s_ref[0, :, cols])
            if not seen:
                keys = jnp.where(col(keys, c * chunk) <= over(row, chunk), keys, jnp.int32(INT_MIN))
            keys_ref[:, cols] = keys
        return of_chunk

    # every row sees all of the chunks before the block's first row
    jax.lax.fori_loop(0, first_row // chunk, make_keys(True), None)
    jax.lax.fori_loop(first_row // chunk, n_chunks, make_keys(False), None)

    one, zero = jnp.ones(lanes, jnp.float32), jnp.zeros(lanes, jnp.float32)

    def count(holds):
        """Columns of each row whose (keys, first column) `holds`, 128 at a
        time in the order the vector unit takes them. Plain lax calls: the
        slices are traced again in every layer of every program that holds
        the call, and a jnp operator costs six times as much to trace."""
        def of_chunk(c, acc):
            keys = keys_ref[:, pl.ds(pl.multiple_of(c * chunk, chunk), chunk)]
            for j in range(0, chunk, 128):
                hit = holds(jax.lax.slice_in_dim(keys, j, j + 128, axis=1), c * chunk + j)
                acc = jax.lax.add(acc, jax.lax.select(hit, one, zero))
            return acc

        acc = jax.lax.fori_loop(0, n_chunks, of_chunk, zero)
        return jnp.broadcast_to(jnp.sum(acc, axis=1, keepdims=True), lanes)

    def unsure(have):
        return (jnp.max(have - want) > 0).astype(jnp.int32)

    def key_bit(state):
        bit, at_least, have, _ = state
        trial = at_least + (jnp.int32(1) << bit)
        n = count(lambda keys, _: jax.lax.ge(keys, trial))
        at_least, have = jnp.where(n >= want, trial, at_least), jnp.where(n >= want, n, have)
        return bit - 1, at_least, have, unsure(have)

    # rows that want every key they see have it before the first pass
    have = (row + 1).astype(jnp.float32)
    bit, kth, have, tied = jax.lax.while_loop(
        lambda state: (state[0] >= 0) & (state[3] > 0), key_bit,
        (jnp.int32(31), jnp.full(lanes, INT_MIN, jnp.int32), have, unsure(have)))
    kth = jnp.maximum(kth, jnp.int32(INT_MIN + 1))  # never a column past the diagonal
    n_bits = _position_bits(t)
    n_ref[...] = jnp.full(n_ref.shape, 31 - bit + tied * n_bits, jnp.int32)

    def pack(take):
        o_ref[0] = jnp.zeros((rows, width), jnp.int32)

        def plane(b, _):
            keys = keys_ref[:, pl.ds(pl.multiple_of(b * width, width), width)]
            o_ref[0] |= jnp.where(take(keys, b * width), jnp.int32(1) << b, jnp.int32(0))

        jax.lax.fori_loop(0, (first_row + rows + width - 1) // width, plane, None)

    @pl.when(tied == 0)
    def _():
        beside = over(kth, width)
        pack(lambda keys, _: keys >= beside)

    @pl.when(tied > 0)
    def _():
        # of the entries equal to the k-th, the lowest positions until the
        # row is full, as _select_rows finds them
        short = want - count(lambda keys, _: jax.lax.gt(keys, kth))

        def pos_bit(n, last):
            trial = last | (jnp.int32(1) << (n_bits - 1 - n))
            n_before = count(lambda keys, at: (keys == kth) & (col(keys, at) < trial))
            return jnp.where(n_before < short, trial, last)

        last = jax.lax.fori_loop(0, n_bits, pos_bit, jnp.zeros(lanes, jnp.int32))
        pack(lambda keys, at: (keys > over(kth, width))
             | ((keys == over(kth, width)) & (col(keys, at) <= over(last, width))))


def _select_block(t):
    """Rows a grid step of index_select takes: a multiple of 8 that divides t."""
    rows = max(8, min(t, _SELECT_BYTES // (4 * t)) // 8 * 8)
    while t % rows:
        rows -= 8
    return rows


def _select_chunk(t):
    """Columns a step of a pass's loop takes: whole planes of the mask."""
    width = mask_width(t)
    return max(c for c in range(width, max(width, min(t, _SELECT_CHUNK)) + 1, width) if t % c == 0)


def _pallas_select(scores, top_k, interpret):
    b, t, _ = scores.shape
    rows, width = _select_block(t), mask_width(t)
    mask, passes = pl.pallas_call(
        functools.partial(_select_kernel, chunk=_select_chunk(t), top_k=top_k),
        grid=(b, t // rows),
        in_specs=[pl.BlockSpec((1, rows, t), lambda n, i: (n, i, 0))],
        out_specs=[pl.BlockSpec((1, rows, width), lambda n, i: (n, i, 0)),
                   pl.BlockSpec((1, 1, 1, 128), lambda n, i: (n, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, t, width), jnp.int32),
                   jax.ShapeDtypeStruct((b, t // rows, 1, 128), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((rows, t), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the block twice (the pipeline's two buffers) and its keys
            vmem_limit_bytes=max(16 * MIB, 4 * rows * t * 4)),
        interpret=interpret,
        name="index_select",
    )(scores)
    return mask, passes[:, :, 0, 0]


def _bits(packed, n):
    """(B, R, W) words -> (B, R, n * W) of 0 and 1: entry b * W + c is bit b
    of word c."""
    bit = jnp.arange(n, dtype=jnp.int32)
    return ((packed[:, :, None, :] >> bit[None, None, :, None]) & 1).reshape(
        packed.shape[0], packed.shape[1], n * packed.shape[2])


def transpose_packed(packed):
    """The packed mask of the transposed relation: from bit b of [t, c] =
    (query t, key b * W + c) to bit b of [s, c] = (query b * W + c, key s).
    A bit of the keys at a time: that bit of every word, the queries' W-row
    planes gathered into the bits of one (W, W) plane of words and turned,
    is the W rows of those keys. Each pass reads the mask and writes its
    own rows once; none reads what another wrote."""
    b, t, width = packed.shape
    n = t // width
    planes = packed.reshape(b, n, width, width)  # [query bit, c, key lane]
    bit = jnp.arange(n, dtype=packed.dtype)

    def keys_of_bit(k_bit):
        seen = (planes >> k_bit) & 1
        # disjoint bits: the sum is their union (bit 31 wraps to the sign)
        return jnp.sum(seen << bit[None, :, None, None], axis=1).swapaxes(1, 2)

    rows = jax.lax.map(keys_of_bit, bit)  # [key bit, batch, key lane, c]
    return jnp.moveaxis(rows, 0, 1).reshape(b, t, width)


def unpack(packed):
    """(B, T, W) packed mask -> (B, T, T) bool, entry [t, s]: query t attends
    to key s."""
    return _bits(packed, packed.shape[1] // packed.shape[2]) != 0


def index_select(scores, top_k: int, *, interpret=False):
    """scores (B, T, T) float32 -> the packed mask (B, T, W) int32 of each
    query's min(top_k, t + 1) best keys at or before it, and the
    compare-and-count passes each block of rows took to find it (int32, a
    block an entry; of 32 + the bits of a position)."""
    if interpret or attention_path(scores.shape[1]) == "flash":
        return _pallas_select(scores, top_k, interpret)
    b, t, _ = scores.shape
    return _xla_select(scores, top_k), jnp.full((b, 1), 32 + _position_bits(t), jnp.int32)
