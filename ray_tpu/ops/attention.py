"""Fused causal attention: pallas flash kernel (TPU) with an XLA fallback.

FlashAttention-2-style tiling. A grid step owns one tile of queries (forward,
dq) or of keys (dkv) of a few heads and loops over the tiles of the other
operand, whose whole sequence stays in VMEM; the forward keeps an online
softmax (running max and sum), the backward recomputes the probabilities per
tile from the saved logsumexp: O(T) memory instead of O(T^2).

Precision: q, k, v and dO tiles reach the MXU in the dtype they arrive in
(bf16 from the models), every matmul accumulates in float32
(preferred_element_type), and the probabilities and dS are cast to that dtype
just before their matmuls; those two casts are the only rounding the kernels
add. Scores, mask, running max and sum, exp, the rescale, lse, delta and the dq
/ dk / dv accumulators are float32. float32 inputs stay float32 operands (which
the MXU multiplies at default precision, one bf16 pass on a v5e, as XLA does).

Tiles: `flash_tiles(bh, t, d, dtype, window)` chooses the tile a grid step
owns, the tile it loops over and the heads it takes at once from the call's
shape, and reckons the VMEM the call needs. The causal mask is built only on
tiles the diagonal crosses; tiles wholly above it are never visited. With a
window (a query sees its last `window` keys, itself included) the same holds
at the other edge: tiles wholly behind the window are never visited, those
its trailing edge crosses are masked, those between are plain.

The reference framework has no attention kernels at all (its data plane is torch);
this op is what its GPU stack gets from flash-attn. Ring attention
(ray_tpu/ops/ring_attention.py) does not call it: its chunk pairs are einsums.

A third kind of call takes its mask from outside: causal attention over the
keys a per-query selection names (ops/indexer.py: each query's top-k keys by
a learned indexer's scores), handed over as a packed bit mask and its
transpose. This first version visits every tile at or below the diagonal and
masks each with its bits; a tile the selection leaves empty is computed and
comes to nothing.

The three pallas calls are named flash_fwd, flash_bwd_dq and flash_bwd_dkv,
flash_win<window>_fwd, flash_win<window>_bwd_dq, flash_win<window>_bwd_dkv
where the call has a window shorter than its sequence, and flash_sel<k>_fwd,
flash_sel<k>_bwd_dq, flash_sel<k>_bwd_dkv where a selection of k keys a query,
fewer than the sequence has, says what is seen.
The name reaches the compiled instruction and the profiler's trace (wrapped by
the transformations it went through, e.g. transpose_jvp_flash_bwd_dq_), on one
chip and under a mesh alike, and is how the benchmark's per-kernel metrics
(bench/layer_metrics/flash_*) find each call: a kernel that is split, fused
or renamed takes a new name, and none is a substring of another.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
MIB = 1 << 20

# --------------------------------------------------------------------------
# tiles
# --------------------------------------------------------------------------

# Score elements (heads x rows x columns) one tile step works on: enough
# that the matmuls hide the softmax and a grid step its fixed cost.
_TILE_ELEMS = 512 * 512
_MAX_BLOCK = 1024  # rows of a tile, the one a grid step owns or loops over
# Largest tile of a windowed call, as a share of its window.
_WINDOW_TILE = 0.5
# What the compiler may use without being asked (the scoped default on a
# v5e) and what the rule will ask for at most (of 128 MiB there).
_VMEM_SCOPED = 16 * MIB
_VMEM_BUDGET = 96 * MIB


class FlashTiles(NamedTuple):
    """block_q rows of the tile a grid step owns (queries in flash_fwd and
    flash_bwd_dq, keys in flash_bwd_dkv), block_k rows of the tiles it
    loops over (keys, resp. queries), heads per grid step; `window` keys a
    query sees, itself included (None: every key before it); `select` keys
    a query sees of those before it, named by a mask (None: no mask)."""

    block_q: int
    block_k: int
    heads: int
    window: Optional[int] = None
    select: Optional[int] = None


def _vmem_bytes(tiles, t, d, itemsize):
    """VMEM of the hungriest of the three calls (dkv), in bytes: blocks are
    double-buffered by the pipeline, a (.., 1, t) float32 row pads to 8
    sublanes, d pads to 128 lanes, and the loop body holds four float32
    score tiles (s, p, dp, ds) and two casts."""
    block_q, block_k, heads = tiles[:3]
    lanes = -(-d // 128) * 128
    whole = 2 * 2 * t * lanes * itemsize + 2 * 2 * 8 * t * 4   # q, dO (or k, v); lse, delta
    own = 2 * 4 * block_q * lanes * itemsize                   # two tiles in, two out
    acc = 2 * block_q * lanes * 4
    scores = block_q * block_k * (4 * 4 + 2 * itemsize)
    return heads * (whole + own + acc + scores)


def _divisor(t, cap):
    """The largest multiple of 128 that divides t and is at most cap."""
    return max(b for b in range(128, max(cap, 128) + 1, 128) if t % b == 0)


def flash_tiles(bh: int, t: int, d: int, dtype, window: Optional[int] = None,
                select: Optional[int] = None) -> FlashTiles:
    """Tiles for a causal flash call on (bh, t, d) operands of `dtype`, from
    the shape alone: the largest square tile, a multiple of 128 that divides
    t, up to _MAX_BLOCK (a tile step's matmuls must be long enough to hide
    its softmax, whose per-row bookkeeping costs the same for a narrow tile
    as for a wide one); where a head is less than _TILE_ELEMS of scores,
    several heads a grid step (a grid step's fixed cost is what a short
    call pays). Heads and then the tile shrink until `_vmem_bytes` reckons
    that the call fits the VMEM budget.

    With a `window` shorter than t the call is a windowed one: a tile of b
    rows visits b + window + b scores a row where window are needed (the
    diagonal tile and the one on the window's edge are half masked), so
    the tile is at most _WINDOW_TILE of the window. A window of t or more
    is the causal call.

    With a selection of `select` keys a query, fewer than t, the call is a
    selected one: the tile it loops over lies within one bit of the packed
    mask's words (ops/indexer.py:mask_width lanes), the tile it owns is the
    causal call's, and a grid step's rows of the mask count against the
    budget. A selection of t keys or more is the causal call."""
    if t % 128:
        raise ValueError(f"seq len {t} is not a multiple of 128")
    if window is not None and window < 1:
        raise ValueError(f"window {window}: a query sees at least itself")
    itemsize = jnp.dtype(dtype).itemsize
    if window is not None and window >= t:
        window = None
    if select is not None and select >= t:
        select = None
    if select is not None and window is not None:
        raise ValueError("a call takes a window or a selection, not both")
    cap = _MAX_BLOCK if window is None else min(_MAX_BLOCK, int(window * _WINDOW_TILE))
    block = _divisor(t, cap)
    heads = max(1, min(bh, _TILE_ELEMS // (block * block)))

    def over_budget():
        return _vmem_bytes((block, block, heads), t, d, itemsize) > _VMEM_BUDGET

    while over_budget() and heads > 1:
        heads //= 2
    while over_budget() and block > 128:
        block = _divisor(t, block - 1)
    if select is None:
        return FlashTiles(block, block, heads, window)
    from ray_tpu.ops.indexer import mask_width

    width = mask_width(t)
    inner = _divisor(width, block)
    if block % inner:
        block = inner
    while heads > 1 and _select_vmem_bytes((block, inner, heads), t, d, itemsize) > _VMEM_BUDGET:
        heads //= 2
    return FlashTiles(block, inner, heads, None, select)


def _select_vmem_bytes(tiles, t, d, itemsize):
    """`_vmem_bytes` of a selected call: and the rows of the packed mask a
    grid step owns, double-buffered."""
    from ray_tpu.ops.indexer import mask_width

    return _vmem_bytes(tiles, t, d, itemsize) + 2 * tiles[0] * mask_width(t) * 4


# --------------------------------------------------------------------------
# the calls
# --------------------------------------------------------------------------


def _call(kernel, name, like, tiles, in_specs, out_specs, out_shape, interpret):
    """The pallas_call of one of the three kernels on operands like `like`,
    (bh, t, d): grid over groups of heads and the tiles a step owns."""
    bh, t, d = like.shape
    # The scoped default is enough for small calls; beyond it ask for what
    # the rule reckoned, with a quarter more for what the reckoning leaves out.
    vmem = _vmem_bytes(tiles, t, d, like.dtype.itemsize)
    if tiles.select is not None:
        name = name.replace("flash_", f"flash_sel{tiles.select}_", 1)
        vmem = _select_vmem_bytes(tiles, t, d, like.dtype.itemsize)
    if tiles.window is not None:
        # a windowed call says so, and how wide: a shape function sees
        # names and shapes only
        name = name.replace("flash_", f"flash_win{tiles.window}_", 1)
        kernel = functools.partial(kernel, window=tiles.window)
    return pl.pallas_call(
        functools.partial(kernel, block_q=tiles.block_q, block_k=tiles.block_k),
        grid=(pl.cdiv(bh, tiles.heads), t // tiles.block_q),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(_VMEM_SCOPED, vmem * 5 // 4)),
        interpret=interpret,
        name=name,
    )


def _specs(like, tiles):
    """Block specs of a call on (bh, t, d) operands: the tile a grid step
    owns and the whole sequence, of a (bh, t, d) array and of a (bh, 1, t)
    row of float32 (lse, delta)."""
    _, t, d = like.shape
    g, block_q = tiles.heads, tiles.block_q
    own = pl.BlockSpec((g, block_q, d), lambda b, i: (b, i, 0))
    # a (g, 1, block_q) block keeps Mosaic's last-two-dims tiling rule
    # satisfied, which a rank-2 (g, block_q) block does not
    own_row = pl.BlockSpec((g, 1, block_q), lambda b, i: (b, 0, i))
    whole = pl.BlockSpec((g, t, d), lambda b, i: (b, 0, 0))
    whole_row = pl.BlockSpec((g, 1, t), lambda b, i: (b, 0, 0))
    return own, own_row, whole, whole_row


# --------------------------------------------------------------------------
# what the three kernels share
# --------------------------------------------------------------------------

_NT = (((2,), (2,)), ((0,), (0,)))  # (g, m, c) x (g, n, c) -> (g, m, n)
_NN = (((2,), (1,)), ((0,), (0,)))  # (g, m, c) x (g, c, n) -> (g, m, n)


def _dot(a, b, dims):
    """One MXU matmul per head: operands as they are, float32 out."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _split_scale(d):
    """1/sqrt(d) as (factor on an operand of QK^T, factor on the float32
    scores). On the operand only where it is a power of two, which no dtype
    rounds."""
    scale = 1.0 / math.sqrt(d)
    return (scale, 1.0) if math.frexp(scale)[0] == 0.5 else (1.0, scale)


def _tile_loop(step, carry, plain, diag_start, diag_tiles, plain_after=None,
               edge=None, edge_after=None):
    """step(j, carry, masked) over a grid step's tiles: the (lo, hi) range
    `plain` without the mask, then the `diag_tiles` tiles from diag_start on,
    which the diagonal crosses, with it (a static count, unrolled), then
    the range `plain_after` without. A windowed call has tiles that the
    window's trailing edge crosses as well, masked like the diagonal's: the
    range `edge` before `plain`, `edge_after` after `plain_after`. A range
    may be None."""
    unmasked = functools.partial(step, masked=False)
    masked = functools.partial(step, masked=True)
    if edge is not None:
        carry = jax.lax.fori_loop(*edge, masked, carry)
    if plain is not None:
        carry = jax.lax.fori_loop(*plain, unmasked, carry)
    for s in range(diag_tiles):
        carry = step(diag_start + s, carry, masked=True)
    if plain_after is not None:
        carry = jax.lax.fori_loop(*plain_after, unmasked, carry)
    if edge_after is not None:
        carry = jax.lax.fori_loop(*edge_after, masked, carry)
    return carry


def _band(window, block_q, block_k):
    """Of the tiles on one side of the diagonal's own, counted from it: how
    many lie wholly inside a window of `window` keys, and how many hold any
    entry inside it (the rest up to that are cut by its trailing edge). The
    farthest pair of a tile s steps away is block_q - 1 + (s + 1) * block_k
    apart, the nearest s * block_k + 1."""
    return max(0, (window - block_q) // block_k), (window + block_k - 2) // block_k


def _before(diag, window, block_q, block_k):
    """(plain, edge) ranges of the tiles before tile `diag`, the diagonal's
    first: without a window all of them plain; with one the nearest that lie
    wholly inside it plain, those its trailing edge cuts masked, the rest
    not visited. `_tile_loop` takes the edge's first: a row they hide wholly
    adds exp(0) terms under a running max of NEG_INF, which the rescale of
    the row's first visible tile (alpha = 0) takes out again."""
    if window is None:
        return (0, diag), None
    inside, any_inside = _band(window, block_q, block_k)
    first_plain = jnp.maximum(diag - inside, 0)
    return (first_plain, diag), (jnp.maximum(diag - any_inside, 0), first_plain)


def _visible(diff, off, window, keys_first=False):
    """Which entries of a tile a query sees. `diff` is row less column and
    `off` the tile's offset from the diagonal, j*block_k - i*block_q; rows
    are queries, or keys with `keys_first` (flash_bwd_dkv). Causal: the
    query is not before the key. Windowed: and less than `window` after."""
    if keys_first:
        seen = diff <= off
        return seen if window is None else seen & (diff > off - window)
    seen = diff >= off
    return seen if window is None else seen & (diff < off + window)


def _row_minus_col(block_q, block_k):
    """Row index less column index of a (block_q, block_k) tile's entries:
    what a tile on the diagonal compares with its offset from it."""
    return (jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))


def _own_tile(t, block_q):
    """Index of the tile this grid step owns; static where there is one."""
    return 0 if t == block_q else pl.program_id(1)


def _rows(ref, j, block):
    """Tile j of `block` rows along the second axis of a (g, t, d) ref."""
    return ref[:, pl.ds(pl.multiple_of(j * block, block), block), :]


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q, block_k, window=None):
    g, _, d = q_ref.shape
    ratio = block_q // block_k
    i = _own_tile(k_ref.shape[1], block_q)
    q_scale, s_scale = _split_scale(d)
    q = q_ref[...]
    if q_scale != 1.0:
        q = q * q_scale
    # entry (r, c) of q tile i against k tile j is visible iff
    # i*block_q + r >= j*block_k + c
    diff = _row_minus_col(block_q, block_k)

    def step(j, carry, masked):
        k = _rows(k_ref, j, block_k)
        v = _rows(v_ref, j, block_k)
        s = _dot(q, k, _NT)  # (g, block_q, block_k) float32
        if s_scale != 1.0:
            s = s * s_scale
        if masked:
            s = jnp.where(_visible(diff, j * block_k - i * block_q, window), s, NEG_INF)
        s_max = jnp.max(s, axis=-1, keepdims=True)
        if carry is None:  # a row's first tile: nothing to rescale
            p = jnp.exp(s - s_max)
            return s_max, jnp.sum(p, axis=-1, keepdims=True), _dot(p.astype(v.dtype), v, _NN)
        m, l, acc = carry
        m_new = jnp.maximum(m, s_max)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + _dot(p.astype(v.dtype), v, _NN)
        return m_new, l, acc

    # k tiles 0 .. i*ratio-1 lie below the diagonal, the next `ratio` cross
    # it. Tile 0 is every row's first; it holds key 0, which every row sees.
    if isinstance(i, int):  # the only q tile: no tile lies below the diagonal
        m, l, acc = _tile_loop(step, step(0, None, masked=True), None, 1, ratio - 1)
    else:
        init = (jnp.full((g, block_q, 1), NEG_INF, jnp.float32),
                jnp.zeros((g, block_q, 1), jnp.float32),
                jnp.zeros((g, block_q, d), jnp.float32))
        plain, edge = _before(i * ratio, window, block_q, block_k)
        m, l, acc = _tile_loop(step, init, plain, i * ratio, ratio, edge=edge)
    o_ref[...] = (acc * (1.0 / l)).astype(o_ref.dtype)
    lse = m + jnp.log(l)
    for h in range(g):  # (g, block_q, 1) columns -> (g, 1, block_q) rows
        lse_ref[h, 0] = lse[h, :, 0]


def _flash_fwd(q, k, v, *, tiles, interpret):
    own, own_row, whole, _ = _specs(q, tiles)
    return _call(
        _fwd_kernel, "flash_fwd", q, tiles,
        in_specs=[own, whole, whole],
        out_specs=[own, own_row],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((q.shape[0], 1, q.shape[1]), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, block_q, block_k, window=None):
    g, _, d = q_ref.shape
    ratio = block_q // block_k
    i = _own_tile(k_ref.shape[1], block_q)
    q_scale, s_scale = _split_scale(d)
    q = q_ref[...]
    if q_scale != 1.0:
        q = q * q_scale
    do = do_ref[...]
    # (g, 1, block_q) rows -> (g, block_q, 1) columns, a head at a time
    lse = jnp.stack([lse_ref[h, 0][:, None] for h in range(g)])
    delta = jnp.stack([delta_ref[h, 0][:, None] for h in range(g)])
    diff = _row_minus_col(block_q, block_k)

    def step(j, dq, masked):
        k = _rows(k_ref, j, block_k)
        v = _rows(v_ref, j, block_k)
        s = _dot(q, k, _NT)
        if s_scale != 1.0:
            s = s * s_scale
        if masked:
            s = jnp.where(_visible(diff, j * block_k - i * block_q, window), s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = _dot(do, v, _NT)
        ds = p * (dp - delta)
        return dq + _dot(ds.astype(k.dtype), k, _NN)

    plain, edge = (None, None) if isinstance(i, int) else _before(
        i * ratio, window, block_q, block_k)
    dq = _tile_loop(step, jnp.zeros((g, block_q, d), jnp.float32),
                    plain, i * ratio, ratio, edge=edge)
    dq_ref[...] = (dq * (q_scale * s_scale)).astype(dq_ref.dtype)  # 1/sqrt(d)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    *, block_q, block_k, window=None):
    """Owns block_q keys, loops over tiles of block_k queries. Scores are
    held transposed, (keys, queries): lse and delta then broadcast along
    sublanes as the (.., 1, t) rows they are stored as, and no matmul needs
    a transposed operand."""
    g, _, d = k_ref.shape
    seq_len = q_ref.shape[1]
    ratio = block_q // block_k
    i = _own_tile(seq_len, block_q)
    q_scale, s_scale = _split_scale(d)
    k = k_ref[...]
    if q_scale != 1.0:
        k = k * q_scale  # for the scores alone: dk sums dS^T Q with q as it is
    v = v_ref[...]
    # entry (r, c), key r of tile i against query c of tile j, is visible
    # iff j*block_k + c >= i*block_q + r
    diff = _row_minus_col(block_q, block_k)

    def step(j, carry, masked):
        dk, dv = carry
        q = _rows(q_ref, j, block_k)
        do = _rows(do_ref, j, block_k)
        at = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        lse = lse_ref[:, :, at]      # (g, 1, block_k)
        delta = delta_ref[:, :, at]
        s = _dot(k, q, _NT)          # (g, block_q keys, block_k queries)
        if s_scale != 1.0:
            s = s * s_scale
        if masked:
            s = jnp.where(_visible(diff, j * block_k - i * block_q, window, keys_first=True),
                          s, NEG_INF)
        p = jnp.exp(s - lse)
        dv = dv + _dot(p.astype(do.dtype), do, _NN)
        dp = _dot(v, do, _NT)
        ds = p * (dp - delta)
        dk = dk + _dot(ds.astype(q.dtype), q, _NN)
        return dk, dv

    zeros = jnp.zeros((g, block_q, d), jnp.float32)
    # q tiles before i*ratio see none of these keys, the next `ratio` cross
    # the diagonal, the rest see all of them (with a window: the nearest do,
    # then come those its trailing edge cuts, the rest see none)
    after = edge = None
    if not isinstance(i, int):
        first, last = (i + 1) * ratio, seq_len // block_k
        if window is None:
            after = (first, last)
        else:
            inside, any_inside = _band(window, block_q, block_k)
            last_plain = jnp.minimum(first + inside, last)
            after, edge = (first, last_plain), (last_plain, jnp.minimum(first + any_inside, last))
    dk, dv = _tile_loop(step, (zeros, zeros), None, i * ratio, ratio, after, edge_after=edge)
    dk_ref[...] = (dk * (q_scale * s_scale)).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _flash_bwd(res, do, *, tiles, interpret):
    q, k, v, o, lse = res
    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1
    )[:, None, :]  # (bh, 1, t) — same layout as lse
    own, own_row, whole, whole_row = _specs(q, tiles)
    like_q = jax.ShapeDtypeStruct(q.shape, q.dtype)
    dq = _call(
        _bwd_dq_kernel, "flash_bwd_dq", q, tiles,
        in_specs=[own, whole, whole, own, own_row, own_row],
        out_specs=own,
        out_shape=like_q,
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk, dv = _call(
        _bwd_dkv_kernel, "flash_bwd_dkv", q, tiles,
        in_specs=[whole, own, own, whole, whole_row, whole_row],
        out_specs=[own, own],
        out_shape=[like_q, like_q],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------
# attention over selected keys
# --------------------------------------------------------------------------


def _tile_bits(m_ref, j, block_k):
    """Which entries of the tile of columns j * block_k onward the packed mask
    m_ref (1, rows, W) allows, (rows, block_k) bool: the tile's columns are
    block_k lanes of one bit of the words (ops/indexer.py)."""
    width = m_ref.shape[2]
    if block_k == width:
        return ((m_ref[0] >> j) & 1) != 0
    per_bit = width // block_k
    lanes = pl.ds(pl.multiple_of((j % per_bit) * block_k, block_k), block_k)
    return ((m_ref[0, :, lanes] >> (j // per_bit)) & 1) != 0


def _sel_fwd_kernel(q_ref, k_ref, v_ref, m_ref, o_ref, lse_ref, *, block_q, block_k):
    """`_fwd_kernel` with every tile masked by the selection's bits, which
    hold the causal mask too. A row may see nothing in its first tiles:
    what those add under a running max of NEG_INF, the rescale of its
    first visible tile takes out again."""
    g, _, d = q_ref.shape
    i = _own_tile(k_ref.shape[1], block_q)
    q_scale, s_scale = _split_scale(d)
    q = q_ref[...]
    if q_scale != 1.0:
        q = q * q_scale

    def step(j, carry):
        m, l, acc = carry
        k = _rows(k_ref, j, block_k)
        v = _rows(v_ref, j, block_k)
        s = _dot(q, k, _NT)
        if s_scale != 1.0:
            s = s * s_scale
        s = jnp.where(_tile_bits(m_ref, j, block_k)[None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + _dot(p.astype(v.dtype), v, _NN)
        return m_new, l, acc

    init = (jnp.full((g, block_q, 1), NEG_INF, jnp.float32),
            jnp.zeros((g, block_q, 1), jnp.float32),
            jnp.zeros((g, block_q, d), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, (i + 1) * (block_q // block_k), step, init)
    o_ref[...] = (acc * (1.0 / l)).astype(o_ref.dtype)
    lse = m + jnp.log(l)
    for h in range(g):
        lse_ref[h, 0] = lse[h, :, 0]


def _sel_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, m_ref, dq_ref,
                       *, block_q, block_k):
    g, _, d = q_ref.shape
    i = _own_tile(k_ref.shape[1], block_q)
    q_scale, s_scale = _split_scale(d)
    q = q_ref[...]
    if q_scale != 1.0:
        q = q * q_scale
    do = do_ref[...]
    lse = jnp.stack([lse_ref[h, 0][:, None] for h in range(g)])
    delta = jnp.stack([delta_ref[h, 0][:, None] for h in range(g)])

    def step(j, dq):
        k = _rows(k_ref, j, block_k)
        v = _rows(v_ref, j, block_k)
        s = _dot(q, k, _NT)
        if s_scale != 1.0:
            s = s * s_scale
        s = jnp.where(_tile_bits(m_ref, j, block_k)[None], s, NEG_INF)
        p = jnp.exp(s - lse)
        ds = p * (_dot(do, v, _NT) - delta)
        return dq + _dot(ds.astype(k.dtype), k, _NN)

    dq = jax.lax.fori_loop(0, (i + 1) * (block_q // block_k), step,
                           jnp.zeros((g, block_q, d), jnp.float32))
    dq_ref[...] = (dq * (q_scale * s_scale)).astype(dq_ref.dtype)


def _sel_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, m_ref, dk_ref, dv_ref,
                        *, block_q, block_k):
    """As `_bwd_dkv_kernel`: owns block_q keys, loops over the tiles of
    block_k queries from the diagonal's first on; m_ref is the transposed
    relation's mask, rows keys, bits and lanes queries."""
    g, _, d = k_ref.shape
    seq_len = q_ref.shape[1]
    i = _own_tile(seq_len, block_q)
    q_scale, s_scale = _split_scale(d)
    k = k_ref[...]
    if q_scale != 1.0:
        k = k * q_scale
    v = v_ref[...]

    def step(j, carry):
        dk, dv = carry
        q = _rows(q_ref, j, block_k)
        do = _rows(do_ref, j, block_k)
        at = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        lse = lse_ref[:, :, at]
        delta = delta_ref[:, :, at]
        s = _dot(k, q, _NT)
        if s_scale != 1.0:
            s = s * s_scale
        s = jnp.where(_tile_bits(m_ref, j, block_k)[None], s, NEG_INF)
        p = jnp.exp(s - lse)
        dv = dv + _dot(p.astype(do.dtype), do, _NN)
        ds = p * (_dot(v, do, _NT) - delta)
        dk = dk + _dot(ds.astype(q.dtype), q, _NN)
        return dk, dv

    zeros = jnp.zeros((g, block_q, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(i * (block_q // block_k), seq_len // block_k, step, (zeros, zeros))
    dk_ref[...] = (dk * (q_scale * s_scale)).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _mask_spec(like, mask, tiles):
    """Block spec of the packed mask (B, t, W) beside (B * heads, t, d)
    operands: the rows of the tile a grid step owns, of the batch row its
    heads belong to."""
    per_row = like.shape[0] // mask.shape[0] // tiles.heads  # grid steps a batch row
    return pl.BlockSpec((1, tiles.block_q, mask.shape[2]), lambda b, i: (b // per_row, i, 0))


def _flash_sel_fwd(q, k, v, mask, *, tiles, interpret):
    own, own_row, whole, _ = _specs(q, tiles)
    return _call(
        _sel_fwd_kernel, "flash_fwd", q, tiles,
        in_specs=[own, whole, whole, _mask_spec(q, mask, tiles)],
        out_specs=[own, own_row],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((q.shape[0], 1, q.shape[1]), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_sel(q, k, v, mask, mask_t, tiles, interpret):
    o, _ = _flash_sel_fwd(q, k, v, mask, tiles=tiles, interpret=interpret)
    return o


def _flash_sel_fwd_rule(q, k, v, mask, mask_t, tiles, interpret):
    # as `_flash_fwd_rule`; `attn_sel` is what the backward needs of the
    # selection: saved, the indexer and the selection run once a layer
    q, k, v = (checkpoint_name(x, name) for x, name in
               ((q, "attn_q"), (k, "attn_k"), (v, "attn_v")))
    mask, mask_t = checkpoint_name(mask, "attn_sel"), checkpoint_name(mask_t, "attn_sel")
    o, lse = _flash_sel_fwd(q, k, v, mask, tiles=tiles, interpret=interpret)
    o, lse = checkpoint_name(o, "attn_out"), checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, o, lse, mask, mask_t)


def _flash_sel_bwd_rule(tiles, interpret, res, do):
    q, k, v, o, lse, mask, mask_t = res
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)[:, None, :]
    own, own_row, whole, whole_row = _specs(q, tiles)
    like_q = jax.ShapeDtypeStruct(q.shape, q.dtype)
    dq = _call(
        _sel_bwd_dq_kernel, "flash_bwd_dq", q, tiles,
        in_specs=[own, whole, whole, own, own_row, own_row, _mask_spec(q, mask, tiles)],
        out_specs=own,
        out_shape=like_q,
        interpret=interpret,
    )(q, k, v, do, lse, delta, mask)
    dk, dv = _call(
        _sel_bwd_dkv_kernel, "flash_bwd_dkv", q, tiles,
        in_specs=[whole, own, own, whole, whole_row, whole_row, _mask_spec(q, mask_t, tiles)],
        out_specs=[own, own],
        out_shape=[like_q, like_q],
        interpret=interpret,
    )(q, k, v, do, lse, delta, mask_t)
    return dq, dk, dv, None, None


_flash_sel.defvjp(_flash_sel_fwd_rule, _flash_sel_bwd_rule)


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, tiles, interpret):
    o, _ = _flash_fwd(q, k, v, tiles=tiles, interpret=interpret)
    return o


def _flash_fwd_rule(q, k, v, tiles, interpret):
    # The backward's residuals by name, for a checkpoint policy to save
    # across a block's remat (models/remat.py): what only the kernel can
    # give, and its operands in its own (bh, t, d) layout. A name that no
    # policy asks for is an identity.
    q, k, v = (checkpoint_name(x, name) for x, name in
               ((q, "attn_q"), (k, "attn_k"), (v, "attn_v")))
    o, lse = _flash_fwd(q, k, v, tiles=tiles, interpret=interpret)
    o, lse = checkpoint_name(o, "attn_out"), checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(tiles, interpret, res, g):
    return _flash_bwd(res, g, tiles=tiles, interpret=interpret)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_causal_attention(q, k, v, *, window=None, block_q=None, block_k=None,
                           interpret=False):
    """q/k/v: (B, H, T, D) → (B, H, T, D); fused causal attention, with
    `window` over the last `window` keys alone (the query's own included).
    Tiles come from `flash_tiles`; block_q / block_k override it (the tests'
    way to reach every tile shape at small sizes)."""
    b, h, t, d = q.shape
    tiles = flash_tiles(b * h, t, d, q.dtype, window)
    if block_q or block_k:
        block_q, block_k = block_q or tiles.block_q, block_k or tiles.block_k
        if t % block_q or block_q % block_k:
            raise ValueError(
                f"block_q ({block_q}) must divide the seq len ({t}) and be a "
                f"multiple of block_k ({block_k}): a grid step's tile is cut "
                "into whole tiles of the other operand along the diagonal")
        tiles = tiles._replace(block_q=block_q, block_k=block_k)
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, d)
    o = _flash(qf, kf, vf, tiles, interpret)
    return o.reshape(b, h, t, d)


def xla_causal_attention(q, k, v, window=None):
    """Plain einsum-softmax reference path; XLA fuses it adequately on TPU."""
    d = q.shape[-1]
    t = q.shape[2]
    s = jnp.einsum("bhtd,bhsd->bhts", q, k, preferred_element_type=jnp.float32)
    s = s / math.sqrt(d)
    mask = jnp.tril(jnp.ones((t, t), dtype=bool))
    if window is not None and window < t:
        mask = mask & ~jnp.tril(jnp.ones((t, t), dtype=bool), -window)
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


def flash_selected_attention(q, k, v, mask, mask_t, top_k, *, interpret=False):
    """q/k/v: (B, H, T, D) -> (B, H, T, D): causal attention over the keys
    that `mask` names, the packed mask (B, T, W) of ops/indexer.py with
    `top_k` keys a query at most, and `mask_t` its transposed relation's.
    Where top_k is the sequence or more every causal key is seen and the
    call is `flash_causal_attention`."""
    b, h, t, d = q.shape
    if top_k >= t:
        return flash_causal_attention(q, k, v, interpret=interpret)
    tiles = flash_tiles(b * h, t, d, q.dtype, select=top_k)
    # a grid step's heads are of one batch row, whose mask they share
    tiles = tiles._replace(heads=math.gcd(tiles.heads, h))
    o = _flash_sel(q.reshape(b * h, t, d), k.reshape(b * h, t, d), v.reshape(b * h, t, d),
                   mask, mask_t, tiles, interpret)
    return o.reshape(b, h, t, d)


def xla_selected_attention(q, k, v, mask):
    """Plain einsum-softmax over the keys the packed mask names."""
    from ray_tpu.ops.indexer import unpack

    s = jnp.einsum("bhtd,bhsd->bhts", q, k, preferred_element_type=jnp.float32)
    s = jnp.where(unpack(mask)[:, None], s / math.sqrt(q.shape[-1]), NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


def selected_attention(q, k, v, mask, mask_t, top_k):
    """Layout-adapting entry, as `causal_attention`: q/k/v (B, T, H, D) ->
    (B, T, H, D), over the keys of the packed mask."""
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    if attention_path(q.shape[1]) == "flash":
        o = flash_selected_attention(qt, kt, vt, mask, mask_t, top_k)
    else:
        o = xla_selected_attention(qt, kt, vt, mask)
    return o.transpose(0, 2, 1, 3)


def _on_tpu() -> bool:
    # No try/except: a worker that cannot reach its chip must fail here, not
    # train on the XLA path in silence.
    return jax.devices()[0].platform == "tpu"


def attention_path(seq_len: int) -> str:
    """Which path `causal_attention` takes for this sequence length on this
    process's backend: "flash" (the pallas kernel) or "xla"."""
    if _on_tpu() and seq_len >= 256 and seq_len % 128 == 0:
        return "flash"
    return "xla"


def causal_attention(q, k, v, window=None):
    """Layout-adapting entry: q/k/v (B, T, H, D) → (B, T, H, D); `window`
    keys a query sees, itself included (None: all before it).

    Uses the pallas flash kernel on TPU for sequences long enough to matter;
    XLA path elsewhere (CPU tests, tiny shapes). A Mosaic kernel cannot be
    partitioned by the compiler: under a multi-device mesh call it through
    `parallel.train_step.attn_for_mesh` (shard_map over batch and heads).
    """
    T = q.shape[1]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if attention_path(T) == "flash":
        o = flash_causal_attention(qt, kt, vt, window=window)
    else:
        o = xla_causal_attention(qt, kt, vt, window)
    return o.transpose(0, 2, 1, 3)
