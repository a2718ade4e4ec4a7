"""Operations and bytes of the latent attention's two pallas calls,
flash_mla_fwd and flash_mla_bwd_fused (ray_tpu/ops/attention.py), from their
name and shapes alone: the work the mathematics needs, whatever implements
it."""

import collections
import math
import re

_ARRAY = re.compile(r"\b(bf16|f16|f32)\[([\d,]+)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def _arrays(text):
    return [(dtype, tuple(map(int, dims.split(",")))) for dtype, dims in _ARRAY.findall(text)]


def _is_row(array):
    """A float32 (heads, 1, t) array: the logsumexp's rows."""
    dtype, dims = array
    return dtype == "f32" and len(dims) == 3 and dims[1] == 1


def _widths(arrays, per_head):
    """Elements a head and token of each array, the smallest array left out:
    the shared key among the operands, its gradient among the results."""
    sizes = sorted(math.prod(dims) for _, dims in arrays)[1:]
    return [n // per_head for n in sizes]


def flash_mla(text, operands=""):
    """Sizes. The float32 rows (heads, 1, t) (the logsumexp: a result of the
    forward call, an operand of the backward) give the heads of all batch
    rows and t; every other array's width is its elements a head and token.
    The value width v is the forward's wide result's and, in the backward,
    the width that v, o and dO share. The smallest array is the shared key
    (among the results, its gradient). The other wide arrays are the query
    and the heads' own key part, n wide, and the query's second part, r
    wide, apart (the narrowest is r) or as one (n + r): their widths add up
    to 2 n + r either way, and tell n and r apart by their count.

    Scores a head: causal, t * t / 2. Forward: QK^T over n + r and PV over
    v, 2 * scores * (n + r + v) operations. Fused backward: QK^T, dS^T Q and
    dS K over n + r, dO V^T and P^T dO over v: 2 * scores * (3 (n + r) + 2 v).
    Bytes, each once: q (n + r), the head's own keys (n), v and o a head and
    token, with dO read and dq, dk, dv written in the backward; the float32
    row; the shared key and, in the backward, its float32 gradient once a
    token: the shapes do not say how many batch rows share the heads, so of
    one row at least, t * r each. A kernel that pads the value, repeats the
    shared key a head or computes masked tiles needs more than this and
    reads a lower share, never a higher."""
    name, _, results = text.partition("->")
    backward = "flash_mla_bwd_fused" in name
    if not backward and "flash_mla_fwd" not in name:
        return None
    outs, ins = _arrays(results), _arrays(operands)
    rows = [dims for _, dims in filter(_is_row, ins if backward else outs)]
    if not rows:
        return None
    heads, _, t = rows[0]
    per_head = heads * t
    wide_in, wide_out = ([a for a in arrays if not _is_row(a)] for arrays in (ins, outs))
    if not wide_in or not wide_out:
        return None
    item = _BYTES[wide_in[0][0]]
    if backward:
        v = collections.Counter(_widths(wide_in, per_head)).most_common(1)[0][0]
        parts = _widths(wide_out, per_head)
    else:
        v = math.prod(wide_out[0][1]) // per_head
        parts = _widths(wide_in, per_head)
    if v not in parts:
        return None
    parts.remove(v)
    if len(parts) == 3:    # q's two parts apart: r, n, n
        r, n = parts[0], parts[1]
    elif len(parts) == 2:  # q whole: n, n + r
        n, r = parts[0], parts[1] - parts[0]
    else:
        return None
    depth = n + r
    scores = t * t // 2
    if backward:
        flops = 2 * scores * (3 * depth + 2 * v) * heads
        nbytes = per_head * (2 * (depth + n + v) + 2 * v) * item + per_head * 4 + t * r * (item + 4)
    else:
        flops = 2 * scores * (depth + v) * heads
        nbytes = per_head * (depth + n + 2 * v) * item + per_head * 4 + t * r * item
    return flops, nbytes
