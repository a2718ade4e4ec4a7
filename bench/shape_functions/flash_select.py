"""Operations and bytes of the selected flash calls of ops/attention.py,
which say in their name how many keys a query's selection holds at most:
flash_sel<k>_fwd, flash_sel<k>_bwd_dq, flash_sel<k>_bwd_dkv (wrapped by the
transformations they went through)."""

import re

from bench.shapes import flash_attention

_SELECT = re.compile(r"flash_sel(\d+)_")
_SEQ = re.compile(r"\b(?:bf16|f16|f32)\[\d+,(\d+),\d+\]")


def flash_select(text, operands=""):
    """As `bench.shapes.flash_attention` counts a causal call (the same
    matmuls a kernel, the same operands read and results written once), on
    the scores a selection needs: query i attends to min(i + 1, k) keys, so
    k*t - k*k/2 a head in all where the causal call has t*t/2, as
    bench/shape_functions/flash_window.py reckons a window. It is the
    roofline of what the selection needs: the scores a kernel computes of
    pairs the selection leaves out count for nothing. k comes from the name,
    t from the results; a call whose name holds no k, or whose k is not
    less than t, is none of these."""
    name, _, results = text.partition("->")
    select = _SELECT.search(name)
    seq = [int(t) for t in _SEQ.findall(results) if int(t) > 1]
    need = flash_attention(text, operands)
    if not select or not seq or need is None:
        return None
    k, t = int(select.group(1)), seq[0]
    if k >= t:
        return None
    flops, nbytes = need
    return flops * (2 * k * t - k * k) // (t * t), nbytes
