"""Operations and bytes of the windowed flash calls of ops/attention.py,
which say their window in their name: flash_win<w>_fwd, flash_win<w>_bwd_dq,
flash_win<w>_bwd_dkv (wrapped by the transformations they went through)."""

import re

from bench.shapes import flash_attention

_WINDOW = re.compile(r"flash_win(\d+)_")
_SEQ = re.compile(r"\b(?:bf16|f16|f32)\[\d+,(\d+),\d+\]")


def flash_window(text, operands=""):
    """As `bench.shapes.flash_attention` counts a causal call (the same
    matmuls a kernel, the same operands read and results written once), on
    the scores a window needs: a query sees min(i + 1, w) keys, w*t - w*w/2
    a head in all where the causal call has t*t/2. The window comes from
    the name, t from the results; a call whose name holds no window, or
    whose window is not shorter than t, is none of these."""
    name, _, results = text.partition("->")
    window = _WINDOW.search(name)
    seq = [int(t) for t in _SEQ.findall(results) if int(t) > 1]
    need = flash_attention(text, operands)
    if not window or not seq or need is None:
        return None
    w, t = int(window.group(1)), seq[0]
    if w >= t:
        return None
    flops, nbytes = need
    return flops * (2 * w * t - w * w) // (t * t), nbytes
