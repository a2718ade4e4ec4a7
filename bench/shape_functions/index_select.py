"""Bytes of the selection, the pallas call that ops/indexer.py names
index_select: (B, t, t) float32 scores -> the packed mask (B, t, W) int32 of
each query's top-k keys at or before it."""

import re

_SCORES = re.compile(r"\bf32\[(\d+),(\d+),(\d+)\]")
_MASK = re.compile(r"\bs32\[(\d+),(\d+),(\d+)\]")


def index_select(text, operands=""):
    """What a selection needs of the memory: the causal half of the scores
    read once, the mask written once. Its compares and counts are the
    vector unit's and no matmul's: no operations are counted, so the share
    is of the time the bytes alone would take, whatever the algorithm."""
    scores = _SCORES.search(operands)
    mask = _MASK.search(text.split("->", 1)[-1])
    if "index_select" not in text.partition("->")[0] or not scores or not mask:
        return None
    b, t, _ = map(int, scores.groups())
    _, _, width = map(int, mask.groups())
    return 0, 4 * b * t * t // 2 + 4 * b * t * width
