"""Operations and bytes of the indexer's scores, the pallas call that
ops/indexer.py names index_scores: (B, J, t, E) query heads against one
(B, t, E) key head and a (B, t, J) weight a head -> (B, t, t) float32."""

import re

_ARRAY = re.compile(r"\b(bf16|f16|f32)\[([\d,]+)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def index_scores(text, operands=""):
    """Every causal pair once: 2 x J x E operations a pair (the heads' dot
    products; the ReLU, the weight and the sum over heads are not counted),
    t*t/2 pairs a row of the batch. Bytes: the operands read once, the causal
    half of the float32 scores written once."""
    arrays = [(d, tuple(map(int, s.split(",")))) for d, s in _ARRAY.findall(operands)]
    heads = [(d, s) for d, s in arrays if len(s) == 4]
    results = _ARRAY.findall(text.split("->", 1)[-1])
    if "index_scores" not in text.partition("->")[0] or not heads or len(results) != 1:
        return None
    _, (b, j, t, e) = heads[0]
    pairs = b * t * t // 2
    read = sum(_BYTES[d] * _prod(s) for d, s in arrays)
    return 2 * j * e * pairs, read + _BYTES[results[0][0]] * pairs


def _prod(shape):
    n = 1
    for x in shape:
        n *= x
    return n
