"""Operations and bytes of the block-diffusion flash calls of
ops/attention.py, which say their block length in their name:
flash_bd<L>_fwd and flash_bd<L>_bwd_fused (wrapped by the transformations
they went through)."""

import re

from bench.shapes import _BYTES, _SHAPE

_CALL = re.compile(r"flash_bd(\d+)_(fwd|bwd_fused)")


def flash_blockdiff(text, operands=""):
    """From the mask's definition, whatever implements the call: the results
    are (bh, t, d) over a doubled stream [noised | clean] of t = 2T
    positions in blocks of L (the name's). A noised query in block b sees its
    own block and the b clean blocks before it, a clean one the b + 1 clean
    blocks up to its own: L (b + 1) keys each, T^2 + T L pairs a head of the
    (2T)^2. Forward, two matmuls of those pairs x d multiply-adds (QK^T,
    PV); fused backward, five, each once (QK^T, dO V^T, P^T dO, dS^T Q,
    dS K). Bytes, every operand read and every result written once: forward
    q, k, v and o with the float32 logsumexp; backward q, k, v, o, dO and the
    logsumexp read, dq, dk, dv written. A call without the name or the results
    is none of these."""
    name, _, results = text.partition("->")
    call = _CALL.search(name)
    wide = [r for r in _SHAPE.findall(results) if int(r[2]) > 1 and int(r[3]) > 1]
    if not call or not wide:
        return None
    dtype, bh, t, d = wide[0][0], *map(int, wide[0][1:])
    backward = call.group(2) == "bwd_fused"
    if len(wide) != (3 if backward else 1) or t % 2:
        return None
    half, length = t // 2, int(call.group(1))
    pairs = half * half + half * length
    matmuls, arrays = (5, 8) if backward else (2, 4)
    return matmuls * 2 * pairs * d * bh, bh * (arrays * t * d * _BYTES[dtype] + t * 4)
