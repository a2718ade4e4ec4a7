"""Operations and bytes a kernel call needs, from its shapes alone. A
per-layer metric names one of these functions; a new kernel adds one.

Each takes the kind of a device event (bench/trace.py: op name, opcode and
result shapes, "attn custom-call -> (bf16[1536,256,64], f32[1536,1,256])")
and returns (flops, bytes) for one such call, or None where the kind is
not a call of that kernel.
"""

import re

_SHAPE = re.compile(r"\b(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def flash_attention(text):
    """The three pallas calls of ops/attention.py on (bh, t, d) operands,
    told apart by their results: forward (o, lse), dq (dq), dkv (dk, dv).

    Causal, so half of the t x t score tile is needed. Matmuls of t*t/2*d
    multiply-adds each: forward 2 (QK^T, PV); dq 3 (QK^T, dO V^T, dS K); dkv
    4 (QK^T, P^T dO, dO V^T, dS^T Q). Each call is counted for what its own
    results need, so what the two backward calls both recompute is counted
    in both. Bytes: every operand read once, every result written once.
    """
    results = _SHAPE.findall(text.split("->", 1)[-1])
    if not results:
        return None
    wide = [r for r in results if int(r[3]) > 1 and int(r[2]) > 1]
    if not wide:
        return None
    dtype, bh, t, d = wide[0][0], *map(int, wide[0][1:])
    lse_out = any(int(r[2]) == 1 for r in results)  # (bh, 1, t) float32
    if lse_out:
        matmuls, reads, writes, rows_in, rows_out = 2, 3, 1, 0, 1
    elif len(wide) >= 2:
        matmuls, reads, writes, rows_in, rows_out = 4, 4, 2, 2, 0
    else:
        matmuls, reads, writes, rows_in, rows_out = 3, 4, 1, 2, 0
    flops = matmuls * 2 * (t * t // 2) * d * bh
    nbytes = bh * ((reads + writes) * t * d * _BYTES[dtype] + (rows_in + rows_out) * t * 4)
    return flops, nbytes


FUNCTIONS = {"flash_attention": flash_attention}
