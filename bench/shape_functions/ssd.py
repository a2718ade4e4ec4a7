"""Operations and bytes of the Mamba-2 scan's two pallas calls, ssd_fwd and
ssd_bwd (ray_tpu/ops/ssd.py), from their shapes alone: the chunked dual form
at the chunk the call was made with, whatever the kernel's tiling."""

import math
import re

_ARRAY = re.compile(r"\b(bf16|f16|f32)\[([\d,]+)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def ssd(text, operands=""):
    """Sizes: x is the first operand, (b, T, H P); B and C the last two
    rank-3 operands, (b, T, G N); the chunk states (b, T / Q, H P, N) float32
    are the forward's second result and the backward's last operand; Delta is
    the first float32 operand smaller than x, and H is the count of its
    elements over b T, however an implementation lays them out.

    Forward, a chunk of Q steps: a head's (C B^T * L)(Delta x), 2 Q Q P, its
    read of the carried state and its own new state, 4 Q N P, and a group's
    C B^T, 2 Q Q N. The backward is counted as two forwards plus C B^T made
    again. The exp of L and every element-wise product are not counted.
    Bytes, each read or written once: forward x, Delta, B, C in and y, the
    states out; backward x, dy, Delta, B, C, the states in and dx, dDelta,
    dB, dC out (c, the cumulative sum the calls are also handed, is Delta's
    size and is an implementation's, so it is left out; dB and dC in the
    operands' dtype)."""
    name = text.partition(" custom-call")[0]
    backward = "ssd_bwd" in name
    if not backward and "ssd_fwd" not in name:
        return None
    arrays = [(d, tuple(map(int, s.split(",")))) for d, s in _ARRAY.findall(operands)]
    results = [(d, tuple(map(int, s.split(","))))
               for d, s in _ARRAY.findall(text.split("->", 1)[-1])]
    states = [s for d, s in (arrays if backward else results) if d == "f32" and len(s) == 4]
    wide = [(d, s) for d, s in arrays if len(s) == 3]
    if not states or len(wide) < 3:
        return None
    (x_dtype, (b, t, hp)) = wide[0]
    shared_dtype, (_, _, gn) = wide[-1]
    _, chunks, _, n = states[-1]
    deltas = [math.prod(s) for d, s in arrays
              if d == "f32" and math.prod(s) < b * t * hp and math.prod(s) % (b * t) == 0]
    if not deltas:
        return None
    h = deltas[0] // (b * t)
    p, q, g = hp // h, t // chunks, gn // n
    forward = b * chunks * (h * (2 * q * q * p + 4 * q * n * p) + g * 2 * q * q * n)
    x_bytes, shared = b * t * hp * _BYTES[x_dtype], b * t * gn * _BYTES[shared_dtype]
    delta, state_bytes = b * t * h * 4, b * chunks * hp * n * 4
    if backward:
        return (2 * forward + b * chunks * g * 2 * q * q * n,
                3 * x_bytes + 2 * delta + 4 * shared + state_bytes)
    return forward, 2 * x_bytes + delta + 2 * shared + state_bytes
