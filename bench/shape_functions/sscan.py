"""Operations and bytes of the Mamba-1 recurrence's two pallas calls,
sscan_fwd and sscan_bwd (ray_tpu/ops/selective_scan.py), from their shapes
alone: b, T, C and N, whatever the kernel's chunk or tile."""

import re

_ARRAY = re.compile(r"\b(bf16|f16|f32)\[([\d,]+)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def sscan(text, operands=""):
    """Sizes: u is the first rank-3 operand, (b, T, C), in the stream's
    dtype; A the first rank-2 float32 operand with C along one axis, N the
    other.

    Operations: a channel, state and step forward takes the decay's exponent
    (Delta A), the decay's product with the state, (Delta u) B and its sum
    into the state, and the read-out's product with C and its sum: 6, as the
    family's `flops_per_token` has them; the backward is counted as two
    forwards. **exp is not counted** (one a channel, state and step forward,
    made again in the backward), nor is whatever a kernel spends beyond the
    recurrence (a chunk's states made again, masks, reductions by matmul):
    bench/peaks.json has no line for the vector or the transcendental units,
    so these operations are held to the MXU's peak, beside which they come
    to nothing, and the call is bounded by its bytes.
    Bytes, each array read or written once: forward u, Delta (float32) and y
    (b, T, C), B and C (b, T, N) in u's dtype, A (C, N) and D (C) float32;
    backward those with dy in and du, dDelta, dB and dC (float32) and dA, dD
    out. The states a kernel writes at its chunks' starts, and B and C laid
    out as it reads them, are an implementation's and are left out."""
    name = text.partition(" custom-call")[0]
    backward = "sscan_bwd" in name
    if not backward and "sscan_fwd" not in name:
        return None
    arrays = [(d, tuple(map(int, s.split(",")))) for d, s in _ARRAY.findall(operands)]
    wide = [(d, s) for d, s in arrays if len(s) == 3 and s[1] > 1]
    if not wide:
        return None
    dtype, (b, t, c) = wide[0]
    rates = [s for d, s in arrays if d == "f32" and len(s) == 2 and c in s and min(s) > 1]
    if not rates:
        return None
    n = rates[0][0] if rates[0][1] == c else rates[0][1]
    item = _BYTES[dtype]
    forward = 6 * b * t * c * n
    stream, shared, fixed = b * t * c, b * t * n, (c * n + c) * 4
    if backward:
        return (2 * forward,
                stream * (3 * item + 2 * 4) + shared * (2 * item + 2 * 4) + 2 * fixed)
    return forward, stream * (2 * item + 4) + 2 * shared * item + fixed
