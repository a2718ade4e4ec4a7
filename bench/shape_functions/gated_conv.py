"""Operations and bytes of the gated short convolution's two pallas calls,
gated_conv_fwd and gated_conv_bwd (ray_tpu/ops/short_conv.py), from their
shapes alone, whatever the kernel's tiling."""

import re

_ARRAY = re.compile(r"\b(bf16|f16|f32)\[([\d,]+)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def gated_conv(text, operands=""):
    """Sizes: the streams B, C, u side by side are the first operand, (b, T,
    3 d), in both calls; the taps (k, d) are the one rank-2 operand. An
    operand handed to the call more than once (a second view of the same
    array) is counted once: the equations read each element once.

    Forward, a token and channel: B * u, k multiplies and k - 1 adds, the
    gate C: 2 k + 1 operations (the issue's 2 k + 2 counts the rounding).
    Bytes: 3 d elements read and d written a token, the taps once.
    Backward: g = C * dy, the convolution made again for dC (2 k), dz the
    convolution run backwards (2 k - 1), dB and du from dz, dC from dy, and the
    taps' gradient (2 k): 6 k + 4. Bytes: dy (d) and the streams (3 d) read,
    the streams' gradients (3 d) written a token; the taps read and their
    float32 gradient written once."""
    name = text.partition(" custom-call")[0]
    backward = "gated_conv_bwd" in name
    if not backward and "gated_conv_fwd" not in name:
        return None
    arrays = [(d, tuple(map(int, s.split(",")))) for d, s in _ARRAY.findall(operands)]
    streams = [(d, s) for d, s in arrays if len(s) == 3 and s[2] % 3 == 0]
    taps = [s for d, s in arrays if len(s) == 2]
    if not streams or not taps:
        return None
    dtype, (b, t, d3) = streams[0]
    k, d = taps[0]
    if 3 * d != d3:
        return None
    tokens, item = b * t, _BYTES[dtype]
    if backward:
        return (tokens * d * (6 * k + 4), tokens * 7 * d * item + 2 * k * d * 4)
    return tokens * d * (2 * k + 1), tokens * 4 * d * item + k * d * 4
