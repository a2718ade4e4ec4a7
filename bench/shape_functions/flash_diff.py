"""Operations and bytes of the flash calls of ops/attention.py where they
run differential attention's maps (ray_tpu/models/phi4_flash.py): heads of
d lanes whose queries and keys are d / 2 lanes beside d / 2 zeros, so that a
map is applied to a value d wide in one call."""

import re

from bench.shapes import _BYTES, _SHAPE

_CALL = re.compile(r"flash_(?:win(\d+)_)?(fwd|bwd_fused)")


def flash_diff(text, operands=""):
    """The model's own work for the call's shapes, (bh, t, d) results: scores
    d / 2 deep, values d wide. Scores a head: causal t*t/2, or with a window
    w from the call's name w*t - w*w/2 (bench/shape_functions/flash_window.py).
    Forward, two matmuls: QK^T over d / 2, PV over d. Fused backward, five,
    each once: QK^T, dS^T Q and dS K over d / 2; dO V^T and P^T dO over d.
    The zeros the calls carry beside q and k count for nothing, in operations
    and in bytes: forward q and k at d / 2 and v read, o and the float32
    logsumexp written; backward q, k at d / 2, v and dO read with two float32
    rows, dq and dk at d / 2 and dv written. A call without the name or the
    results is none of these."""
    name, _, results = text.partition("->")
    call = _CALL.search(name)
    wide = [r for r in _SHAPE.findall(results) if int(r[2]) > 1 and int(r[3]) > 1]
    if not call or not wide:
        return None
    dtype, bh, t, d = wide[0][0], *map(int, wide[0][1:])
    backward = call.group(2) == "bwd_fused"
    if len(wide) != (3 if backward else 1):
        return None
    seen = min(int(call.group(1) or t), t)
    scores = seen * t - seen * seen // 2
    item = _BYTES[dtype]
    if backward:
        return (2 * scores * bh * (3 * (d // 2) + 2 * d),
                bh * (t * item * (4 * (d // 2) + 3 * d) + 2 * t * 4))
    return (2 * scores * bh * (d // 2 + d), bh * (t * item * (2 * (d // 2) + 2 * d) + t * 4))
