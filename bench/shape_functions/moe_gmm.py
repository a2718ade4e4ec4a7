"""Operations and bytes of the grouped matmuls of ops/moe.py's ExpertShare.
They are megablox's pallas kernels (jax.experimental.pallas.ops.tpu), which
the compiler names gmm and tgmm; they are found by those names and told
apart by their shapes."""

import re

_ARRAY = re.compile(r"\b(bf16|f16|f32)\[([\d,]+)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}

# ops/moe.py:_ROW_HEADROOM: the buffer a step's rows are gathered into has
# room for this many times the rows an even routing sends here. No operand
# shows it; it is the program's constant, stated here as there.
ROW_HEADROOM = 1.5


def moe_gmm(text, operands=""):
    """Two kinds of call. gmm: rows times their expert's matrix, (M, K) x
    (G, K, N) -> (M, N), forward, or (M, N) x (G, K, N) -> (M, K), the
    gradient to the rows. tgmm: the gradient of the matrices, (K, M) x (M, N)
    -> (G, K, N). M, K, N and G (the experts held here) are read from the
    last two operands and the result.

    M is the buffer's rows, not the rows worked on: which rows a step
    routed to the experts held here is decided on the device and no shape
    shows it. Counted is the even-routing load, M / ROW_HEADROOM rows at
    2*K*N operations each (at the benchmark's shape 49,152 / 1.5 = 32,768
    = 16,384 tokens x 8 experts a token x 16 of 64 experts held); a run's
    own rows are `telemetry/moe_rows_held` a layer, its share
    `telemetry/moe_held_share` (0.25 where routing is even). A step that
    routes more than the headroom here takes a buffer of every row, and its
    calls, counted the same way, read high: a share over 0.375 says so.
    Bytes: those rows read and written once, every held matrix once."""
    results = _ARRAY.findall(text.split("->", 1)[-1])
    arrays = [(d, tuple(map(int, s.split(",")))) for d, s in _ARRAY.findall(operands)]
    if len(results) != 1 or len(arrays) < 2:
        return None
    out_dtype, out = results[0][0], tuple(map(int, results[0][1].split(",")))
    (a_dtype, a), (b_dtype, b) = arrays[-2:]
    if len(out) == 2 and len(a) == 2 and len(b) == 3 and a[0] == out[0]:
        m, kn, g = a[0], b[1] * b[2], b[0]
        widths = a[1] * _BYTES[a_dtype] + out[1] * _BYTES[out_dtype]
        matrices = _BYTES[b_dtype]
    elif len(out) == 3 and len(a) == 2 and len(b) == 2 and a[1] == b[0]:
        m, kn, g = b[0], out[1] * out[2], out[0]
        widths = a[0] * _BYTES[a_dtype] + b[1] * _BYTES[b_dtype]
        matrices = _BYTES[out_dtype]
    else:
        return None
    rows = int(m / ROW_HEADROOM)
    return 2 * rows * kn, rows * widths + g * kn * matrices
