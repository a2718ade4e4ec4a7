"""Operations and bytes of the scalar-decay delta rule's two pallas calls,
gdn_fwd and gdn_bwd (ray_tpu/ops/gdn.py), from their shapes alone: the
chunked form at the chunk the call was made with and one decay a value head
and step, whatever the kernel does inside."""

import math
import re

_ARRAY = re.compile(r"\b(bf16|f16|f32)\[([\d,]+)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def gdn(text, operands=""):
    """Sizes: q is the first rank-3 operand, (b, T, Hk K), k the one after it
    and v the third, (b, T, Hv V); the chunk states (b, T / C, Hv V, K)
    float32 are the forward's rank-4 result and the backward's last rank-4
    operand whose last axis is more than 1; the decays and rates are the one
    float32 operand whose entries are a whole number a token, fewer than Hk K,
    two (g and beta) a value head, however an implementation lays them out.

    Forward, a chunk of C steps of one value head: the two products inside a
    chunk, (k k^T) and (q k^T) under Gamma, the half of each that causality
    needs, 2 C C K together, counted a value head whether or not the value
    heads of a key head share the raw products; the unit-triangular solve of
    [W | U], K + V columns by forward substitution, C C (K + V); U - W S, the
    state's read (q exp(G)) S and its update k^T Vn, 2 C K V each; A_qk Vn,
    C C V. The backward is counted as bench/shape_functions/kda.py counts its
    own: two forwards plus the products inside a chunk and the solve made
    again. Not counted: the exps, Gamma, the element-wise products, the
    cumulative sum, the l2 norms, and whatever an implementation spends
    beyond this form (an explicit inverse, products masked rather than
    skipped).
    Bytes, each read or written once: forward q and k (once a **key** head),
    v, g and beta in, o and the states out; backward those, do and the
    states in, and dq, dk, dv, dg and dbeta out."""
    name = text.partition(" custom-call")[0]
    backward = "gdn_bwd" in name
    if not backward and "gdn_fwd" not in name:
        return None
    arrays = [(d, tuple(map(int, s.split(",")))) for d, s in _ARRAY.findall(operands)]
    results = [(d, tuple(map(int, s.split(","))))
               for d, s in _ARRAY.findall(text.split("->", 1)[-1])]
    states = [s for d, s in (arrays if backward else results)
              if d == "f32" and len(s) == 4 and s[-1] > 1]
    wide = [(d, s) for d, s in arrays if len(s) == 3 and s[1] > 1]
    if not states or len(wide) < 3:
        return None
    (q_dtype, (b, t, hk_k)), (v_dtype, (_, _, hv_v)) = wide[0], wide[2]
    _, chunks, _, k = states[-1]
    rates = [math.prod(s) for d, s in arrays
             if d == "f32" and math.prod(s) < b * t * hk_k and math.prod(s) % (2 * b * t) == 0]
    if not rates:
        return None
    hv = rates[0] // (2 * b * t)
    v, c = hv_v // hv, t // chunks
    inside = 2 * c * c * k + c * c * (k + v)
    forward = b * chunks * hv * (inside + 6 * c * k * v + c * c * v)
    keys, values = b * t * hk_k * _BYTES[q_dtype], b * t * hv_v * _BYTES[v_dtype]
    rate_bytes, state_bytes = 2 * b * t * hv * 4, b * chunks * hv_v * k * 4
    if backward:
        return (2 * forward + b * chunks * hv * inside,
                4 * keys + 3 * values + 2 * rate_bytes + state_bytes)
    return forward, 2 * keys + 2 * values + rate_bytes + state_bytes
