"""models/granite.py's cell compiled for a described TPU v5e, as
tests/test_tpu_compile.py and with no chip: the scan's two kernels at
`granite4_h_micro_l10.t4096`'s shape."""

import jax
import jax.numpy as jnp

from tests._tpu_compile import _CUSTOM_CALL


def test_scan_kernels_compile_at_the_cell_s_shape(one_chip):
    """granite4_h_micro_l10.t4096's Mamba layers: 64 heads of 64 with a state
    of 128 over 4,096 positions in chunks of 256, forward and backward, each
    a pallas call under its name; what the forward leaves for the backward is
    the chunk states, 33.6 MB."""
    from ray_tpu.ops import ssd

    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
    x, shared = shape((1, 4096, 64, 64), jnp.bfloat16), shape((1, 4096, 1, 128), jnp.bfloat16)
    dt, head = shape((1, 4096, 64), jnp.float32), shape((64,), jnp.float32)

    def loss(x, dt, a, b, c, d):
        return ssd.ssd(x, dt, a, b, c, d, 256, interpret=False)[0].astype(jnp.float32).sum()

    c = jax.jit(jax.grad(loss, argnums=range(6))).lower(x, dt, head, shared, shared, head).compile()
    names = _CUSTOM_CALL.findall(c.as_text())
    # wrapped by the transformations it went through, as the trace shows it
    assert len(names) == 2 and sum("ssd_fwd" in n for n in names) == 1 \
        and sum("ssd_bwd" in n for n in names) == 1, names
    states = 16 * 64 * 64 * 128 * 4
    assert states < c.memory_analysis().temp_size_in_bytes < 4 * states


def test_convolution_kernels_compile_at_the_cell_s_shape(one_chip):
    """granite4_h_micro_l10.t4096's Mamba layers' convolution: 4352 channels
    over (1, 4096) tokens and 4 taps with a bias under
    silu, read where the input projection wrote them (after 4,096 lanes of z,
    before 64 of dt) and written as x, B and C, forward and backward, each a
    pallas call under its name;
    the backward writes x's gradient into the buffer that holds its
    neighbours' (no copy of it beside the call), and nothing is left for the
    backward but the operands."""
    from ray_tpu.ops import short_conv

    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
    wide = shape((1, 4096, 4096 + 4352 + 64), jnp.bfloat16)
    taps, bias = shape((4, 4352), jnp.float32), shape((4352,), jnp.float32)
    # a block is a tile's rows of all of x, and the calls cut y themselves
    assert short_conv._cut(4096, 4352, (4096, 4224)) == (
        256, 4352, 64, (4096, 4224))

    def loss(wide, taps, bias):
        outs = short_conv.causal_conv_within(wide, taps, bias, 4096, (4096, 4224), interpret=False)
        # kept: the forward call is not dead code
        return sum(v.astype(jnp.float32).sum() for v in outs), outs

    c = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True)).lower(wide, taps, bias).compile()
    text = c.as_text()
    names = _CUSTOM_CALL.findall(text)
    assert len(names) == 2 and sum("causal_conv_fwd" in n for n in names) == 1 \
        and sum("causal_conv_bwd" in n for n in names) == 1, names
    # the gradient's buffer is the call's own result: XLA put no copy before it
    assert "causal_conv_bwd" in text and "output_to_operand_aliasing" in text
