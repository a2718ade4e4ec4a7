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
