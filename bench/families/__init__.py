"""One file per model family, found by the name a configuration file gives
under "family". Each holds what belongs to a family:

    build(sizes, compute_dtype)   the program's own config object for these
                                  sizes (the only place that names models/)
    matmul_params(sizes)          parameters that take part in a matmul
    flops_per_token(sizes, T)     model FLOPs a token, forward and backward
    layer_names(sizes)            the parameter groups that are layers
    embed(outer, idx, sizes)      } the plain float32 reference in three
    layer(x, blk, sizes)          } pieces, written from the published
    head_loss(outer, x, targets, sizes)  } equations and independent of
                                  models/ and ops/: no kernel, no bf16.
                                  `outer` is every parameter group that is
                                  not a layer.

The reference comes in pieces so that the harness can take its gradient a
layer at a time (7B widths at 8k tokens do not fit otherwise); composed, they
are the whole forward pass and loss.
"""

import importlib


def load(family):
    return importlib.import_module(f"bench.families.{family}")


def split_params(family, params, sizes):
    names = family.layer_names(sizes)
    return names, {k: v for k, v in params.items() if k not in names}


def reference_loss(family, params, idx, targets, sizes):
    """The pieces composed: loss of the whole model on one batch."""
    names, outer = split_params(family, params, sizes)
    x = family.embed(outer, idx, sizes)
    for name in names:
        x = family.layer(x, params[name], sizes)
    return family.head_loss(outer, x, targets, sizes)
