"""What a block under `nn.remat` keeps for its backward pass.

Every block of every family is rematerialised: the backward pass gets the
block's input and computes the rest again. Some of the rest is dear to
compute again and cheap to hold, and a checkpoint policy saves it by name
(`jax.ad_checkpoint.checkpoint_name` where it is made: ops/attention.py's
`_flash_fwd_rule`, the families' MLPs). A name no policy asks for is an
identity.

First rung, always: `attn_out` and `attn_lse`, what only the flash kernel can
give (and `attn_sel` where a layer selects its keys: the packed mask the
backward reads, the dearest thing in such a layer to compute twice; and
`moe_plan` where a layer routes: the choices and the plan's sorts, integers). They cost one more copy of the stream a layer and spare the kernel's
second run. Further rungs by a rule: a family states, beside its blocks, its
other names and what each is worth (`REMAT_RUNGS`: rungs of names that are
only worth saving together, each with the milliseconds of a step it spared
for a GiB held), and `plan` gives each rung a depth: in how many of the
layers that make its names they are saved, the last of them first, since
their backward runs first and lets go of them before most gradients exist.
Under the chips' `bytes_limit` less a margin it takes the depths that spare
most by the rungs' stated worths (ms a GiB x the GiB held at that depth): a
rung too large for every layer is saved in some. It is a reckoning from
shapes, as ops/attention.py's `flash_tiles` is, made at trace time in the
model's `__call__`, where the batch's shape is static (`block_policy`, a
policy a layer); `traced` hands the plan to whoever books it (TrainStep at a
compile). No option selects it and none turns it off.

The constants are calibrated against what a v5e's allocator read of the
benchmark's cells' steps with each set of names saved (PERF.md section 6,
PR 33, and PR 45 for the four routed cells; tests/test_remat.py holds the
readings and the error against them), and at shapes no chip ran against the
step compiled for a described v5e (tests/test_tpu_compile.py).
"""

from __future__ import annotations

import bisect
import itertools
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax

GIB = 1 << 30

FIRST_RUNG = ("attn_out", "attn_lse")

# Bytes a parameter: the float32 weight and two AdamW moments, and the
# float32 gradient beside them while the optimizer runs.
_STATE_BYTES = (12, 4)
# Of the chips' limit, what the reckoned total may reach. The rest is for what
# the reckoning leaves out: the compiler's temporaries beside the largest
# one, fragmentation, and whatever else the process keeps on the chip. The
# reckoning has read up to 0.35 GiB under and 0.85 over what a v5e's
# allocator read of whole rungs (tests/test_remat.py; 0.7 under at a depth),
# and a v5e's 15.6875 GiB (`chip_limit`) less a tenth is 14.119.
_LIMIT_SHARE = 0.9


class StepShape(NamedTuple):
    """One chip's part of a step: `rows` sequences of `seq_len` tokens after
    the batch's split, a parameter's state split over `state_split` chips
    (fsdp x tp), a block's heads and MLP columns over `tp`."""

    rows: int
    seq_len: int
    state_split: int = 1
    tp: int = 1


class RematPlan(NamedTuple):
    """`names` saved across remat in any layer; the bytes saved in each layer
    and over all layers on one chip; the step's reckoned total with them; the
    limit that total was held to (None: no chip said one, first rung alone);
    of the saved bytes those of `attn_sel`, a layer's selection of keys (0
    where no layer selects); what the family stated one block's backward
    works in (`Held.block`; 0 where it states none); each rung's depth,
    (its names, the layers they are saved in, the layers that make them), in
    the order the family states its rungs; and the names each layer's policy
    saves: all of `names` but a rung's that the layer makes and is not to
    save (a name a layer does not make is an identity there, so layers that
    save all they make share one policy)."""

    names: Tuple[str, ...]
    layer_bytes: Tuple[int, ...]
    saved_bytes: int
    reckoned_bytes: int
    limit_bytes: Optional[int]
    sel_bytes: int = 0
    block_bytes: int = 0
    depths: Tuple[Tuple[Tuple[str, ...], int, int], ...] = ()
    by_layer: Tuple[Tuple[str, ...], ...] = ()

    def saved_in(self, *names: str) -> Tuple[bool, ...]:
        """Of each layer, whether its policy saves any of `names`."""
        return tuple(any(n in saved for n in names) for saved in self.by_layer)

    def depth(self, name: str) -> int:
        """In how many layers `name` is saved: its rung's depth (0 where no
        rung has it)."""
        return next((k for rung, k, _ in self.depths if name in rung), 0)


def step_shape(batch_shape, axis_sizes) -> StepShape:
    """The StepShape of a (B, T) batch on a mesh of these axis sizes (none:
    one device), split as parallel/mesh.py:batch_sharding splits it."""
    rows, seq_len = batch_shape
    size = lambda axis: axis_sizes.get(axis, 1)
    return StepShape(max(1, rows // (size("dp") * size("fsdp"))), max(1, seq_len // size("sp")),
                     size("fsdp") * size("tp"), size("tp"))


def chip_limit(stream) -> Optional[int]:
    """The bytes one chip's allocator may hand out, as every process of the
    job reckons it: the least `bytes_limit` (`device.memory_stats()`) of the
    devices this process can ask, among those the residual stream's sharding
    lies on (parallel/mesh.py:stream_sharding; None: one device, the
    process's first), rounded down to a whole 64 MiB. Chips of one kind read
    a few KiB apart from run to run (16,909,336,064 and 16,909,334,528 on
    the v5e: both 251 x 64 MiB, 15.6875 GiB), and every host of a mesh has
    to trace the same program: the rounding is what makes them agree. It
    costs a chip under 64 MiB; a whole GiB, the grain until PR 65, cost the
    v5e 0.75 of its 15.75, 4.7% of the chip, for an agreement that a grain
    a thousand times the chips' difference gives as well. None where a
    device keeps no such count (a CPU device) or none of the mesh's devices
    is this process's (a chip that is described and not attached:
    tests/test_tpu_compile.py). A chip that cannot say raises: a weaker plan
    is not taken in silence."""
    local = set(jax.local_devices())
    asked = jax.local_devices()[:1] if stream is None else [
        d for d in stream.mesh.devices.flat if d in local]
    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in asked]
    if not limits or None in limits:
        return None
    grain = GIB // 16  # 64 MiB
    return min(limits) // grain * grain


class Held(NamedTuple):
    """What one chip holds whatever is saved, in bytes: `always`, through the
    whole step (its shard's state and gradients, each layer's input: one
    copy of the stream a layer); `grads`, the gradients' part of that, which
    fill up through the backward pass as the saved residuals are let go;
    `logits`, the float32 logits, beside which the saved residuals pile up
    while the forward runs; `head`, the logits and their gradient in the
    compute dtype, the head's own fullest moment; `block`, what one block's
    backward works in beside its weights, where a family says it is large
    (an expert layer's buffers over every assignment)."""

    always: int
    grads: int
    logits: int
    head: int
    block: int

    def total(self, saved: int) -> int:
        """The step's reckoned bytes with `saved` bytes of residuals over all
        layers: `always` and the fullest of three moments. The head's own;
        the logits beside everything saved (on the chip the saved residuals
        did not add to the head's moment: gpt2_small read 10.79 GiB with
        0.6, 2.3 and 2.8 GiB of them and 12.29 with 4.5); a block's
        backward, with whatever is saved beyond the gradients still to
        come."""
        return self.always + max(self.head, self.logits + saved,
                                 self.block + max(0, saved - self.grads))


def held_bytes(shape: StepShape, *, params: int, width: int, vocab: int,
               n_layer: int, itemsize: int, block: int = 0) -> Held:
    """Held of a model of `params` parameters, `n_layer` layers on a stream
    `width` wide in a dtype of `itemsize` bytes, and a head `vocab` wide."""
    tokens = shape.rows * shape.seq_len
    state, grads = (n * params // shape.state_split for n in _STATE_BYTES)
    return Held(state + grads + n_layer * tokens * width * itemsize, grads,
                4 * tokens * vocab, (4 + itemsize) * tokens * vocab, block)


def attention_bytes(shape: StepShape, n_head: int, head_dim: int, itemsize: int) -> Dict[str, int]:
    """Bytes a layer, on one chip, of the flash call's named residuals
    (ops/attention.py): the output and the three operands are (rows, T,
    heads * head_dim) each, key-value heads already repeated; the logsumexp
    is a float32 a head and token."""
    tokens = shape.rows * shape.seq_len
    operand = tokens * n_head * head_dim * itemsize // shape.tp
    return {"attn_out": operand, "attn_q": operand, "attn_k": operand, "attn_v": operand,
            "attn_lse": tokens * n_head * 4 // shape.tp}


def layers_of(kinds, *of) -> Tuple[int, ...]:
    """The layers of a model whose layers are of these `kinds` that are one of
    `of`: what a hybrid family's `made_in` says of a name."""
    return tuple(i for i, kind in enumerate(kinds) if kind in of)


def plan(rungs, name_bytes: Dict[str, int], n_layer: int, held: Held,
         limit: Optional[int], first_rung: Tuple[str, ...] = FIRST_RUNG,
         made_in: Optional[Dict[str, Sequence[int]]] = None) -> RematPlan:
    """The first rung (`first_rung`: a family whose attention selects its keys
    holds the selection there too, `attn_sel`) in every layer, and each of
    `rungs` (`(names, ms a step spared for a GiB held)`) at a depth: saved in
    the last k of the layers that make its names, 0 <= k <= all of them.
    `name_bytes` are a name's bytes in one layer that makes it, `made_in` the
    layers that make a name (every one of `n_layer` where it says none). Held
    to a reckoned total (`Held.total` of the bytes saved) under `_LIMIT_SHARE`
    of `limit`: of the depths that stay under it those that spare most
    (`_depths`); with no limit the first rung alone. The first rung
    is taken whatever the limit: it is one more copy of the stream a layer,
    whatever the shape."""
    makers = lambda name: (made_in or {}).get(name, range(n_layer))
    room = None if limit is None else int(limit * _LIMIT_SHARE)
    first = sum(name_bytes[n] * len(makers(n)) for n in first_rung)
    # a rung's layers, the last first, and the bytes it holds at each depth
    rung_layers = [sorted({i for n in names for i in makers(n)}, reverse=True)
                   for names, _ in rungs]
    at_depth = [[0, *itertools.accumulate(
        sum(name_bytes[n] for n in names if i in makers(n)) for i in layers)]
        for (names, _), layers in zip(rungs, rung_layers)]
    depths = _depths([rate for _, rate in rungs], at_depth,
                     lambda saved: room is not None and held.total(first + saved) <= room)
    names = first_rung + tuple(n for (names, _), k in zip(rungs, depths) if k for n in names)
    # a layer's policy leaves out a rung that it makes and is not to save
    left_out = [set(layers[k:]) for layers, k in zip(rung_layers, depths)]
    by_layer = tuple(
        tuple(n for n in names if not any(
            n in rung and i in out for (rung, _), out in zip(rungs, left_out)))
        for i in range(n_layer))
    layer_bytes = tuple(sum(name_bytes[n] for n in saved if i in makers(n))
                        for i, saved in enumerate(by_layer))
    sel = name_bytes["attn_sel"] * len(makers("attn_sel")) if "attn_sel" in names else 0
    return RematPlan(names, layer_bytes, sum(layer_bytes), held.total(sum(layer_bytes)), room,
                     sel, held.block,
                     tuple((rung, k, len(layers))
                           for (rung, _), k, layers in zip(rungs, depths, rung_layers)),
                     by_layer)


def _depths(rates, at_depth, fits) -> Tuple[int, ...]:
    """A depth for each rung, `at_depth[r][k]` the bytes rung r holds at depth
    k, `rates[r]` what a byte of it spares and `fits(bytes)` whether so many
    have room (it holds of every fewer if of any): of all the depths that
    fit, those that spare most; among equals the deeper in the rung that
    spares more a byte. One search over every rung's every depth, whole rungs
    among them, so what a plan spares never rises as the room falls and never
    falls below what whole rungs alone would spare there. The rungs in the
    order of what a byte spares, each from its deepest depth down; a branch
    is left where the room still free, filled with the rungs still to come
    in that order and cut anywhere, would not pass the best found."""
    order = sorted(range(len(rates)), key=lambda r: -rates[r])
    # the most bytes that fit (-1: none do, and nothing is saved beyond the first rung)
    room = bisect.bisect_left(range(sum(by_depth[-1] for by_depth in at_depth) + 1), True,
                              key=lambda saved: not fits(saved)) - 1
    best = [0.0, (0,) * len(rates)]

    def at_most(at, free):
        spared = 0.0
        for r in order[at:]:
            spared += rates[r] * min(free, at_depth[r][-1])
            free -= min(free, at_depth[r][-1])
        return spared

    def search(at, free, spared, depths):
        if spared > best[0]:
            best[:] = spared, depths
        if at == len(order) or spared + at_most(at, free) <= best[0]:
            return
        r = order[at]
        for k in reversed(range(len(at_depth[r]))):
            if at_depth[r][k] <= free:
                search(at + 1, free - at_depth[r][k], spared + rates[r] * at_depth[r][k],
                       depths[:r] + (k,) + depths[r + 1:])

    search(0, room, 0.0, best[1])
    return best[1]


_traced = None  # (the configuration, its RematPlan) of the newest trace


def block_policy(family_plan, cfg, batch_shape, stream):
    """The checkpoint policies of `nn.remat` round the blocks of a model of
    `cfg` on a (B, T) batch, one a layer: layer i's saves the names the
    family's plan (`family_plan(cfg, StepShape, limit)`) saves in layer i
    (`RematPlan.by_layer`), for the batch's part on one chip of the stream's
    mesh, under those chips' limit. Called where the model is traced;
    `traced` hands the plan on."""
    global _traced
    sizes = {} if stream is None else stream.mesh.shape
    chosen = family_plan(cfg, step_shape(batch_shape, sizes), chip_limit(stream))
    _traced = (cfg, chosen)
    policies = {names: jax.checkpoint_policies.save_only_these_names(*names)
                for names in set(chosen.by_layer)}
    return tuple(policies[names] for names in chosen.by_layer)


def traced(cfg) -> Optional[RematPlan]:
    """The plan of the newest trace if it was of a model of `cfg`, else None:
    what the program just compiled saves, for whoever books it."""
    return _traced[1] if _traced is not None and _traced[0] == cfg else None
