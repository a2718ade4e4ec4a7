"""Device-mesh construction and sharding-rule utilities.

This is the TPU-native substitute for the reference's NCCL process groups
(reference: python/ray/util/collective/collective_group/nccl_collective_group.py):
instead of creating communicator handles and calling collectives imperatively,
we build a `jax.sharding.Mesh` over the slice's devices, annotate arrays with
`NamedSharding`s, and let XLA insert ICI collectives during compilation
(psum/all-gather/reduce-scatter chosen by the partitioner). The step program
also says where the residual stream lives (`stream_sharding`: the batch's own
split, the hidden dimension whole), so the partitioner gathers weights over
fsdp and leaves the activations where they are.

Axis conventions used across the framework:
  dp    — data parallel (batch dimension)
  fsdp  — parameter/optimizer sharding (ZeRO-style), usually merged with dp
  tp    — tensor parallel (hidden/heads dimension)
  sp    — sequence/context parallel (ring attention rides this axis)
  ep    — expert parallel (MoE)
  pp    — pipeline stages (handled by the compiled-DAG layer, not the mesh)
"""

from __future__ import annotations

import collections
import math
import re
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(
    axes: Dict[str, int],
    *,
    devices: Optional[Sequence] = None,
    allow_split_physical: bool = True,
) -> Mesh:
    """Build a Mesh with the given axis sizes (-1 once to mean 'the rest').

    Axis order in `axes` is the layout order: the last axis varies fastest over
    the device list, so put the most bandwidth-hungry axis (tp, then dp) last —
    adjacent devices share the fastest ICI links.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    sizes = dict(axes)
    unknown = [k for k, v in sizes.items() if v == -1]
    if len(unknown) > 1:
        raise ValueError("only one axis may be -1")
    if unknown:
        known = math.prod(v for v in sizes.values() if v != -1)
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        sizes[unknown[0]] = n // known
    total = math.prod(sizes.values())
    if total != n:
        raise ValueError(f"mesh axes {sizes} need {total} devices, have {n}")
    arr = np.array(devices).reshape(*sizes.values())
    return Mesh(arr, tuple(sizes.keys()))


def single_axis_mesh(name: str = "dp", devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.array(devices), (name,))


class ShardingRules:
    """Map parameter-path regexes to PartitionSpecs.

    Rules are checked in order; first match wins. Paths are '/'-joined pytree
    key paths, e.g. 'transformer/h_3/attn/c_attn/kernel'.
    """

    def __init__(self, rules: Sequence[Tuple[str, P]], default: P = P()):
        self._rules = [(re.compile(pat), spec) for pat, spec in rules]
        self._default = default

    def spec_for(self, path: str) -> P:
        for pat, spec in self._rules:
            if pat.search(path):
                return spec
        return self._default

    def tree_specs(self, tree):
        """PartitionSpec pytree matching `tree`'s structure."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs = []
        for keypath, _leaf in flat:
            path = "/".join(_key_str(k) for k in keypath)
            specs.append(self.spec_for(path))
        return jax.tree_util.tree_unflatten(treedef, specs)

    def tree_shardings(self, tree, mesh: Mesh):
        specs = self.tree_specs(tree)
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def filter_spec_for_mesh(spec: P, mesh: Mesh, shape=None) -> P:
    """Drop axis names the mesh doesn't have (lets one rule set serve many
    mesh shapes — e.g. tp rules are no-ops on a pure-dp mesh) and, given the
    array's shape, axes whose size does not divide their dimension: GPT-2's
    V=50257 embedding stays replicated over tp=2 instead of failing to place.
    """
    def keep(i, entry):
        names = entry if isinstance(entry, (tuple, list)) else (entry,)
        kept, span = [], 1
        for e in names:
            if e is None or e not in mesh.axis_names or mesh.shape[e] == 1:
                continue
            if shape is not None and shape[i] % (span * mesh.shape[e]):
                continue
            kept.append(e)
            span *= mesh.shape[e]
        if not kept:
            return None
        return tuple(kept) if isinstance(entry, (tuple, list)) else kept[0]

    return P(*(keep(i, e) for i, e in enumerate(spec)))


def filtered_tree_specs(rules: ShardingRules, tree, mesh: Mesh):
    """Rule-derived PartitionSpecs with axes the mesh lacks, or that do not
    divide the leaf's dimension, dropped."""
    specs = rules.tree_specs(tree)
    return jax.tree.map(
        lambda s, leaf: filter_spec_for_mesh(s, mesh, getattr(leaf, "shape", None)),
        specs, tree, is_leaf=lambda x: isinstance(x, P))


def filtered_tree_shardings(rules: ShardingRules, tree, mesh: Mesh):
    specs = filtered_tree_specs(rules, tree, mesh)
    return specs, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                               is_leaf=lambda x: isinstance(x, P))


def shard_tree(tree, mesh: Mesh, rules: ShardingRules):
    """device_put a pytree with rule-derived (mesh-filtered) shardings."""
    _, shardings = filtered_tree_shardings(rules, tree, mesh)
    return jax.device_put(tree, shardings), shardings


def batch_sharding(mesh: Mesh, *, data_axes=("dp", "fsdp"), seq_axis="sp") -> NamedSharding:
    """Sharding for a [batch, seq, ...] input batch."""
    data = tuple(a for a in data_axes if a in mesh.axis_names and mesh.shape[a] > 1)
    seq = seq_axis if seq_axis in mesh.axis_names and mesh.shape[seq_axis] > 1 else None
    return NamedSharding(mesh, P(data if data else None, seq))


def stream_sharding(mesh: Mesh) -> Optional[NamedSharding]:
    """Where a model's residual stream [batch, seq, hidden] lives: split as
    the batch is (`batch_sharding`), the hidden dimension whole, which is
    also what Megatron tp wants of it. None on a one-device mesh: there is
    nothing to say, and the step lowers as it does without."""
    if mesh.devices.size == 1:
        return None
    return NamedSharding(mesh, P(*batch_sharding(mesh).spec, None))


def pin(x, sharding: Optional[NamedSharding]):
    """`x` held to `sharding` inside a jitted program; `x` itself where
    there is none. A NamedSharding carries its mesh: no ambient one."""
    return x if sharding is None else jax.lax.with_sharding_constraint(x, sharding)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


class Collective(NamedTuple):
    """One kind of cross-device operation of a compiled program, by the
    array it leaves on each device."""

    kind: str  # all-reduce, all-gather, all-to-all, reduce-scatter, collective-permute
    dtype: str
    shape: Tuple[int, ...]

    @property
    def nbytes(self) -> int:
        bits = re.search(r"\d+", self.dtype)  # pred has none: a byte
        return math.prod(self.shape) * (int(bits.group()) if bits else 8) // 8

    def __str__(self) -> str:
        return f"{self.kind} {self.dtype}[{','.join(map(str, self.shape))}]"


_COLLECTIVE = re.compile(
    r"= (\([^=]*?\)|\S+) "
    r"(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute)(?:-done)?\("
    r"(?:.*?channel_id=(\d+))?")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")


def collective_tally(compiled_text: str) -> collections.Counter[Collective]:
    """How many collectives of each kind and result shape a compiled
    program (`compiled.as_text()`) holds: what the partitioner made of the
    shardings, read without a chip. One channel counts once (the TPU
    compiler writes an overlapped collective into each fusion that carries
    a stage of it, and an asynchronous pair as `-start` and `-done`); a
    collective of several arrays counts once for each."""
    tally = collections.Counter()
    seen = set()
    for result, kind, channel in _COLLECTIVE.findall(compiled_text):
        if channel in seen:
            continue
        if channel:
            seen.add(channel)
        for dtype, dims in _ARRAY.findall(result):
            tally[Collective(kind, dtype, tuple(int(d) for d in dims.split(",") if d))] += 1
    return tally


_FUNCTION = re.compile(r"^  func\.func \w+ @([\w.]+)\(", re.M)
_CALLED = re.compile(r"call @([\w.]+)\(")
_KERNEL = re.compile(r'kernel_name = "(\w+)"')


def kernel_tally(lowered_text: str) -> collections.Counter[str]:
    """How often a lowered program (`lowered.as_text()`, StableHLO) runs each
    Pallas kernel, by the kernel's name: read without a chip, as
    `collective_tally` reads a compiled one. A kernel under a jit of its own
    (ops/attention.py's calls) is lowered once, in that jit's function, and
    runs as often as the function is called."""
    starts = [(m.start(), m.group(1)) for m in _FUNCTION.finditer(lowered_text)]
    bodies = {fn: lowered_text[at:end] for (at, fn), (end, _) in
              zip(starts, starts[1:] + [(len(lowered_text), None)])}
    callers = collections.defaultdict(collections.Counter)  # callee -> {caller: calls}
    for fn, body in bodies.items():
        for callee, n in collections.Counter(_CALLED.findall(body)).items():
            callers[callee][fn] += n
    runs = {"main": 1}  # times a function runs in one run of the program

    def count(fn):
        if fn not in runs:
            runs[fn] = 0  # a function that calls itself runs no more for it
            runs[fn] = sum(n * count(caller) for caller, n in callers[fn].items())
        return runs[fn]

    tally = collections.Counter()
    for fn, body in bodies.items():
        for name in _KERNEL.findall(body):
            tally[name] += count(fn)
    return tally


def local_slice_info() -> dict:
    """Topology of the slice this process sees."""
    devs = jax.devices()
    return {
        "num_devices": len(devs),
        "num_local_devices": len(jax.local_devices()),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "platform": devs[0].platform if devs else "none",
        "device_kind": devs[0].device_kind if devs else "",
    }
