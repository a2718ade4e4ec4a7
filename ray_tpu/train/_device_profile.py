"""Device time by the program's own scopes.

A device-trace window (``_telemetry.DeviceTraceController``) ends in a raw
``.xplane.pb``: device events named by their HLO text, ``%fusion.123 = ...``,
which says what an op's results look like and nothing of where in the program
it was written. The program has said where, all along: every instruction of
the compiled step carries ``metadata={op_name="jit(train_step)/jvp(GPT2)/
h_3/mlp/c_fc/dot_general"}``, the path of flax's module names and the
``jax.named_scope``s (``loss``, ``optimizer``, ``moe.route``, ``ssm.scan``)
with jax's own wrappers saying which pass. This module joins the two and
reduces a window to a table, scope x pass -> ms a step:

  1. ``scope_table(hlo_text)``: for every instruction of the step program
     ``(scope, pass, class, group)``. The HLO is the one the profiler stored
     in the trace's ``/host:metadata`` plane under the program's own name,
     ``jit_train_step(<fingerprint>)``: the program that ran, by
     construction, and nothing the process has to keep or lower again.
  2. ``reduce(trace, tables)``: over the whole step programs of the window,
     self time in ms a step per ``(group, pass)``, per ``(scope, pass,
     class)``, per Pallas kernel and for the twenty largest kinds of
     instruction, busy and idle, and for each step how long after the
     program's end on the device the program's own watcher saw it complete.
     Pure functions on plain data: tested without a chip.
  3. ``profile_window(trace_dir)`` writes ``device_profile.json`` beside the
     trace; ``python -m ray_tpu.train._device_profile <xplane.pb | dir>``
     reduces a trace taken by anyone (the benchmark's ``--trace 1`` run).

Nothing here runs while no window is armed: the controller imports this
module when a window closes, and has the reduction done by a child process
(``start_child``), so the loop's interpreter is not held.
"""

from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re
import statistics
import subprocess
import sys
import time
from typing import Dict, Iterable, List, Optional, Tuple

PASSES = ("fwd", "bwd", "remat", "update")
CLASSES = ("matmul", "kernel", "copy", "collective", "elementwise")
# One vocabulary for every family. `attn.core` is the kernels and what feeds
# them (the q/k/v split, the (B,T,H,D)<->(B,H,T,D) copies, rotary, q/k norm,
# the indexer); `mla` what stands round a latent attention's kernels (its
# four projections, the latent's norm, the rotary on the 64: the kernels
# themselves are `attn.core`); `moe.shared` the expert every token passes
# through beside the routed ones; `kda` a delta-rule mixer whole
# (models/kimi_linear.py: `kda.in_proj`, `.conv`, `.gate`, `.scan`, `.norm`,
# `.out_proj`), and `gdn` the same of one whose decay is one number a head
# and step (models/qwen3_next.py: `gdn.in_proj`, `.conv`, `.rule`, `.norm`,
# `.out_proj`); `gmu` a gated memory unit, which gates another layer's scan
# output by this layer's stream, and `attn.cross` a layer that attends over
# another layer's keys and values, its projections and its kernels
# (models/phi4_flash.py); `conv` a gated short convolution's mixing
# and its two projections; `norm` holds the norms and the residual stream's own ops
# beside them (a block's adds and pins, which XLA fuses with the norms; `norm.post`,
# a sublayer's output normed before its add: models/afmoe.py);
# `optimizer` the clip and the global norm with AdamW.
GROUPS = ("embed", "attn.proj", "attn.core", "attn.cross", "mla", "mlp", "moe", "moe.shared",
          "ssm", "gmu", "kda", "gdn", "conv", "norm", "head", "loss", "optimizer", "collective", "unscoped")
TOP_ROWS = 5  # (group, pass) rows in what rides a report and the GCS record
SCOPE_ROWS = 40  # scope rows printed for a terminal (--json holds them all)
KIND_ROWS = 20  # kinds of instruction kept, largest first
HOST_THUNKS = " (XLA's host thunks: no device's time)"  # a trace with no TPU plane
CHILD_WAIT_S = 60.0  # a window's reduction takes 3.5-4.6 s on the cells' traces

# ------------------------------------------------------------ op_name -> scope

_WRAPPED = re.compile(r"^([A-Za-z_]\w*)\((.*)\)$")
# the families' repeated layers: h_3, p_0/h_3; and a layer's half under a remat
# of its own, which flax names after the function: h_3._mixer_half (models/kimi_linear.py)
_LAYER = re.compile(r"^([hp])_\d+(?:\._\w+)?$")
# what jax itself puts on the name stack beside its transform(...) wrappers
_JAX_OWN = frozenset((
    "checkpoint", "rematted_computation", "remat", "while", "body", "cond",
    "branch", "closed_call", "core_call", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "custom_lin", "shard_map", "pjit"))
_BRANCH = re.compile(r"^branch_\d+_fun$")  # lax.cond's name for a branch
_FUNCTIONS = frozenset(("jit", "pjit"))  # jit(f): f is a function's name, no scope


def scope_of(op_name: str) -> Tuple[str, str]:
    """``(scope, pass)`` of an instruction's ``op_name``. The scope is the
    module path with layer numbers folded and jax's wrappers taken out; the
    pass is `remat` for the forward run again inside the backward pass,
    `bwd` under ``transpose(...)``, `update` in the optimizer, else `fwd`.

    The forward run again: under ``rematted_computation``, or, where the
    block holds a ``lax.cond``, a scope that is ``jvp(...)`` and not
    ``transpose(jvp(...))`` inside the transposed block (jax re-traces a
    cond's branches and says it this way: the routed cells' expert layer)."""
    scope: List[str] = []
    transposed = rematted = False
    for part in op_name.split("/")[:-1]:  # the last is the primitive
        heads = []
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            heads.append(m.group(1))
            part = m.group(2)
        if heads and heads[-1] in _FUNCTIONS:
            continue
        if "transpose" in heads:
            transposed = True
        elif transposed and "jvp" in heads and not part[:1].isupper():
            rematted = True
        if part == "rematted_computation":
            rematted = True
        if part in _JAX_OWN or not part or _BRANCH.match(part):
            continue
        if part[0].isupper():
            # flax names the top module by its class: it says nothing of
            # where, and the path starts again after it (a module that holds
            # checkpointed blocks comes before and after: p_0/jvp(Granite)/p_0)
            scope.clear()
            continue
        scope.append(_LAYER.sub(r"\1", part))
    if rematted:
        which = "remat"
    elif transposed:
        which = "bwd"
    else:
        which = "update" if scope[:1] == ["optimizer"] else "fwd"
    return "/".join(scope), which


# and what makes the embedding's input: a diffusion step's noise (models/sdar.py)
_EMBED = frozenset(("wte", "wpe", "tok_emb", "sdar.noise"))
_HEAD = frozenset(("lm_head", "wte.attend", "tok_emb.attend"))
_ATTN_PROJ = frozenset(("c_attn", "c_proj", "wq", "wk", "wv", "wo", "qkv"))
_BLOCK = frozenset(("h", "p"))


def _is_norm(part: str) -> bool:
    return part == "norm" or part.startswith("norm.") or part.endswith("_norm") \
        or part.startswith("ln_")


def group_of(scope: str, cls: str = "elementwise") -> str:
    """The coarse group of a scope: one of GROUPS."""
    if cls == "collective":
        return "collective"
    if not scope:
        return "unscoped"
    parts = scope.split("/")
    if parts[0] in ("optimizer", "loss"):
        return parts[0]
    if parts[0].startswith("loss."):
        return "loss"  # a family's own objective names its loss (models/sdar.py: `loss.diffusion`)
    if any(p in _HEAD for p in parts):
        return "head"
    if any(p in _EMBED for p in parts):
        return "embed"
    if "moe.shared" in parts:
        return "moe.shared"
    if any(p == "moe" or p.startswith("moe.") for p in parts):
        return "moe"
    if any(p == "mamba" or p.startswith("ssm.") for p in parts):
        return "ssm"
    if any(p == "gmu" or p.startswith("gmu.") for p in parts):
        return "gmu"  # a gated memory unit: another layer's scan output gated by this stream
    if "cross" in parts:
        return "attn.cross"  # a layer that reads another layer's K and V: its projections and core
    if any(p == "kda" or p.startswith("kda.") for p in parts):
        return "kda"  # a delta-rule mixer whole: its projections, convolution, gates, scan, norm
    if any(p == "gdn" or p.startswith("gdn.") for p in parts):
        return "gdn"  # the same of a scalar-decay delta rule
    if any(p == "conv" or p.startswith("conv.") for p in parts):
        return "conv"
    if any(p.startswith("mla.") for p in parts):
        return "mla"
    if "attn" in parts or any(p.startswith("attn.") for p in parts):
        own = parts[parts.index("attn") + 1:] if "attn" in parts else parts
        return "attn.proj" if own and own[0] in _ATTN_PROJ else "attn.core"
    if "mlp" in parts:
        return "mlp"
    if any(_is_norm(p) for p in parts) or all(p in _BLOCK for p in parts):
        return "norm"
    return "unscoped"


# --------------------------------------------------------- HLO text -> table

_INSTR = re.compile(r"^\s*(ROOT )?%?([\w\-.]+) = (.*?) ([a-z][\w\-]*)\(")
_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w\-.]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_ARRAY = re.compile(r"\b(pred|[a-z]+\d+\w*)\[([\d,]*)\]")
_NUMBERED = re.compile(r"\.\d+$")
_CALLED = re.compile(r"\b(calls|body|condition|to_apply|true_computation|false_computation)=%?([\w\-.]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")

_COLLECTIVES = frozenset(("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
                          "all-to-all", "collective-broadcast", "ragged-all-to-all"))
# opcodes that move data and compute nothing
_MOVES = frozenset((
    "copy", "transpose", "reshape", "bitcast", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "broadcast", "reverse"))
# and what a fusion's body holds beside them without computing either
_INERT = frozenset(("parameter", "constant", "tuple", "get-tuple-element", "iota"))
# what runs the computations it names as part of the schedule (a fusion's or a
# reduce's computation is the op itself)
_CONTROL = frozenset(("while", "conditional", "call", "async-start"))
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


class _Instr:
    __slots__ = ("name", "opcode", "shapes", "operands", "op_name", "called", "root", "text")

    def __init__(self, name, opcode, shapes, operands, op_name, called, root, text):
        self.name, self.opcode, self.shapes = name, opcode, shapes
        self.operands, self.op_name, self.called = operands, op_name, called
        self.root, self.text = root, text


def _sync(opcode: str) -> str:
    """An asynchronous op's opcode as the op it runs: `slice-done` -> `slice`
    (the printer's short form of an async-start/-done pair)."""
    for tail in ("-start", "-done", "-update"):
        if opcode.endswith(tail):
            return opcode[:-len(tail)]
    return opcode


def _operands(line: str, start: int) -> Tuple[str, int]:
    """The text between the opcode's parentheses, and where it ends."""
    depth = 1
    for i in range(start, len(line)):
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if not depth:
                return line[start:i], i
    return line[start:], len(line)


def result_bytes(shapes: str) -> int:
    """Bytes of an instruction's results (a tuple's elements summed)."""
    total = 0
    for dtype, dims in _ARRAY.findall(shapes):
        bits = 8 if dtype == "pred" else int(re.search(r"\d+", dtype).group())
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * bits // 8
    return total


def parse_hlo(text: str) -> Tuple[str, Optional[str], Dict[str, List[_Instr]]]:
    """``(module name, entry computation's name, computation -> instructions)``
    of an HLO module printed as text."""
    first = text[:text.find("\n")] if "\n" in text else text
    m = re.match(r"HloModule ([\w\-.]+)", first)
    module = m.group(1) if m else ""
    computations: Dict[str, List[_Instr]] = {}
    entry = current = None
    for line in text.splitlines():
        if current is None:
            c = _COMPUTATION.match(line)
            if c:
                current = computations.setdefault(c.group(2), [])
                if c.group(1):
                    entry = c.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        i = _INSTR.match(line)
        if not i:
            continue
        root, name, shapes, opcode = i.groups()
        inside, end = _operands(line, i.end())
        rest = line[end:]
        named = _OP_NAME.search(rest)
        called = [c for _, c in _CALLED.findall(rest)]
        b = _BRANCHES.search(rest)
        if b:
            called += [c.strip().lstrip("%") for c in b.group(1).split(",")]
        # a name stack has a "/": a parameter's op_name is its argument's name
        op_name = named.group(1) if named and "/" in named.group(1) else None
        current.append(_Instr(
            name, opcode, shapes, re.findall(r"%([\w\-.]+)", inside),
            op_name, called, bool(root), rest))
    return module, entry, computations


def kind_of(name: str, opcode: str, shapes: str) -> str:
    """An instruction as the benchmark's breakdown names it: its name without
    its number, the opcode, the result shapes without layouts."""
    return f"{_NUMBERED.sub('', name)} {opcode} -> {re.sub(r'{[^}]*}', '', shapes)}"[:300]


def _fusion_class_and_source(body: List[_Instr]) -> Tuple[str, Optional[_Instr]]:
    """What a fusion is, from its body: its class, and the instruction whose
    op_name says where it was written. A matmul where it has one (the name XLA
    gives such a fusion may be its epilogue's); else whatever produces the
    largest result."""
    matmuls = [i for i in body if i.opcode in ("dot", "convolution")]
    if matmuls:
        return "matmul", max(matmuls, key=lambda i: result_bytes(i.shapes))
    if any(_sync(i.opcode) in _COLLECTIVES for i in body):
        cls = "collective"
    elif all(i.opcode in _MOVES or i.opcode in _INERT for i in body):
        cls = "copy"
    else:
        cls = "elementwise"
    by_name = {i.name: i for i in body}
    root = next((i for i in body if i.root), body[-1] if body else None)
    if root is not None and root.opcode == "tuple":
        outs = [by_name[o] for o in root.operands if o in by_name]
        root = max(outs, key=lambda i: result_bytes(i.shapes)) if outs else root
    # through what only moves the result, to what computed it
    while (root is not None and root.op_name is None and root.operands
           and root.operands[0] in by_name):
        root = by_name[root.operands[0]]
    return cls, root


def scope_table(hlo_text: str) -> Dict:
    """``{"module": name, "rows": {instruction: [scope, pass, class, group,
    kind]}}`` for every instruction the step program schedules: its entry
    computation and the bodies of its loops, branches and calls."""
    module, entry, computations = parse_hlo(hlo_text)
    scheduled, todo = [], [entry] if entry else []
    while todo:
        comp = todo.pop()
        if comp in scheduled or comp not in computations:
            continue
        scheduled.append(comp)
        for ins in computations[comp]:
            if ins.opcode in _CONTROL:
                todo.extend(ins.called)
    rows: Dict[str, List[str]] = {}
    for comp in scheduled:
        instrs = computations[comp]
        found: Dict[str, Tuple[Optional[str], str]] = {}
        for ins in instrs:
            source, cls = ins, "elementwise"
            if ins.opcode == "fusion":
                body = [i for c in ins.called for i in computations.get(c, [])]
                cls, inner = _fusion_class_and_source(body)
                if inner is not None and inner.op_name is not None:
                    source = inner
            elif ins.opcode in ("dot", "convolution"):
                cls = "matmul"
            elif ins.opcode == "custom-call" and KERNEL_TARGET in ins.text:
                cls = "kernel"
            elif _sync(ins.opcode) in _COLLECTIVES:
                cls = "collective"
            elif _sync(ins.opcode) in _MOVES:
                cls = "copy"
            elif ins.opcode == "async-start":
                # the op it starts is the one instruction of its computation
                inner = [i for c in ins.called for i in computations.get(c, [])
                         if i.opcode not in _INERT]
                if inner and all(_sync(i.opcode) in _COLLECTIVES for i in inner):
                    cls = "collective"
                elif inner and all(i.opcode in _MOVES for i in inner):
                    cls = "copy"
            found[ins.name] = (source.op_name if source.op_name is not None else ins.op_name, cls)
        # XLA's own instructions carry no op_name: each inherits from what it
        # feeds (users come later in a scheduled computation)
        users: Dict[str, List[str]] = collections.defaultdict(list)
        for ins in instrs:
            for o in ins.operands:
                users[o].append(ins.name)
        for ins in reversed(instrs):
            op_name, cls = found[ins.name]
            if op_name is None:
                for u in users.get(ins.name, ()):
                    if found[u][0] is not None:
                        found[ins.name] = (found[u][0], cls)
                        break
        # and what feeds nothing that is named (a result copied into place
        # for the program's output) from what it reads
        for ins in instrs:
            if found[ins.name][0] is None:
                for o in ins.operands:
                    if found.get(o, (None,))[0] is not None:
                        found[ins.name] = (found[o][0], found[ins.name][1])
                        break
        for ins in instrs:
            op_name, cls = found[ins.name]
            scope, which = scope_of(op_name) if op_name else ("", "fwd")
            rows[ins.name] = [scope, which, cls, group_of(scope, cls),
                              kind_of(ins.name, ins.opcode, ins.shapes)]
    return {"module": module, "rows": rows}


# ------------------------------------------------------- the trace, as data

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
METADATA_PLANE = "/host:metadata"
PROGRAM_PREFIX = "ray_tpu."
WAIT_SPAN = "ray_tpu.train_step.wait"


def _fields(buf) -> Iterable[Tuple[int, object]]:
    """(field number, value) over the top level of one protobuf message: an
    int for a varint, a view of the bytes for anything with a length."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        value = shift = 0
        while True:
            b = buf[i]
            i += 1
            value |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return value

    while i < n:
        key = varint()
        wire = key & 7
        if wire == 0:
            yield key >> 3, varint()
        elif wire == 2:
            size = varint()
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            yield key >> 3, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def stored_hlo(xplane_path: str, names: Optional[Iterable[str]] = None) -> Dict[str, str]:
    """The HLO modules the profiler stored with a trace (those of `names`,
    or all), as text, by the name the trace's step programs carry
    (``jit_train_step(<fingerprint>)``).
    ``jax.profiler.ProfileData`` shows a plane's events and the metadata plane
    has none, so the file is walked by field number: XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4 (a map: value = 2);
    XEventMetadata.name = 2, .stats = 5; XStat.bytes_value = 6, which holds an
    HloProto whose field 1 is the module."""
    from jax._src.lib import xla_client

    with open(xplane_path, "rb") as f:
        data = memoryview(f.read())
    names = None if names is None else set(names)
    out = {}
    for number, plane in _fields(data):
        if number != 1:
            continue
        top = list(_fields(plane))
        if next((bytes(v).decode() for n, v in top if n == 2), "") != METADATA_PLANE:
            continue
        for n, entry in top:
            if n != 4:
                continue
            meta = dict(_fields(entry)).get(2)
            if meta is None:
                continue
            name, module = "", None
            for m, v in _fields(meta):
                if m == 2:
                    name = bytes(v).decode()
                elif m == 5:
                    stored = dict(_fields(v)).get(6)
                    if stored is not None:
                        module = dict(_fields(stored)).get(1)
            if module is not None and (names is None or name in names):
                out[name] = xla_client._xla.HloModule.from_serialized_hlo_module_proto(
                    bytes(module)).to_string()
    return out


def read_trace(xplane_path: str) -> Dict:
    """A trace as plain data, nanoseconds on its one clock: per device its
    step programs ``[name, start, duration]`` and its op events ``[text,
    start, duration]`` in lines (events of one line nest, lines run side by
    side), and the program's own spans ``[name, start, duration, step]``.

    On a TPU a device is a ``/device:TPU:n`` plane, its ops the ``XLA Ops``
    line (each named by its HLO text) and its programs the ``XLA Modules``
    line. A CPU has no such plane: XLA's thunks are events of the host's
    threads with ``hlo_op``, ``hlo_module``, ``program_id`` and ``run_id`` as
    stats, and a program is the span of one ``run_id``. That reading proves
    the path on a box without a chip; its times are no device's."""
    from jax.profiler import ProfileData

    devices, spans, platform = [], [], "tpu"
    host = {"name": "/host:CPU", "lines": [], "runs": {}}
    for plane in ProfileData.from_file(xplane_path).planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "modules": [], "lines": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["lines"].append([[e.name, int(e.start_ns), int(e.duration_ns)]
                                         for e in line.events])
                elif line.name == "XLA Modules":
                    dev["modules"] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                                      for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ops = []
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIX):
                        step = dict(e.stats).get("step")
                        spans.append([e.name, int(e.start_ns), int(e.duration_ns),
                                      None if step is None else int(step)])
                    elif not e.name.startswith("$"):  # the python tracer's frames
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            s, d = int(e.start_ns), int(e.duration_ns)
                            ops.append([str(stats["hlo_op"]), s, d])
                            run = host["runs"].setdefault(
                                stats.get("run_id"),
                                [f"{stats.get('hlo_module')}({stats.get('program_id')})", s, s + d])
                            run[1], run[2] = min(run[1], s), max(run[2], s + d)
                if ops:
                    host["lines"].append(ops)
    if not devices and host["lines"]:
        platform = "cpu"
        host["modules"] = sorted(([n, s, e - s] for n, s, e in host.pop("runs").values()),
                                 key=lambda m: m[1])
        devices = [host]
    devices.sort(key=lambda d: d["name"])
    return {"platform": platform, "devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


# ------------------------------------------------------------ the reduction

def _self_times(events):
    """``(text, start, end, self_ns)`` per event of one line: its duration
    less that of the events nested directly inside it (a while loop holds
    its body's ops)."""
    out, stack = [], []  # [text, start, end, child_ns]

    def close(until):
        while stack and stack[-1][2] <= until:
            text, s, e, child = stack.pop()
            out.append((text, s, e, max(0, e - s - child)))
            if stack:
                stack[-1][3] += e - s

    for text, s, d in sorted(events, key=lambda o: (o[1], -o[2])):
        close(s)
        stack.append([text, s, s + d, 0])
    close(float("inf"))
    return out


def _whole_steps(dev) -> Tuple[Optional[str], List[List]]:
    """The step program of a device (the one that takes most of its time) and
    its whole runs in the window. A trace that began while a step program ran
    holds the rest of that one first: it has fewer ops than the others, and
    is left out."""
    by_name = collections.Counter()
    for name, _, d in dev["modules"]:
        by_name[name] += d
    if not by_name:
        return None, []
    step_name = by_name.most_common(1)[0][0]
    steps = sorted((m for m in dev["modules"] if m[0] == step_name), key=lambda m: m[1])
    if len(steps) > 1:
        starts = sorted(s for line in dev["lines"] for _, s, _ in line)
        ops = [bisect.bisect_left(starts, m[1] + m[2]) - bisect.bisect_left(starts, m[1])
               for m in steps]
        if ops[0] < statistics.median(ops[1:]):
            steps = steps[1:]
    return step_name, steps


class _Tally(dict):
    """key (a tuple of fields) -> [self time in ns, calls]."""

    def add(self, key: tuple, ns: int) -> None:
        got = self.setdefault(key, [0, 0])
        got[0] += ns
        got[1] += 1

    def rows(self, fields: Tuple[str, ...], scale: float, busy_ns: float,
             limit: Optional[int] = None) -> List[Dict]:
        """Largest first: the key's fields, ms and calls a step, share of busy."""
        return [dict(zip(fields, key), ms=ns * scale / 1e6,
                     share=ns / busy_ns if busy_ns else 0.0, calls=n * scale)
                for key, (ns, n) in sorted(self.items(), key=lambda kv: -kv[1][0])[:limit]]


def reduce(trace: Dict, tables: Dict[str, Dict]) -> Dict:
    """The device profile of a window. `trace` is ``read_trace``'s plain data,
    `tables` the scope table of each program the trace stored HLO for, by the
    name its runs carry in the trace. Rows are self time in ms a step, mean
    over devices, and sum to `busy_ms`."""
    devices = trace["devices"]
    out: Dict = {"version": 1, "platform": trace["platform"], "devices": len(devices)}
    by_group, by_scope, by_kind, by_kernel, by_class = (_Tally() for _ in range(5))
    where = collections.defaultdict(collections.Counter)  # kind -> (scope, pass, class) -> ns
    busy_ns = window_ns = joined_ns = 0
    n_steps, step_name, ends = 0, None, []
    for dev in devices:
        name, steps = _whole_steps(dev)
        if not steps:
            continue
        step_name = step_name or name
        n_steps = max(n_steps, len(steps))
        window_ns += steps[-1][1] + steps[-1][2] - steps[0][1]
        ends.append([m[1] + m[2] for m in steps])
        rows = (tables.get(name) or {}).get("rows", {})
        bounds = [(m[1], m[1] + m[2]) for m in steps]
        for line in dev["lines"]:
            for text, s, e, self_ns in _self_times(line):
                if not any(a <= s and e <= b for a, b in bounds):
                    continue  # a cut step's, or another program's between steps
                busy_ns += self_ns
                m = _INSTR.match(text)  # on a TPU an event is named by its HLO text
                instr = m.group(2) if m else text
                row = rows.get(instr)
                kind = kind_of(instr, m.group(4), m.group(3)) if m else (row[4] if row else text[:120])
                by_kind.add((kind,), self_ns)
                if row is None or (m and kind.split(" ", 2)[2] != row[4].split(" ", 2)[2]):
                    continue  # not this program's instruction of that name: other results
                joined_ns += self_ns
                scope, which, cls, group = row[:4]
                by_group.add((group, which), self_ns)
                by_scope.add((scope, which, cls, group), self_ns)
                by_class.add((cls,), self_ns)
                where[kind][(scope, which, cls)] += self_ns
                if cls == "kernel":
                    by_kernel.add((_NUMBERED.sub("", instr),), self_ns)
    m = re.match(r"^(.*)\((\d+)\)$", step_name or "")
    joined = joined_ns / busy_ns if busy_ns else 0.0
    if step_name is None:
        state = "no step program in the trace"
    elif step_name not in tables:
        state = "the trace stored no HLO under this program's name"
    elif joined < 0.999:
        state = "the stored HLO's instructions are not the trace's"
    else:
        state = "matched"
    out["program"] = {
        "module": m.group(1) if m else step_name, "fingerprint": m.group(2) if m else None,
        "table": state, "joined_share": joined,
        "instructions": len((tables.get(step_name) or {}).get("rows", ()))}
    per = max(n_steps, 1) * max(len(ends), 1)  # one step of one device
    scale = 1.0 / per
    out.update(steps=n_steps, busy_ms=busy_ns * scale / 1e6, window_ms=window_ns * scale / 1e6,
               idle_share=1 - busy_ns / window_ns if window_ns else 0.0)
    matched = state == "matched"
    for key, tally, fields in (("groups", by_group, ("group", "pass")),
                               ("scopes", by_scope, ("scope", "pass", "class", "group")),
                               ("classes", by_class, ("class",)), ("kernels", by_kernel, ("name",))):
        out[key] = tally.rows(fields, scale, busy_ns) if matched else []
    out["kinds"] = by_kind.rows(("kind",), scale, busy_ns, limit=KIND_ROWS)
    for row in out["kinds"]:
        row["where"] = [dict(scope=s, **{"pass": p, "class": c}, ms=ns * scale / 1e6)
                        for (s, p, c), ns in where[row["kind"]].most_common(4)] if matched else []

    def share(rows, **match):
        return sum(r["share"] for r in rows if all(r[k] in v for k, v in match.items()))

    if matched:
        out["shares"] = {
            "remat_share": share(out["groups"], **{"pass": ("remat",)}),
            "optimizer_share": share(out["groups"], group=("optimizer",)),
            "head_loss_share": share(out["groups"], group=("head", "loss")),
            "copy_share": share(out["classes"], **{"class": ("copy",)}),
            "unscoped_share": share(out["groups"], group=("unscoped",)),
        }
    else:
        out["shares"] = {}
    out["completion_lag_ms"] = _completion_lag(ends, trace["spans"])
    return out


def _completion_lag(ends: List[List[int]], spans: List[List]) -> Dict:
    """For each whole step program, from its end on the device (the last
    device's) to the end of the watcher's wait on that step: how late the
    program sees a completion. Step programs and waits are matched in order,
    each program to the first wait that ends after it."""
    if not ends:
        return {"per_step": []}
    done = [max(col) for col in zip(*(e for e in ends if len(e) == len(ends[0])))]
    waits = sorted((s + d, step) for name, s, d, step in spans if name == WAIT_SPAN)
    lags, steps, j = [], [], 0
    for end in done:
        # the two clocks are one trace's, but a microsecond apart
        while j < len(waits) and waits[j][0] < end - 1000:
            j += 1
        if j == len(waits):
            break
        lags.append((waits[j][0] - end) / 1e6)
        steps.append(waits[j][1])
        j += 1
    out = {"per_step": lags, "steps": steps}
    if lags:
        out.update(min=min(lags), median=statistics.median(lags), max=max(lags))
    return out


# ------------------------------------------------------------- entry points

PROFILE_FILE = "device_profile.json"


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def profile_xplane(xplane_path: str) -> Dict:
    """Read, join and reduce one trace file."""
    started = time.monotonic()
    trace = read_trace(xplane_path)
    programs = {_whole_steps(dev)[0] for dev in trace["devices"]}
    tables = {name: scope_table(text)
              for name, text in stored_hlo(xplane_path, programs - {None}).items()}
    profile = reduce(trace, tables)
    profile["trace"] = xplane_path
    profile["reduce_s"] = time.monotonic() - started
    return profile


def profile_window(trace_dir: str) -> Optional[Dict]:
    """Reduce the newest trace under `trace_dir` and write the profile beside
    it; the profile with its `path`, or None where there is no trace."""
    xplane = newest_xplane(trace_dir)
    if xplane is None:
        return None
    profile = profile_xplane(xplane)
    profile["path"] = os.path.join(trace_dir, PROFILE_FILE)
    with open(profile["path"], "w") as f:
        json.dump(profile, f, indent=1)
    return profile


def start_child(trace_dir: str) -> subprocess.Popen:
    """`profile_window` in a process of its own, for a caller whose
    interpreter has a training loop to run: reading a trace holds the
    interpreter lock for seconds at a time (on the chip a 3-step window of
    `gpt2_small.t256` read on a thread beside the loop held one step back for
    2.56 s). The child never asks for a device."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(filter(None, [root, env.get("PYTHONPATH")]))
    return subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.train._device_profile", trace_dir, "--beside"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True)


def profile_from_child(child: subprocess.Popen, trace_dir: str) -> Dict:
    """The profile `start_child`'s process wrote; a child that takes longer
    than CHILD_WAIT_S is ended, and that, like one that failed, raises."""
    try:
        _, err = child.communicate(timeout=CHILD_WAIT_S)
    except subprocess.TimeoutExpired:
        child.kill()
        _, err = child.communicate()
        err = f"ended after {CHILD_WAIT_S:.0f} s\n{err}"
    path = os.path.join(trace_dir, PROFILE_FILE)
    if child.returncode or not os.path.isfile(path):
        raise RuntimeError(f"no profile of {trace_dir}: exit {child.returncode}: {err[-2000:]}")
    with open(path) as f:
        return json.load(f)


def brief(profile: Dict) -> Dict:
    """What rides a report and the GCS record: the numbers, not the tables.
    `platform` says whose time they are: "tpu" for a device's; "cpu" for
    XLA's host thunks where the trace held no TPU plane (`read_trace`)."""
    return {
        "path": profile.get("path"), "steps": profile["steps"],
        "platform": profile["platform"], "devices": profile["devices"],
        "busy_ms": round(profile["busy_ms"], 3), "table": profile["program"]["table"],
        "top": [[r["group"], r["pass"], round(r["ms"], 3), round(r["share"], 4)]
                for r in profile["groups"][:TOP_ROWS]],
        **{k: round(v, 4) for k, v in profile["shares"].items()},
    }


def render(profile: Dict) -> str:
    """The profile as text, for a terminal."""
    p = profile["program"]
    lag = profile["completion_lag_ms"]
    lines = [
        f"{p['module']}({p['fingerprint']}) on {profile['devices']} x {profile['platform']}"
        f"{'' if profile['platform'] == 'tpu' else HOST_THUNKS}: "
        f"{profile['steps']} steps, busy {profile['busy_ms']:.3f} ms a step of "
        f"{profile['window_ms']:.3f} (idle {100 * profile['idle_share']:.3f}%), "
        f"table {p['table']} ({100 * p['joined_share']:.2f}% of busy joined, "
        f"{p['instructions']} instructions)",
        "  ".join(f"{k} {100 * v:.2f}%" for k, v in profile["shares"].items()),
    ]
    if lag.get("per_step"):
        lines.append(f"completion seen {lag['min']:.3f} / {lag['median']:.3f} / {lag['max']:.3f} ms "
                     f"after the program's end (min / median / max of {len(lag['per_step'])})")
    lines.append(f"{'group':<12}{'pass':<8}{'ms a step':>10}{'share':>9}{'calls':>8}")
    for r in profile["groups"]:
        lines.append(f"{r['group']:<12}{r['pass']:<8}{r['ms']:>10.3f}{100 * r['share']:>8.2f}%"
                     f"{r['calls']:>8.1f}")
    lines.append(f"{'scope':<44}{'pass':<7}{'class':<12}{'ms a step':>10}{'share':>9}{'calls':>7}")
    for r in profile["scopes"][:SCOPE_ROWS]:
        lines.append(f"{r['scope'][:43]:<44}{r['pass']:<7}{r['class']:<12}{r['ms']:>10.3f}"
                     f"{100 * r['share']:>8.2f}%{r['calls']:>7.1f}")
    for r in profile["kernels"]:
        lines.append(f"kernel {r['name']:<48}{r['ms']:>10.3f}{100 * r['share']:>8.3f}%{r['calls']:>7.1f}")
    for r in profile["kinds"]:
        at = "; ".join(f"{w['scope'] or '-'} {w['pass']} {w['class']} {w['ms']:.2f}"
                       for w in r["where"])
        lines.append(f"{r['ms']:>9.3f} {100 * r['share']:>6.2f}% x{r['calls']:<5.1f} {r['kind'][:110]}"
                     f"\n{'':>18}{at}")
    return "\n".join(lines)


def main(argv=None) -> int:
    """``python -m ray_tpu.train._device_profile <xplane.pb | trace dir> [--json out]``"""
    import argparse

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("trace", help="an .xplane.pb, or the directory a trace was written to")
    ap.add_argument("--json", help="write the whole profile here")
    ap.add_argument("--beside", action="store_true",
                    help=f"write {PROFILE_FILE} into the trace's directory and print nothing")
    args = ap.parse_args(argv)
    if args.beside:
        return 0 if profile_window(args.trace) is not None else 1
    path = args.trace if os.path.isfile(args.trace) else newest_xplane(args.trace)
    if path is None:
        print(f"no .xplane.pb under {args.trace}", file=sys.stderr)
        return 1
    profile = profile_xplane(path)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(profile, f, indent=1)
    print(render(profile))
    return 0


if __name__ == "__main__":
    sys.exit(main())
