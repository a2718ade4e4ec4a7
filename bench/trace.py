"""From the profiler's trace to numbers, in two steps that are kept apart so
the second can be checked on a small recorded trace (bench/tests/):

    extract(xplane_path)  -> a plain dict (JSON): per device its operations
                             and step programs, and the loop's host spans,
                             all in nanoseconds on the trace's one clock.
                             Needs jax.profiler.ProfileData, so it runs in
                             the worker, which has JAX anyway.
    Reduced(trace)        -> busy time, idle gaps and per-operation self time
                             inside the window of whole step programs. Pure
                             Python; runs in the benchmark's own process.
"""

import bisect
import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."
# A device event is named by its HLO text: "%attn.80 = (bf16[1536,256,64]{...},
# f32[1536,1,256]{...}) custom-call(bf16[...] %bitcast.1826, ...". Its kind is
# what stays the same from step to step and layer to layer: the op's name
# without its number, the opcode, and the result shapes without layouts.
_HLO = re.compile(r"^%?([\w\-]+?)(?:\.\d+)? = (.*?) ([\w\-]+)\(")


def kind(hlo_text):
    m = _HLO.match(hlo_text)
    if not m:
        return hlo_text[:120]
    name, shapes, opcode = m.groups()
    return f"{name} {opcode} -> {re.sub(r'{[^}]*}', '', shapes)}"[:300]


def newest_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def extract(xplane_path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    texts, index = [], {}

    def intern(text):
        if text not in index:
            index[text] = len(texts)
            texts.append(text)
        return index[text]

    devices, host = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"name": plane.name, "ops": [], "async": [], "modules": []}
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE):
                    into = dev["ops" if line.name == OPS_LINE else "async"]
                    for e in line.events:
                        into.append([intern(kind(e.name)), int(e.start_ns),
                                     int(e.duration_ns)])
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        dev["modules"].append(
                            [intern(e.name[:120]), int(e.start_ns), int(e.duration_ns)])
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([e.name[len(SPAN_PREFIX):], int(e.start_ns),
                                     int(e.duration_ns)])
    devices.sort(key=lambda d: d["name"])
    return {"texts": texts, "devices": devices, "host": sorted(host, key=lambda s: s[1])}


def describe(xplane_path, per_line=4):
    """What is in a trace, for a first look by hand: planes, lines, a few
    events of each with their stats."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(events)}")
            for e in events[:per_line]:
                stats = {k: str(v)[:160] for k, v in e.stats}
                out.append(f"    {e.name[:100]!r} start={e.start_ns} dur={e.duration_ns} {stats}")
    return "\n".join(out)


def _union(intervals):
    """Sorted, merged copy of (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _length(merged):
    return sum(e - s for s, e in merged)


def _self_times(ops):
    """(text, start, end, self_ns) per op: its duration less that of the ops
    nested directly inside it (a while loop holds its body's ops)."""
    out, stack = [], []  # stack of [text, start, end, child_ns]

    def close(until):
        while stack and stack[-1][2] <= until:
            text, s, e, child = stack.pop()
            out.append((text, s, e, max(0, e - s - child)))
            if stack:
                stack[-1][3] += e - s

    for text, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        close(s)
        stack.append([text, s, s + d, 0])
    close(float("inf"))
    return out


class DeviceWindow:
    """One device's operations inside the window of whole step programs."""

    def __init__(self, dev, texts):
        modules = sorted(dev["modules"], key=lambda m: m[1])
        ops = dev["ops"]
        if modules:
            # the step program is the one that takes most of the time; other
            # programs (a transfer, a small jit) stay in the window as work
            by_name = collections.Counter()
            for t, _, d in modules:
                by_name[t] += d
            step_text = by_name.most_common(1)[0][0]
            steps = [m for m in modules if m[0] == step_text]
            if len(steps) > 2:
                # the trace began while a step program ran: its first
                # event is the rest of that one, not a whole step
                steps = steps[1:]
            self.start, self.end = steps[0][1], steps[-1][1] + steps[-1][2]
            self.steps = len(steps)
            self.step_program = texts[step_text]
        elif ops:
            self.start = min(o[1] for o in ops)
            self.end = max(o[1] + o[2] for o in ops)
            self.steps, self.step_program = 0, None
        else:
            self.start = self.end = self.steps = 0
            self.step_program = None
        inside = [(texts[t], max(s, self.start), min(s + d, self.end) - max(s, self.start))
                  for t, s, d in ops if s + d > self.start and s < self.end]
        self.ops = _self_times(inside)
        # start-to-done intervals of asynchronous ops (collectives, copies)
        self.in_flight = [
            (texts[t], max(s, self.start), min(s + d, self.end))
            for t, s, d in dev.get("async", []) if s + d > self.start and s < self.end]
        self.busy = _union((s, e) for _, s, e, _ in self.ops)
        self.busy_ns = _length(self.busy)
        self.window_ns = self.end - self.start

    def gaps(self):
        edges = [self.start] + [x for s, e in self.busy for x in (s, e)] + [self.end]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def matching(self, pattern):
        rx = re.compile(pattern)
        return [op for op in self.ops if rx.search(op[0])]

    def covered_ns(self, pattern, exposed=False):
        """Time in which an op matching the pattern runs or is in flight;
        with exposed, only the part of it in which no other op runs."""
        rx = re.compile(pattern)
        mine = _union([(s, e) for t, s, e, _ in self.ops if rx.search(t)]
                      + [(s, e) for t, s, e in self.in_flight if rx.search(t)])
        if not exposed:
            return _length(mine)
        others = _union((s, e) for t, s, e, self_ns in self.ops
                        if not rx.search(t) and self_ns > 0)
        starts = [s for s, _ in others]
        hidden = 0
        for s, e in mine:
            i = max(0, bisect.bisect_right(starts, s) - 1)
            while i < len(others) and others[i][0] < e:
                hidden += max(0, min(e, others[i][1]) - max(s, others[i][0]))
                i += 1
        return _length(mine) - hidden


class Reduced:
    def __init__(self, trace):
        self.devices = [DeviceWindow(d, trace["texts"]) for d in trace["devices"]]
        self.devices = [d for d in self.devices if d.window_ns > 0]
        self.host = trace["host"]

    def __bool__(self):
        return bool(self.devices)

    def _mean(self, f):
        return sum(f(d) for d in self.devices) / len(self.devices)

    @property
    def busy_s(self):
        return self._mean(lambda d: d.busy_ns) / 1e9

    @property
    def window_s(self):
        return self._mean(lambda d: d.window_ns) / 1e9

    @property
    def steps(self):
        return max(d.steps for d in self.devices)

    def top_ops(self, n=10):
        """Device operations by self time, seconds, averaged over devices."""
        total = collections.Counter()
        for d in self.devices:
            for text, _, _, self_ns in d.ops:
                total[text] += self_ns
        return [[label(t), ns / 1e9 / len(self.devices)] for t, ns in total.most_common(n)]

    def idle_gaps(self, n=10):
        """Idle time of the first device by the loop's span it falls under
        (the span that holds the gap's middle), seconds."""
        spans = self.host
        starts = [s[1] for s in spans]
        total = collections.Counter()
        for s, e in self.devices[0].gaps():
            mid = (s + e) // 2
            name = "between_ops"
            i = bisect.bisect_right(starts, mid) - 1
            # spans of one thread do not nest here; look a few back for one
            # that is still open at the gap's middle
            for j in range(i, max(-1, i - 4), -1):
                if spans[j][1] <= mid < spans[j][1] + spans[j][2]:
                    name = spans[j][0]
                    break
            total[name] += e - s
        return [[name, ns / 1e9] for name, ns in total.most_common(n)]


def label(text, limit=64):
    """An operation's text as a name: letters, digits, _ . - only."""
    return re.sub(r"[^A-Za-z0-9_.-]", "_", text)[:limit]
