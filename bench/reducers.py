"""The generic readers of per-layer metrics. A metric is a data file under
bench/layer_metrics/ that names one of these and gives its arguments; a
reader that finds nothing to read returns None and the metric is left out.

`rec` is what one run recorded: host spans of the loop (milliseconds per
step of the window), named counters, the window's step intervals, the
reduced device trace (bench/trace.py, None without --trace 1) and the
device's peaks.
"""

import json
import os
import statistics

from bench import shapes

HERE = os.path.dirname(os.path.abspath(__file__))


def span_percentile(rec, span, percentile):
    values = sorted(rec["spans"].get(span) or [])
    if not values:
        return None
    return values[min(len(values) - 1, int(round(percentile / 100 * (len(values) - 1))))]


def counter(rec, counter):
    return rec["counters"].get(counter)


def stall_share(rec):
    """Share of the window lost to steps slower than the median one."""
    intervals = rec["step_intervals_s"]
    if len(intervals) < 2:
        return None
    return 100 * (1 - len(intervals) * statistics.median(intervals) / sum(intervals))


def device_time_per_step(rec):
    tr = rec["trace"]
    return tr.busy_s / tr.steps * 1e3 if tr and tr.steps else None


def device_idle_share(rec):
    tr = rec["trace"]
    return 100 * (1 - tr.busy_s / tr.window_s) if tr else None


def ops_share_of_busy(rec, pattern):
    tr = rec["trace"]
    if not tr:
        return None
    share = [sum(op[3] for op in d.matching(pattern)) / d.busy_ns for d in tr.devices]
    return 100 * sum(share) / len(share)


def ops_roofline(rec, pattern, shape_function):
    """Least time the chip could take for the matching calls (the larger of
    operations over peak FLOP/s and bytes over peak bytes/s, call by call)
    over the time they took."""
    tr = rec["trace"]
    if not tr:
        return None
    fn, peaks = shapes.FUNCTIONS[shape_function], rec["peaks"]
    least = took = 0.0
    bound_by = {"flops": 0.0, "bytes": 0.0}
    for d in tr.devices:
        for text, _, _, self_ns in d.matching(pattern):
            need = fn(text)
            if need is None:
                continue
            by_flops = need[0] / peaks["bf16_flops_per_s"]
            by_bytes = need[1] / peaks["hbm_bytes_per_s"]
            least += max(by_flops, by_bytes)
            bound_by["flops" if by_flops >= by_bytes else "bytes"] += self_ns
            took += self_ns / 1e9
    if not took:
        return None
    rec["notes"][f"{shape_function} calls bound by"] = max(bound_by, key=bound_by.get)
    return 100 * least / took


def ops_share_of_window(rec, pattern, exposed=False):
    """Time on the first device in which a matching op runs (with exposed:
    and nothing else does), over the traced window."""
    tr = rec["trace"]
    if not tr:
        return None
    d = tr.devices[0]
    covered = d.covered_ns(pattern)
    if not covered:
        return None
    return 100 * (d.covered_ns(pattern, exposed=True) if exposed else covered) / d.window_ns


REDUCERS = {f.__name__: f for f in (
    span_percentile, counter, stall_share, device_time_per_step,
    device_idle_share, ops_share_of_busy, ops_roofline, ops_share_of_window)}


def load_metric(name):
    with open(os.path.join(HERE, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def read(name, rec):
    spec = load_metric(name)
    return REDUCERS[spec["reducer"]](rec, **spec.get("args", {}))
