"""What PR 52 brought to the benchmark, on the CPU: the reader of a span's
milliseconds a step on a trace small enough to count by hand (with the
collector's spans and without) and on the two steps recorded on the chip
before the program opened any, and the two metrics' data files against their
entries of BENCHMARK.json, each found by its name."""

import gzip
import json
import os

import pytest

from bench import reducers
from bench.tests.test_trace import DATA, US, WITH_PROGRAM, _rec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = {"host_heartbeat_gap_ms_max": ("trace_span_percentile", "ray_tpu.host.heartbeat"),
       "host_gc_ms_per_step": ("trace_span_ms_per_step", "ray_tpu.host.gc")}
WITNESS = {"host_gc_ms_per_step": "ray_tpu.host.heartbeat"}

# The hand-made trace's window of two whole steps runs 100-300 us. Four
# collections: one before the window, one the window's start cuts (95-105: 5
# inside), two inside (20 and 7), and heartbeats of 10, 10 and 14 ms beside.
HOST_SPANS = {**WITH_PROGRAM, "program": WITH_PROGRAM["program"] + [
    ["ray_tpu.host.gc", 60 * US, 20 * US, 41], ["ray_tpu.host.gc", 95 * US, 10 * US, 41],
    ["ray_tpu.host.gc", 150 * US, 20 * US, 41], ["ray_tpu.host.gc", 290 * US, 7 * US, 42],
    ["ray_tpu.host.heartbeat", 100 * US, 10_000 * US, 41],
    ["ray_tpu.host.heartbeat", 10_100 * US, 14_000 * US, 41],
    ["ray_tpu.host.heartbeat", 24_100 * US, 10_000 * US, 42]]}


def test_a_span_s_milliseconds_a_step_by_hand():
    rec = _rec(HOST_SPANS)
    assert rec["trace"].steps == 2
    assert reducers.read("host_gc_ms_per_step", rec) == pytest.approx((5 + 20 + 7) / 1e3 / 2)
    assert reducers.read("host_heartbeat_gap_ms_max", rec) == pytest.approx(14.0)


@pytest.mark.parametrize("trace", ["by_hand", "recorded_on_the_chip"])
def test_a_trace_without_the_spans_reads_nothing(trace):
    """A program from before PR 52 opens neither span, and one whose hook or
    heartbeat went missing would read the same: both metrics are left out of
    the line (which the driver accepts of a parent), and the collector's does
    not read a silent program as a perfect one."""
    if trace == "by_hand":
        rec = _rec(WITH_PROGRAM)
    else:
        with gzip.open(os.path.join(DATA, "gpt2_small_b128_t256_pr28_two_steps.json.gz"), "rt") as f:
            rec = _rec(json.load(f))
    assert reducers.read("host_gc_ms_per_step", rec) is None
    assert reducers.read("host_heartbeat_gap_ms_max", rec) is None


def test_a_window_with_heartbeats_and_no_collection_reads_zero():
    """The heartbeat's spans say the emitter was there: no collection's span
    beside them is a window in which none ran, wherever the beats lie."""
    beats = [s for s in HOST_SPANS["program"] if s[0] != "ray_tpu.host.gc"]
    assert reducers.read("host_gc_ms_per_step", _rec({**HOST_SPANS, "program": beats})) == 0.0
    before_the_window = [s for s in beats if s[0] != "ray_tpu.host.heartbeat"] + [
        ["ray_tpu.host.gc", 60 * US, 20 * US, 41]]
    assert reducers.read("host_gc_ms_per_step", _rec({**HOST_SPANS, "program": before_the_window})) == 0.0


def test_without_a_trace_or_a_whole_step_there_is_nothing_to_read():
    rec = _rec(HOST_SPANS)
    assert reducers.read("host_gc_ms_per_step", {**rec, "trace": None}) is None
    assert reducers.read("host_heartbeat_gap_ms_max", {**rec, "trace": None}) is None
    no_device = _rec({**HOST_SPANS, "devices": []})
    assert reducers.read("host_gc_ms_per_step", no_device) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metric_s_file_and_its_entry_found_by_name(name):
    """Both are read in every cell: the entry lists no cells, so it holds for
    the ten there are and for any a later PR appends, wherever it stands."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert "workloads" not in entry
    spec = reducers.load_metric(name)
    reducer, span = NEW[name]
    assert spec["reducer"] == reducer and spec["args"]["span"] == span
    assert spec["args"].get("witness") == WITNESS.get(name)  # the span the other metric reads
    assert callable(reducers.load(reducer))
    for key in ("layer", "unit", "better", "source", "moves"):
        assert entry[key] == spec[key], key
    assert (entry["layer"], entry["unit"], entry["better"], entry["source"], entry["moves"]) == (
        "trainer", "ms", "lower", "program_span", "tokens_per_s")
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    cells = {w["name"] for w in bench["workloads"]}
    assert len(cells) >= 10 and all(
        name in [m["name"] for m in bench["per_layer"] if c in m.get("workloads", [c])]
        for c in cells)
