"""Every cell's files are found by name, and the harness names no cell."""

import json
import os

import pytest

from bench import families, reducers, shapes, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_is_found_by_name(cell):
    config = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        sizes = json.load(f)
    assert sizes["source"] == config["source"]
    assert sorted(sizes["reduced"]) == sorted(config["reduced"])
    fam = families.load(sizes["family"])
    mix = traffic.load(cell["traffic"])
    assert fam.flops_per_token(sizes, mix["seq_len"]) > 0
    data = 1
    for axis, n in sizes["mesh"].items():
        data *= n
    assert data == cell["chips"]
    assert mix["batch"] % mix["reference_rows"] == 0
    a = traffic.make_batch(mix, sizes["vocab_size"], 2 ** 31 + 11, 5)
    b = traffic.make_batch(mix, sizes["vocab_size"], 2 ** 31 + 11, 5)
    assert a["idx"].shape == (mix["batch"], mix["seq_len"])
    assert (a["idx"] == b["idx"]).all() and (a["idx"][:, 1:] == a["targets"][:, :-1]).all()
    small = traffic.load(cell["traffic"], rehearse=True)
    assert small["batch"] * small["seq_len"] < mix["batch"] * mix["seq_len"]


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_is_found_by_name(metric):
    spec = reducers.load_metric(metric["name"])
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == metric[key], key
    assert spec["reducer"] in reducers.REDUCERS
    if "shape_function" in spec.get("args", {}):
        assert spec["args"]["shape_function"] in shapes.FUNCTIONS
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    empty = {"spans": {}, "counters": {}, "step_intervals_s": [], "trace": None,
             "peaks": {}, "notes": {}}
    assert reducers.read(metric["name"], empty) is None  # nothing to read: left out


def test_harness_holds_no_cell_or_configuration_name():
    names = {c["name"] for c in BENCH["configs"]} | {w["name"] for w in BENCH["workloads"]} \
        | {w["traffic"] for w in BENCH["workloads"]}
    bench_dir = os.path.join(ROOT, "bench")
    for fname in ("run.py", "worker.py", "traffic.py", "reducers.py", "trace.py"):
        with open(os.path.join(bench_dir, fname)) as f:
            text = f.read()
        found = sorted(n for n in names if n in text)
        assert not found, (fname, found)


def test_flash_shape_function():
    t, d, bh = 256, 64, 1536
    mm = t * t // 2 * d * 2 * bh  # one causal matmul over all heads
    fwd = shapes.flash_attention("attn custom-call -> (bf16[1536,256,64], f32[1536,1,256])")
    dkv = shapes.flash_attention("attn custom-call -> (bf16[1536,256,64], bf16[1536,256,64])")
    dq = shapes.flash_attention("attn custom-call -> bf16[1536,256,64]")
    assert fwd == (2 * mm, bh * (4 * t * d * 2 + t * 4))
    assert dq == (3 * mm, bh * (5 * t * d * 2 + 2 * t * 4))
    assert dkv == (4 * mm, bh * (6 * t * d * 2 + 2 * t * 4))
    assert shapes.flash_attention("fusion fusion -> f32[128,256]") is None
