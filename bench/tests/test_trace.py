"""The reduction from trace to numbers: on a trace small enough to count by
hand, and on two steps recorded on the chip (data/)."""

import gzip
import json
import os

import pytest

from bench import reducers, trace

DATA = os.path.join(os.path.dirname(__file__), "data")

# One device, microseconds written as ns x 1000. Three step programs: the
# first is cut off by the start of the trace and is dropped. In each whole
# step: a while loop 0-60 holding a kernel 10-30 and a fusion 30-50, then an
# all-gather in flight 40-90 of which the done op waits 80-90, a fusion
# 60-80, idle 90-100.
US = 1000


def _step(at):
    return [
        [0, at, 60 * US],            # while (self: 20)
        [1, at + 10 * US, 20 * US],  # kernel
        [2, at + 30 * US, 20 * US],  # fusion
        [2, at + 60 * US, 20 * US],  # fusion
        [3, at + 80 * US, 10 * US],  # all-gather-done (waits)
    ]


HAND = {
    "texts": ["while while -> ()", "attn custom-call -> bf16[8,256,64]",
              "fusion fusion -> f32[4]", "all-gather-done all-gather-done -> f32[4]",
              "all-gather-start all-gather-start -> f32[4]", "jit_step_fn(1)"],
    "devices": [{
        "name": "/device:TPU:0",
        "modules": [[5, 50 * US, 50 * US], [5, 100 * US, 100 * US], [5, 200 * US, 100 * US]],
        "ops": [[2, 60 * US, 30 * US]] + _step(100 * US) + _step(200 * US),
        "async": [[4, 140 * US, 50 * US], [4, 240 * US, 50 * US]],
    }],
    "host": [["input", 185 * US, 10 * US], ["dispatch", 195 * US, 10 * US],
             ["sync", 205 * US, 200 * US]],
}


def _rec(tr):
    return {"trace": trace.Reduced(tr), "notes": {}, "spans": {}, "counters": {},
            "step_intervals_s": [],
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_by_hand():
    rec = _rec(HAND)
    tr = rec["trace"]
    assert tr.steps == 2
    assert tr.window_s == pytest.approx(200e-6)
    assert tr.busy_s == pytest.approx(180e-6)
    assert reducers.device_idle_share(rec) == pytest.approx(10.0)
    assert reducers.device_time_per_step(rec) == pytest.approx(0.09)
    # self time: while 20, kernel 20, fusions 40, done 10 of 90 busy a step
    assert reducers.ops_share_of_busy(rec, "^attn custom-call") == pytest.approx(100 * 20 / 90)
    assert dict(tr.top_ops())["fusion_fusion_-__f32_4_"] == pytest.approx(80e-6)
    assert dict(tr.top_ops())["while_while_-____"] == pytest.approx(40e-6)
    # in flight 40-90 of each 100: 50%; other ops cover 40-80, so 10 exposed
    assert reducers.ops_share_of_window(rec, "all-gather") == pytest.approx(50.0)
    assert reducers.ops_share_of_window(rec, "all-gather", exposed=True) == pytest.approx(10.0)
    assert reducers.ops_share_of_window(rec, "reduce-scatter") is None
    # the gap 190-200 has its middle under "dispatch", the gap 290-300 under "sync"
    assert dict(tr.idle_gaps()) == {"dispatch": pytest.approx(10e-6), "sync": pytest.approx(10e-6)}
    # kernel: 8 heads, t=256, d=64: 3 causal matmuls and 5 reads/writes, bytes-bound
    flops, nbytes = 3 * 256 * 256 // 2 * 64 * 2 * 8, 8 * (5 * 256 * 64 * 2 + 2 * 256 * 4)
    least = max(flops / 197e12, nbytes / 819e9)
    assert reducers.ops_roofline(rec, "^attn custom-call", "flash_attention") == pytest.approx(
        100 * least / 20e-6)
    assert rec["notes"] == {"flash_attention calls bound by": "bytes"}


def test_kind_of_an_hlo_text():
    text = ("%attn.80 = (bf16[1536,256,64]{2,1,0:T(8,128)(2,1)}, f32[1536,1,256]{2,1,0:T(1,128)S(1)})"
            " custom-call(bf16[1536,256,64]{2,1,0:T(8,128)(2,1)} %bitcast.1826, f32[4]{0} %custom-call.7)")
    assert trace.kind(text) == "attn custom-call -> (bf16[1536,256,64], f32[1536,1,256])"
    assert trace.kind("%fusion.7 = f32[128,256]{1,0} fusion(f32[2]{0} %p)") == "fusion fusion -> f32[128,256]"
    assert trace.label("attn custom-call -> (bf16[1536,256,64])") == "attn_custom-call_-___bf16_1536_256_64__"


def test_recorded_on_the_chip():
    """Two whole steps and the end of a third of gpt2_small at B=128, T=256 on
    a TPU v5 lite (my chip run, PR 24): the step takes 361.4 ms on the device,
    the flash kernels a third of it, and the device is idle 0.02% of it."""
    with gzip.open(os.path.join(DATA, "gpt2_small_b128_t256_two_steps.json.gz"), "rt") as f:
        rec = _rec(json.load(f))
    tr = rec["trace"]
    assert tr.steps == 2
    assert reducers.device_time_per_step(rec) == pytest.approx(361.4, abs=0.2)
    assert reducers.device_idle_share(rec) == pytest.approx(0.02, abs=0.01)
    flash = "^(?!custom-call )[\\w\\-]+ custom-call -> "
    assert reducers.ops_share_of_busy(rec, flash) == pytest.approx(32.6, abs=0.1)
    assert reducers.ops_roofline(rec, flash, "flash_attention") == pytest.approx(12.0, abs=0.1)
    # brute force: busy time by marking microseconds
    d = tr.devices[0]
    marks = bytearray((d.end - d.start) // 1000 + 2)
    for _, s, e, _ in d.ops:
        for us in range((s - d.start) // 1000, (e - d.start + 999) // 1000):
            marks[us] = 1
    assert sum(marks) * 1e-6 == pytest.approx(tr.busy_s, rel=2e-3)
    assert sum(t for _, t in tr.top_ops(10 ** 6)) == pytest.approx(tr.busy_s, rel=1e-6)
