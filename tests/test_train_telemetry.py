"""Step-level training telemetry (train/_telemetry.py): recorder math with
a fake clock, the post-warmup jit-compile storm and its promotion to a
jit_cache_miss_storm incident by the watchdog, the completion clock with
handles whose readiness the test controls, what a slow step's record says of
the host (the heartbeat's gap, the collector's pauses, the device's run-ahead,
the cause they name), the model configs' FLOP counts,
metric export through util.metrics, HBM absent-on-CPU, TrainStep
integration, session.report auto-attach, the program's spans in a real
profiler trace, and SPAN events landing in the timeline dump.

CPU-only (JAX_PLATFORMS=cpu via conftest); everything here rides the fast
marker — the cluster tests use the tiniest possible model/loops.
"""

import gc
import glob
import json
import os
import threading
import time
import urllib.request

import pytest

from ray_tpu.train._telemetry import (
    StepRecorder,
    peak_flops_per_device,
    set_current_recorder,
)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def _recorder(clock, **kw):
    kw.setdefault("publish_interval_s", 0.0)
    kw.setdefault("devices", [])
    kw.setdefault("emit_spans", False)
    return StepRecorder(clock=clock, wall_clock=clock, **kw)


@pytest.mark.fast
def test_goodput_and_throughput_math():
    clk = FakeClock()
    rec = _recorder(clk)
    # compile call: 2s, booked as compile not productive
    clk.advance(2.0)
    rec.record_step(2.0, compile_step=True)
    # 8 productive steps of 0.25s, back to back
    for _ in range(8):
        clk.advance(0.25)
        rec.record_step(0.25, tokens=1024, examples=8)
    assert rec.steps == 9
    assert rec.productive_steps == 8
    assert rec.compile_s == pytest.approx(2.0)
    assert rec.productive_s == pytest.approx(2.0)
    # elapsed 4s, productive 2s
    assert rec.goodput() == pytest.approx(0.5)
    assert rec.tokens_per_second() == pytest.approx(8 * 1024 / 2.0)
    assert rec.examples_per_second() == pytest.approx(32.0)
    # a 4s stall (driver pause / restart) halves goodput again
    clk.advance(4.0)
    assert rec.goodput() == pytest.approx(0.25)
    s = rec.summary()
    assert s["steps"] == 9
    assert s["step_time_s"] == pytest.approx(0.25)
    assert s["compile_time_s"] == pytest.approx(2.0)


@pytest.mark.fast
def test_mfu_from_flops_per_step():
    clk = FakeClock()
    rec = _recorder(clk, flops_per_step=1e9, peak_flops=1e12, n_devices=2)
    clk.advance(1.0)
    rec.record_step(1.0, compile_step=True)
    for _ in range(4):
        clk.advance(0.5)
        rec.record_step(0.5)
    # 4 steps * 1e9 FLOPs over 2s on 2 chips of 1e12 peak
    assert rec.mfu() == pytest.approx(4e9 / 2.0 / 2e12)
    # multi-step scan records count as `steps` optimizer steps
    clk.advance(1.0)
    rec.record_step(1.0, steps=10)
    assert rec.productive_steps == 14
    assert rec.mfu() == pytest.approx(14e9 / 3.0 / 2e12)


@pytest.mark.fast
def test_mfu_from_flops_per_token_and_unknown_device():
    clk = FakeClock()
    rec = _recorder(clk, flops_per_token=6e6, peak_flops=1e12, n_devices=1)
    clk.advance(0.5)
    rec.record_step(0.5, tokens=2000)
    assert rec.mfu() == pytest.approx(6e6 * 2000 / 0.5 / 1e12)
    # no peak (CPU device kind) -> MFU honestly absent, not fabricated
    rec2 = _recorder(clk, flops_per_step=1e9)
    rec2.record_step(0.5)
    assert rec2.mfu() is None
    assert peak_flops_per_device("cpu") is None
    assert peak_flops_per_device("TPU v4") == pytest.approx(275e12)


def _mistral_7b_l8():
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig(vocab_size=32768, block_size=8192, n_layer=8, n_head=32,
                       n_kv_head=8, n_embd=4096, intermediate=14336)


def _gpt2_small():
    from ray_tpu.models.gpt2 import GPT2Config

    return GPT2Config.gpt2_124m()


@pytest.mark.fast
@pytest.mark.parametrize("make_cfg, seq_len, flops", [
    (_gpt2_small, 256, 755_347_968),
    (_gpt2_small, 1024, 797_815_296),
    (_mistral_7b_l8, 8192, 12_884_901_888),
], ids=["gpt2_small.t256", "gpt2_small.t1024", "mistral_7b_l8.t8192"])
def test_flops_estimate_from_model_config(make_cfg, seq_len, flops):
    """The program's count is the benchmark's rule (bench/families/, which
    the program does not import): the literals are what bench computes for
    its three cells."""
    assert make_cfg().flops_per_token(seq_len) == flops


@pytest.mark.fast
def test_flops_count_moe_by_active_parameters():
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.models.gpt2_moe import GPT2MoEConfig, MoEConfig

    dense = GPT2Config.tiny()  # L=2, d=128, V=512
    assert dense.flops_per_token(64) == 6 * (12 * 2 * 128 * 128 + 512 * 128) \
        + 6 * 2 * 64 * 128
    # every second block routed: one expert of the 4 d^2 + 4 d^2 kind a token
    # is a dense MLP's count plus the router
    one = GPT2MoEConfig.tiny(moe=MoEConfig(num_experts=4, top_k=1), moe_every=2)
    assert one.matmul_params() == dense.matmul_params() + 128 * 4
    two = GPT2MoEConfig.tiny(moe=MoEConfig(num_experts=4, top_k=2), moe_every=2)
    assert two.matmul_params() == one.matmul_params() + 8 * 128 * 128


# ------------------------------------------------- compile-storm detection


@pytest.mark.fast
def test_compile_storm_detection_after_warmup():
    clk = FakeClock()
    rec = _recorder(clk, emit_metrics=False)
    # warmup: the first compile is expected and never counted
    rec.record_step(1.0, compile_step=True)
    for _ in range(6):
        clk.advance(0.1)
        rec.record_step(0.1)
    assert rec.pop_compile_storm() is None
    # three post-warmup recompiles inside the window (default K=3, 120s)
    for _ in range(3):
        clk.advance(1.0)
        rec.record_step(0.5, compile_step=True)
    storm = rec.pop_compile_storm()
    assert storm is not None and storm["compiles"] >= 3
    assert storm["step"] == rec.steps
    assert rec.pop_compile_storm() is None  # cleared on read


@pytest.mark.fast
def test_compile_storm_respects_window():
    clk = FakeClock()
    rec = _recorder(clk, emit_metrics=False)
    rec.record_step(1.0, compile_step=True)
    for _ in range(6):
        clk.advance(0.1)
        rec.record_step(0.1)
    # compiles spread far wider than the 120s window never accumulate
    for _ in range(4):
        clk.advance(200.0)
        rec.record_step(0.5, compile_step=True)
    assert rec.pop_compile_storm() is None


def test_watchdog_promotes_storm_to_incident(monkeypatch):
    # incident publishing must not depend on a live cluster capture
    monkeypatch.setenv("RTPU_profile_on_incident", "0")
    from ray_tpu._private.watchdog import StallWatchdog
    from ray_tpu.train import _telemetry
    from test_profiling_plane import _StubCore

    clk = FakeClock()
    rec = _recorder(clk, emit_metrics=False)
    rec.record_step(1.0, compile_step=True)
    for _ in range(6):
        clk.advance(0.1)
        rec.record_step(0.1)
    for _ in range(3):
        clk.advance(1.0)
        rec.record_step(0.5, compile_step=True)
    prev = _telemetry.current_recorder()
    _telemetry.set_current_recorder(rec)
    try:
        core = _StubCore()
        wd = StallWatchdog(core)
        wd.check()
        incidents = [p["incident"] for m, p in core.gcs.calls
                     if m == "ReportIncident"]
        storms = [i for i in incidents if i["kind"] == "jit_cache_miss_storm"]
        assert storms, incidents
        inc = storms[0]
        assert inc["compile_storm"]["compiles"] >= 3
        assert "retraced" in inc["detail"]
        # rate-limited: an immediate second storm does not refire
        rec.record_step(0.5, compile_step=True)
        rec.record_step(0.5, compile_step=True)
        rec.record_step(0.5, compile_step=True)
        wd.check()
        incidents2 = [p["incident"] for m, p in core.gcs.calls
                      if m == "ReportIncident"
                      and p["incident"]["kind"] == "jit_cache_miss_storm"]
        assert len(incidents2) == 1
    finally:
        _telemetry.set_current_recorder(prev)


class Handle:
    """A step's output whose readiness the test controls (what
    jax.block_until_ready and is_ready look for on a leaf)."""

    def __init__(self):
        self._done = threading.Event()

    def block_until_ready(self):
        assert self._done.wait(10)
        return self

    def is_ready(self):
        return self._done.is_set()

    def complete(self):
        self._done.set()


class PipelinedLoop:
    """A loop that keeps one step in flight, on the fake clock: each dispatch
    takes `dispatch_s` on the calling thread, each completion comes when the
    test says."""

    def __init__(self, clk, rec, tokens=1000):
        self.clk, self.rec, self.tokens = clk, rec, tokens
        self.in_flight = []

    def dispatch(self, dispatch_s=0.001, compile_step=False):
        started = self.clk()
        self.clk.advance(dispatch_s)
        h = Handle()
        if compile_step:
            h.complete()  # the caller waited for it
        else:
            self.in_flight.append(h)
        self.rec.dispatched(h, started=started, tokens=self.tokens,
                            examples=4, compile_step=compile_step)

    def complete_at(self, t):
        self.clk.t = t
        self.in_flight.pop(0).complete()
        self.rec.settle(30.0)  # the watcher books it; a loaded box may be slow

    def finish(self):
        """Complete what is still in flight and see it booked, so no watcher
        is left waiting, and none books a step inside the next test (where
        `_host_pressure` may be that test's own: it read two for one)."""
        while self.in_flight:
            self.in_flight.pop(0).complete()
        self.rec.settle(30.0)


@pytest.mark.fast
def test_completion_clock_times_the_device_not_the_dispatch():
    clk = FakeClock()
    rec = _recorder(clk, flops_per_token=6e6, peak_flops=1e12, n_devices=1)
    loop = PipelinedLoop(clk, rec)
    loop.dispatch(2.0, compile_step=True)
    rec.settle(30.0)
    assert rec.compiles == 1 and rec.compile_s == pytest.approx(2.0)
    loop.dispatch()
    for k in range(1, 21):
        loop.dispatch()  # step k+1 goes out before step k is done
        loop.complete_at(2.0 + 0.1 * k)
        assert rec.steps == 1 + k
    s = rec.summary()
    assert s["step_time_s"] == pytest.approx(0.1)
    assert s["productive_time_s"] == pytest.approx(2.0)
    assert s["tokens_per_s"] == pytest.approx(1000 / 0.1)
    assert s["examples_per_s"] == pytest.approx(4 / 0.1)
    assert s["mfu"] == pytest.approx(6e6 * 1000 / 0.1 / 1e12)
    assert s["compiles"] == 1
    assert s["dispatch_time_s"] == pytest.approx(21 * 0.001)
    # elapsed 4.0 s, 2.0 s of it compile: goodput after it within a step of 1
    assert (s["productive_time_s"] / (clk() - 2.0)) >= 1 - 1 / 20
    assert rec.goodput() == pytest.approx(2.0 / 4.0, abs=0.01)
    assert rec.pop_slow_step() is None
    # a dispatch five times slower than a whole step moves nothing: the
    # device had work queued, the completion comes on time
    loop.dispatch(0.05)
    loop.complete_at(4.1)
    assert rec.pop_slow_step() is None
    assert rec.summary()["step_time_s"] == pytest.approx(0.1)
    # a completion five times late is a slow step
    loop.dispatch()
    loop.complete_at(4.6)
    slow = rec.pop_slow_step()
    assert slow is not None and slow["ratio"] == pytest.approx(5.0)
    assert rec.summary()["step_time_s"] == pytest.approx(0.5)
    loop.finish()


@pytest.mark.fast
@pytest.mark.parametrize("waited", [2.0, 0.0])
def test_a_slow_step_says_what_the_host_did_to_it(monkeypatch, waited):
    """The watcher reads the process's run-queue wait and the machine's
    steal and iowait at every completion; a step five times the median is
    flagged with the three deltas over that step, zeros where the host did
    nothing (the device or the program was slow), and its flight-recorder
    event carries them after the numbers every flagged step has, under the
    names `slow_step_from_detail` gives them back by."""
    from ray_tpu._private import flight_recorder
    from ray_tpu.train import _telemetry

    host = {"sched": 10.0, "steal": 100.0, "iowait": 50.0}
    monkeypatch.setattr(_telemetry, "_host_pressure",
                        lambda: (host["sched"], host["steal"], host["iowait"]))
    clk = FakeClock()
    rec = _recorder(clk)
    loop = PipelinedLoop(clk, rec)
    loop.dispatch()
    for k in range(1, 21):
        loop.dispatch()
        loop.complete_at(0.1 * k)
    assert rec.pop_slow_step() is None
    # over the slow step the process stood on a run queue for `waited` seconds
    # of its 0.5, and a quarter second was stolen from the machine's CPUs
    host["sched"] += waited
    host["steal"] += waited / 8
    loop.dispatch()
    loop.complete_at(2.5)
    slow = rec.pop_slow_step()
    assert slow["ratio"] == pytest.approx(5.0) and slow["duration_s"] == pytest.approx(0.5)
    assert slow["sched_wait_s"] == pytest.approx(waited)
    assert slow["steal_s"] == pytest.approx(waited / 8) and slow["iowait_s"] == 0.0
    assert slow["cause"] in _telemetry.SLOW_STEP_CAUSES
    assert all(isinstance(v, float) for k, v in slow.items() if k not in ("step", "cause"))
    last = [e for e in flight_recorder.dump() if e.get("event") == "train.step"][-1]
    assert len(last["b"]) == len(_telemetry.SLOW_STEP_DETAIL) + 3
    assert last["b"][:2] == pytest.approx((0.5, 0.1))
    assert last["b"][-3:] == pytest.approx((waited, waited / 8, 0.0))
    # through a worker's flight file and back: every number of the flag
    again = _telemetry.slow_step_from_detail(json.loads(json.dumps(last))["b"])
    assert again == {k: slow[k] for k in again}
    assert set(slow) - set(again) == {"step", "ratio", "time"}
    before = [e for e in flight_recorder.dump() if e.get("event") == "train.step"][-2]
    assert _telemetry.slow_step_from_detail(before["b"]) is None  # its seconds alone
    # the step after it is not slow and carries nothing
    loop.dispatch()
    loop.complete_at(2.6)
    assert rec.pop_slow_step() is None
    loop.finish()


@pytest.mark.fast
def test_a_slow_step_off_linux_carries_no_host_numbers(monkeypatch):
    from ray_tpu.train import _telemetry

    reads = []
    monkeypatch.setattr(_telemetry, "_host_pressure", lambda: reads.append(1))
    clk = FakeClock()
    rec = _recorder(clk)
    loop = PipelinedLoop(clk, rec)
    loop.dispatch()
    for k in range(1, 21):
        loop.dispatch()
        loop.complete_at(0.1 * k)
    loop.dispatch()
    loop.complete_at(2.5)
    slow = rec.pop_slow_step()
    assert slow["ratio"] == pytest.approx(5.0)
    assert not {"sched_wait_s", "steal_s", "iowait_s"} & set(slow)
    loop.finish()
    assert len(reads) == 1  # a kernel that says nothing is asked once, not at every step


@pytest.mark.fast
@pytest.mark.parametrize("cause, numbers", [
    # excess, host_gap_s, host_gap_cpu_s, gc_pause_s, next_done
    ("gc", (2.0, 1.9, 1.9, 1.8, 1.0)),
    ("host_frozen", (2.0, 2.0, 0.01, 0.0, 1.0)),
    ("interpreter_held", (2.0, 1.5, 1.4, 0.05, 0.0)),
    ("completion_late", (2.0, 0.012, 0.001, 0.0, 1.0)),
    ("device", (2.0, 0.018, 0.002, 0.01, 0.0)),
    ("unknown", (2.0, 0.6, 0.5, 0.3, 1.0)),
])
def test_the_cause_of_a_slow_step_is_a_rule_over_its_numbers(cause, numbers):
    from ray_tpu.train import _telemetry

    assert _telemetry.slow_step_cause(*numbers) == cause
    assert cause in _telemetry.SLOW_STEP_CAUSES


@pytest.mark.fast
def test_the_rule_s_edges():
    from ray_tpu.train._telemetry import slow_step_cause

    # a sound host's heartbeat reads up to twice its sleep: no gap, whatever the excess
    assert slow_step_cause(0.012, 0.019, 0.0, 0.0, 0.0) == "device"
    # a gap that covers a fifth of the excess names nothing
    assert slow_step_cause(2.0, 0.41, 0.4, 0.0, 1.0) == "unknown"
    # a held interpreter with a fifth of the excess in collections is not told from them
    assert slow_step_cause(2.0, 1.5, 1.5, 0.4, 1.0) == "unknown"
    # no gap and no other step in flight: nothing to tell late from slow by
    assert slow_step_cause(2.0, 0.011, 0.0, 0.0, -1.0) == "unknown"
    assert slow_step_cause(0.0, 0.0, 0.0, 0.0, 1.0) == "unknown"


def _steps_on_the_real_clock(rec, n, step_s=0.02):
    """A loop that keeps one step in flight, each complete `step_s` after the
    one before: the recorder's own clock and the host's instruments beside it."""
    hs = [Handle()]
    rec.dispatched(hs[0], started=rec.clock(), tokens=10)
    for _ in range(n):
        hs.append(Handle())
        rec.dispatched(hs[-1], started=rec.clock(), tokens=10)
        time.sleep(step_s)
        hs.pop(0).complete()
        rec.settle(30.0)
    return hs


def _paused_step(rec, hs, pause):
    """One more step, with `pause()` run on this thread while it is in flight."""
    hs.append(Handle())
    rec.dispatched(hs[-1], started=rec.clock(), tokens=10)
    pause()
    hs.pop(0).complete()
    rec.settle(30.0)
    return rec.pop_slow_step()


class _Bookings:
    """What `_GcPauses` hands a watch, kept as it comes: the hook's own
    reading of every collection, for a test to hold the intervals' against."""

    step = None

    def __init__(self):
        self.collections = []


def test_a_thread_that_holds_the_interpreter_shows_as_a_gap_with_cpu_burned():
    """A thread of the process's own inside one C call that never lets go of
    the interpreter lock (a sum over a range) for some tenths of a second: the
    heartbeat cannot wake, the process burns CPU all the while, no collection
    runs, and the step that was in flight says so. (On a box whose other
    processes take its CPUs the process is itself held up, which is the
    other cause and no fault: three tries.)"""
    from ray_tpu.train import _telemetry

    t0 = time.perf_counter()
    sum(range(3_000_000))
    n = int(3_000_000 * 0.4 / (time.perf_counter() - t0))

    def hold():
        t = threading.Thread(target=lambda: sum(range(n)))
        t.start()
        t.join(60)
        assert not t.is_alive()

    rec = StepRecorder(devices=[], emit_spans=False, emit_metrics=False)
    gc.disable()  # no collection of another test's heap beside it
    try:
        hs = _steps_on_the_real_clock(rec, 16)
        rec.pop_slow_step()  # a loaded box may have held a step of the sixteen up
        for _ in range(3):
            slow = _paused_step(rec, hs, hold)
            if slow is not None and slow["cause"] == "interpreter_held":
                break
    finally:
        gc.enable()
        hs.pop(0).complete()
        rec.settle(30.0)
    assert slow is not None and slow["cause"] == "interpreter_held", slow
    assert slow["duration_s"] > 0.1 and slow["host_gap_s"] > 0.1
    assert slow["host_gap_cpu_s"] > 0.1 * slow["host_gap_s"]
    assert slow["gc_pause_s"] == 0.0 and slow["gc_generation"] == -1.0
    assert slow["next_done"] == 0.0  # the step after it was in flight, and not done
    s = rec.summary()
    assert s["slow_steps"] >= 1 and s["host_gap_max_s"] >= round(slow["host_gap_s"], 6)
    assert s["gc_pause_max_s"] == 0.0


def test_a_forced_collection_shows_as_a_pause_the_heartbeat_confirms():
    """gc.collect() over a heap of several hundred thousand cycles while a
    step is in flight: the hook times it, the heartbeat's gap over the same
    stretch agrees with the hook's reading within 20% (and the heartbeat's
    own resolution), and the step's cause is the collector."""
    from ray_tpu.train import _telemetry

    rec = StepRecorder(devices=[], emit_spans=False, emit_metrics=False)
    booked = _Bookings()  # what the hook itself timed, beside the watcher's reading of it
    _telemetry._gc_pauses.add(booked)
    gc.disable()  # the one collection is the test's own
    try:
        heap = []
        for _ in range(800_000):
            cell = []
            cell.append(cell)
            heap.append(cell)
        hs = _steps_on_the_real_clock(rec, 16)
        rec.pop_slow_step()  # a loaded box may have held a step of the sixteen up
        for attempt in range(3):  # and may wake the heartbeat late
            slow = _paused_step(rec, hs, gc.collect)
            if (slow is not None and slow["cause"] == "gc"
                    and abs(slow["host_gap_s"] - slow["gc_longest_s"]) <= (
                        0.2 * slow["gc_longest_s"] + 2 * _telemetry._HEARTBEAT_S)):
                break
        else:
            raise AssertionError(f"the heartbeat's gap and the hook's reading disagree: {slow}")
        assert slow["gc_generation"] == 2.0 and slow["gc_pause_s"] >= slow["gc_longest_s"] > 0.03
    finally:
        gc.enable()
        _telemetry._gc_pauses.remove(booked)
        hs.pop(0).complete()
        rec.settle(30.0)
        del heap
    # the hook's own timing: the forced collections were generation 2's, one an
    # attempt, and the last one's length is what the flag and the summary hold
    forced = [took for _, took, generation in booked.collections if generation == 2]
    assert len(forced) == attempt + 1
    assert slow["gc_longest_s"] == pytest.approx(forced[-1] / 1e9, rel=0.2)
    assert rec.summary()["gc_pause_max_s"] >= round(slow["gc_longest_s"], 6)


@pytest.mark.fast
def test_a_collection_still_under_way_counts_up_to_now_and_its_booking_the_rest():
    """The watcher often runs before the collection that delayed it is booked
    (the interpreter hands the lock over at the hook's `stop` call): the
    interval takes the pause from its start to now, and the booking that
    follows adds only what came after."""
    from ray_tpu.train import _telemetry

    pauses = _telemetry._GcPauses()
    watch = _telemetry._HostWatch(step=7, pauses=pauses)
    try:
        assert pauses._watches == [watch] and pauses._hook in gc.callbacks
        watch.take(-1.0)
        started = time.perf_counter_ns()
        pauses.open = (started, 2)  # a generation-2 collection begins
        time.sleep(0.05)
        first = watch.take(1.0)._asdict()
        assert 0.05 <= first["gc_pause_s"] < 0.5 and first["gc_generation"] == 2.0
        assert first["gc_longest_s"] == first["gc_pause_s"]
        # booked 20 ms later, with a small collection after it
        took = round(first["gc_pause_s"] * 1e9) + 20_000_000
        watch.collections.append((started, took, 2))
        watch.collections.append((started + took + 10_000_000, 1_000_000, 0))
        pauses.open = None
        second = watch.take(0.0)._asdict()
        assert second["gc_pause_s"] == pytest.approx(0.020 + 0.001, abs=1e-6)
        assert watch.take(0.0).gc_pause_s == 0.0  # and nothing a third time
    finally:
        watch.stop(join_s=5.0)
    assert pauses._watches == [] and pauses._hook not in gc.callbacks
    assert not watch._thread.is_alive()


@pytest.mark.fast
def test_a_collection_the_last_watch_left_open_is_not_the_next_watch_s():
    """The last watch goes while a collection's start has been seen and its
    stop has not (the hook is out of gc.callbacks by then): the next watch
    finds nothing under way, and its first interval holds no pause."""
    from ray_tpu.train import _telemetry

    pauses = _telemetry._GcPauses()
    first = _telemetry._HostWatch(step=1, pauses=pauses)
    pauses._hook("start", {"generation": 2})
    assert pauses.open is not None and pauses._span is not None
    first.stop(join_s=5.0)
    assert pauses.open is None and pauses._span is None
    pauses._hook("stop", {"generation": 2})  # a stop that does arrive books nothing
    second = _telemetry._HostWatch(step=2, pauses=pauses)
    try:
        time.sleep(0.02)
        taken = second.take(-1.0)
        assert taken.gc_pause_s == 0.0 and taken.gc_generation == -1.0
        assert not second.collections
    finally:
        second.stop(join_s=5.0)


def test_no_pause_is_lost_or_counted_twice_under_switching_threads():
    """Three threads collect as fast as they can while this one takes the
    interval's readings, the interpreter switching threads every 10 us: the
    pauses the intervals took add up to the hook's own readings to the
    nanosecond, whichever side of a collection's booking each take fell on."""
    import sys

    from ray_tpu.train import _telemetry

    pauses = _telemetry._GcPauses()
    watch = _telemetry._HostWatch(step=None, pauses=pauses)
    booked = _Bookings()
    pauses.add(booked)
    stop = threading.Event()

    def collect():
        while not stop.is_set():
            junk = [[] for _ in range(50)]
            junk[0].append(junk)
            gc.collect(0)

    workers = [threading.Thread(target=collect) for _ in range(3)]
    taken_ns = under_way = 0
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for w in workers:
            w.start()
        deadline = time.monotonic() + 1.0
        while time.monotonic() < deadline:
            under_way += pauses.open is not None
            taken_ns += round(watch.take(-1.0).gc_pause_s * 1e9)
    finally:
        stop.set()
        for w in workers:
            w.join(30)
        sys.setswitchinterval(interval)
        watch.stop(join_s=5.0)
    assert not any(w.is_alive() for w in workers)
    taken_ns += round(watch.take(-1.0).gc_pause_s * 1e9)
    booked_ns = sum(took for _, took, _ in booked.collections)
    assert len(booked.collections) > 10 and booked_ns > 0
    assert taken_ns == booked_ns, (taken_ns, booked_ns, under_way)


@pytest.mark.fast
@pytest.mark.parametrize("next_first, cause", [(True, "completion_late"), (False, "device")])
def test_run_ahead_tells_a_late_completion_from_a_slow_device(next_first, cause):
    """The step after the slow one is complete when the watcher's wait on the
    slow one returns (the device went on, the host heard late), or is not
    (the device took long): no host gap either way."""
    clk = FakeClock()
    rec = _recorder(clk)
    asked, next_done = [], rec._next_done
    rec._next_done = lambda: asked.append(rec.steps) or next_done()
    loop = PipelinedLoop(clk, rec)
    loop.dispatch()
    for k in range(1, 21):
        loop.dispatch()
        loop.complete_at(0.1 * k)
    assert asked == []  # a step that comes on time asks nothing of the one after it
    loop.dispatch()
    if next_first:
        loop.in_flight[1].complete()
    loop.complete_at(12.0)  # ten seconds late: far over what a loaded box's heartbeat reads
    slow = rec.pop_slow_step()
    assert asked == [20]
    assert slow["next_done"] == float(next_first) and slow["cause"] == cause, slow
    assert slow["host_gap_s"] < 1.0 and slow["gc_pause_s"] < 1.0
    loop.finish()


@pytest.mark.fast
def test_a_step_timed_by_its_caller_is_flagged_with_no_host_numbers():
    clk = FakeClock()
    rec = _recorder(clk)
    for _ in range(8):
        rec.record_step(0.01)
    rec.record_step(0.2)
    slow = rec.pop_slow_step()
    assert slow["cause"] == "unknown" and slow["next_done"] == -1.0
    assert slow["host_gap_s"] == slow["gc_pause_s"] == 0.0
    assert {"slow_steps", "host_gap_max_s", "gc_pause_max_s"} <= set(rec.summary())
    assert rec.summary()["slow_steps"] == 1


def test_two_recorders_come_and_go_and_leave_no_heartbeat_and_no_hook(monkeypatch):
    """While either recorder has a watcher the process has one hook in
    gc.callbacks and a heartbeat a watcher; when the watchers have ended
    (nothing in flight for _WATCHER_IDLE_S) both are gone, and a recorder
    stopped at exit leaves none either."""
    from ray_tpu.train import _telemetry

    def beats():
        return [t for t in threading.enumerate() if t.name == "train-host-heartbeat"]

    def hooks():
        return [c for c in gc.callbacks if getattr(c, "__self__", None) is _telemetry._gc_pauses]

    def gone(what):
        deadline = time.monotonic() + 10
        while what() and time.monotonic() < deadline:
            time.sleep(0.01)
        return not what()

    assert gone(beats) and not hooks()  # an earlier test's watcher has idled out
    monkeypatch.setattr(_telemetry, "_WATCHER_IDLE_S", 0.05)
    clk = FakeClock()
    first, second = _recorder(clk), _recorder(clk)
    loops = [PipelinedLoop(clk, first), PipelinedLoop(clk, second)]
    for loop in loops:
        loop.dispatch()
    assert len(beats()) == 2 and len(hooks()) == 1
    loops[0].complete_at(0.1)
    assert gone(lambda: first._watcher)
    assert gone(lambda: len(beats()) > 1) and len(hooks()) == 1  # the second's still run
    loops[1].complete_at(0.2)
    assert gone(lambda: second._watcher) and gone(beats) and not hooks()
    assert first._host is None and second._host is None
    # and again: a new watcher brings both back, _stop_watcher ends both at once
    loops[0].dispatch()
    assert len(beats()) == 1 and len(hooks()) == 1
    loops[0].complete_at(0.3)
    first._stop_watcher()
    assert not beats() and not hooks() and first._watcher is None


def test_host_pressure_reads_this_machine():
    from ray_tpu.train import _telemetry

    read = _telemetry._host_pressure()
    if not os.path.exists("/proc/self/schedstat"):
        assert read is None
        return
    assert len(read) == 3 and all(isinstance(v, float) and v >= 0 for v in read)
    again = _telemetry._host_pressure()
    assert all(b >= a for a, b in zip(read, again))  # counters


@pytest.mark.fast
def test_completion_clock_starts_a_step_at_its_dispatch_on_an_idle_device():
    """Where the loop let the device run dry (input stall, checkpoint pause)
    the step lasts from its own dispatch to its completion, and the pause is
    lost goodput, not a slow step."""
    clk = FakeClock()
    rec = _recorder(clk)
    loop = PipelinedLoop(clk, rec)
    for k in range(10):
        loop.dispatch()
        loop.complete_at(k * 1.0 + 0.1)  # 0.9 s of every second idle
        clk.t = (k + 1) * 1.0
    assert rec.summary()["step_time_s"] == pytest.approx(0.1)
    assert rec.productive_s == pytest.approx(1.0)
    assert rec.goodput() == pytest.approx(0.1)
    assert rec.pop_slow_step() is None
    loop.finish()


@pytest.mark.fast
def test_completion_clock_leaves_no_thread_and_survives_a_failed_step(monkeypatch):
    from ray_tpu.train import _telemetry

    class Failing(Handle):
        def block_until_ready(self):
            raise RuntimeError("device lost")

        is_ready = block_until_ready

    monkeypatch.setattr(_telemetry, "_WATCHER_IDLE_S", 0.05)
    clk = FakeClock()
    rec = _recorder(clk)
    rec.dispatched(Failing(), started=clk())
    loop = PipelinedLoop(clk, rec)
    loop.dispatch()
    loop.complete_at(0.3)
    assert rec.steps == 1  # the failed one is not booked, the next one is
    watcher = rec._watcher
    assert watcher is not None and watcher.daemon
    watcher.join(5)
    assert not watcher.is_alive() and rec._watcher is None
    loop.dispatch()  # a later dispatch starts a new one
    loop.complete_at(0.5)
    assert rec.steps == 2
    loop.finish()


@pytest.mark.fast
def test_hbm_gauge_absent_on_cpu():
    """device.memory_stats() returns None on CPU — the recorder must not
    crash nor emit an HBM gauge."""
    import jax

    clk = FakeClock()
    rec = StepRecorder(clock=clk, wall_clock=clk, publish_interval_s=0.0,
                       devices=jax.local_devices(), emit_spans=False)
    rec.record_step(0.1)
    assert rec.hbm_bytes_in_use() == {}
    assert "hbm_bytes_in_use" not in rec.summary()


@pytest.mark.fast
def test_hbm_gauge_present_with_stats():
    class FakeDev:
        platform = "tpu"
        id = 0
        device_kind = "TPU v5e"

        def memory_stats(self):
            return {"bytes_in_use": 123456}

    clk = FakeClock()
    rec = StepRecorder(clock=clk, wall_clock=clk, publish_interval_s=0.0,
                       devices=[FakeDev()], emit_spans=False)
    rec.record_step(0.1)
    assert rec.hbm_bytes_in_use() == {"tpu:0": 123456.0}
    assert rec.summary()["hbm_bytes_in_use"] == 123456.0


@pytest.mark.fast
def test_metrics_reach_util_metrics_records():
    from ray_tpu.util import metrics as um

    um.drain_records()  # isolate from other tests' leftovers
    clk = FakeClock()
    rec = _recorder(clk, flops_per_step=1e9, peak_flops=1e12, n_devices=1)
    clk.advance(1.0)
    rec.record_step(1.0, compile_step=True)
    for _ in range(3):
        clk.advance(0.2)
        rec.record_step(0.2, tokens=100, examples=2)
    by_name = {}
    for r in um.drain_records():
        by_name.setdefault(r["name"], r)
    assert by_name["ray_tpu_train_steps_total"]["value"] == 4
    assert by_name["ray_tpu_train_step_seconds"]["count"] == 3
    assert by_name["ray_tpu_train_step_seconds"]["sum"] == pytest.approx(0.6)
    assert by_name["ray_tpu_train_goodput_ratio"]["value"] == pytest.approx(
        0.6 / 1.6)
    assert by_name["ray_tpu_train_tokens_per_second"]["value"] == pytest.approx(
        300 / 0.6)
    assert by_name["ray_tpu_train_mfu_ratio"]["value"] == pytest.approx(
        3e9 / 0.6 / 1e12)
    assert by_name["ray_tpu_train_compile_seconds"]["value"] == pytest.approx(
        1.0)


@pytest.mark.fast
def test_session_report_auto_attaches_telemetry():
    from ray_tpu.train._session import (
        TrainContext, init_session, report, shutdown_session,
    )

    clk = FakeClock()
    s = init_session(TrainContext(0, 1, 0, 1, "127.0.0.1"), None,
                     pipeline_depth=4)
    try:
        rec = _recorder(clk)
        set_current_recorder(rec)
        clk.advance(0.5)
        rec.record_step(0.5, tokens=64)
        report({"loss": 1.5, "telemetry/goodput": "user-wins"})
        item = s.reports.get_nowait()
        m = item["metrics"]
        assert m["loss"] == 1.5
        assert m["telemetry/steps"] == 1
        assert m["telemetry/tokens_per_s"] == pytest.approx(128.0)
        # user-provided keys always win over auto-attached ones
        assert m["telemetry/goodput"] == "user-wins"
    finally:
        set_current_recorder(None)
        shutdown_session()


@pytest.mark.fast
def test_train_step_records_compile_and_steps():
    """TrainStep books jit cache misses as compile time (both the first
    trace AND the ambient-mesh-context recompile), and productive steps
    carry token counts."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.train_step import TrainStep

    cfg = GPT2Config.tiny(use_flash_attention=False, dtype=jnp.float32)
    ts = TrainStep(cfg, make_mesh({"dp": 8}), learning_rate=1e-3)
    assert ts.telemetry is not None
    from ray_tpu.train._telemetry import current_recorder

    assert current_recorder() is ts.telemetry
    state = ts.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    idx = rng.integers(0, cfg.vocab_size, size=(8, 32)).astype(np.int32)
    batch = {"idx": jnp.asarray(idx),
             "targets": jnp.asarray(np.roll(idx, -1, 1))}
    for _ in range(4):
        state, _ = ts.step(state, ts.shard_batch(batch))
    rec = ts.telemetry
    # steps are booked when they complete, not when they are enqueued
    jax.block_until_ready(state)
    rec.settle(30.0)
    assert rec.steps == 4
    assert rec.compiles == rec.steps - rec.productive_steps
    assert rec.compile_s > 0
    assert rec.productive_steps >= 2  # at most 2 calls were cache misses
    assert rec.productive_s > 0
    assert rec.tokens == 8 * 32 * rec.productive_steps
    assert rec.flops == cfg.flops_per_token(32) * rec.tokens
    # CPU: no HBM stats, no MFU (unknown peak) — absent, not wrong
    assert rec.hbm_bytes_in_use() == {}
    s = rec.summary()
    assert s["goodput"] <= 1.0


@pytest.mark.fast
def test_telemetry_opt_out():
    import jax.numpy as jnp

    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.train_step import TrainStep

    cfg = GPT2Config.tiny(use_flash_attention=False, dtype=jnp.float32)
    ts = TrainStep(cfg, make_mesh({"dp": 8}), telemetry=False)
    assert ts.telemetry is None


def _program_spans(trace_dir):
    """(name, start, end, step, thread line) of every ray_tpu.* span in the
    profiler's trace under trace_dir."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("ray_tpu."):
                    stats = {k: v for k, v in e.stats}
                    spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                  stats.get("step"), (plane.name, i)))
    return spans


def test_program_spans_in_a_device_trace_window(monkeypatch, tmp_path):
    """A window opened by request_device_trace (forced on the CPU) round
    three steps of a tiny TrainStep and their train.report holds every span
    of the program, each with its step, the children inside their parents."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    monkeypatch.setenv("RTPU_device_trace_force", "1")
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.train_step import TrainStep
    from ray_tpu.train import _telemetry
    from ray_tpu.train._session import (
        TrainContext, init_session, report, shutdown_session,
    )

    # an earlier test's recorder beats on for _WATCHER_IDLE_S after its last
    # step, longer than this one's compiles take since PR 58: the window is
    # to hold one heartbeat's line, this recorder's
    for t in threading.enumerate():
        if t.name == "train-host-heartbeat":
            t.join(10 * _telemetry._WATCHER_IDLE_S)
            assert not t.is_alive()
    session = init_session(TrainContext(0, 1, 0, 1, "127.0.0.1"), None,
                           pipeline_depth=4)
    try:
        cfg = GPT2Config.tiny(use_flash_attention=False, dtype=jnp.float32)
        ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]))
        state = ts.init(jax.random.PRNGKey(0))
        idx = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 16)).astype(np.int32)
        batch = {"idx": idx, "targets": np.roll(idx, -1, 1)}
        state, _ = ts.step(state, ts.shard_batch(batch))  # compiles: step 1
        trace_dir = str(tmp_path / "window")
        assert _telemetry.request_device_trace(3, trace_dir)
        for _ in range(3):  # steps 2, 3, 4
            state, _ = ts.step(state, ts.shard_batch(batch))
            # the watcher has closed its wait on this step before the next
            # begins, whatever the load (step 4's ends as the window closes)
            jax.block_until_ready(state)
            ts.telemetry.settle(30.0)
            time.sleep(2.5 * _telemetry._HEARTBEAT_S)  # a tiny step is shorter than a tick
            report({"loss": 0.0})
            session.reports.get_nowait()
            session.ack()
        summary = ts.telemetry.summary()
    finally:
        _telemetry.set_current_recorder(None)
        shutdown_session()
    spans = _program_spans(trace_dir)
    by_name = {}
    for name, start, end, step, line in spans:
        # the watcher may still be on its (instant) wait for step 1, the
        # compile call, when the window opens, and its heartbeat with it; a
        # collection carries the step the watcher was on
        first = 2 if name.startswith(("ray_tpu.train_step.", "ray_tpu.train.")) else 1
        if name == "ray_tpu.train_step.wait":
            first = 1
        assert isinstance(step, int) and first <= step <= 4, (name, step)
        by_name.setdefault(name.removeprefix("ray_tpu."), {})[step] = (start, end, line)
    # the heartbeat ticks all through the window on a thread of its own, a
    # span from one wake-up to the next
    beats = sorted((start, end, line) for n, start, end, _, line in spans
                   if n == "ray_tpu.host.heartbeat")
    assert len(beats) >= 3 and len({line for _, _, line in beats}) == 1
    assert all(end - start >= 0.9 * _telemetry._HEARTBEAT_S * 1e9 for start, end, _ in beats)
    assert all(b[0] >= a[1] for a, b in zip(beats, beats[1:]))
    assert beats[0][2] not in {line for n, _, _, _, line in spans if n != "ray_tpu.host.heartbeat"}
    by_name.pop("host.heartbeat")
    by_name.pop("host.gc", None)  # a collection may or may not fall in three tiny steps
    assert sorted(by_name) == [
        "train.report", "train.report.slot_wait", "train_step.dispatch",
        "train_step.jit", "train_step.record", "train_step.shard_batch",
        "train_step.wait"]
    # the window opens inside step 2 (after its batch was placed) and closes
    # when step 4 is complete (before it is reported)
    assert sorted(by_name["train_step.dispatch"]) == [2, 3, 4]
    # (step 4's wait ends as the window closes, on the watcher's thread)
    assert {2, 3} <= set(by_name["train_step.wait"])
    assert sorted(by_name["train_step.shard_batch"]) == [3, 4]
    assert sorted(by_name["train.report"]) == [2, 3]

    def inside(child, parent):
        for step, (start, end, line) in by_name[child].items():
            p_start, p_end, p_line = by_name[parent][step]
            assert p_start <= start and end <= p_end and line == p_line, (child, step)

    inside("train_step.jit", "train_step.dispatch")
    inside("train_step.record", "train_step.dispatch")
    inside("train.report.slot_wait", "train.report")
    # the watcher waits on its own thread, and ends after the dispatch began
    # (step 1's dispatch, the compile call, came before the window opened)
    for step, (start, end, line) in by_name["train_step.wait"].items():
        if step == 1:
            continue
        d_start, _, d_line = by_name["train_step.dispatch"][step]
        assert line != d_line and end > d_start
    # the counters at the same boundaries, through the summary that is there
    assert summary["steps"] == 4 and summary["compiles"] == 1
    assert summary["dispatch_time_s"] > 0 and summary["slot_wait_time_s"] > 0


def test_step_spans_reach_timeline_dump(ray_start_regular, tmp_path):
    """Per-step SPAN events flow task-events -> GCS -> timeline(): the
    Chrome trace must contain train_step spans with durations."""
    import ray_tpu

    rec = StepRecorder(publish_interval_s=0.0, devices=[])
    rec.record_step(0.5, compile_step=True)
    for _ in range(3):
        rec.record_step(0.02, tokens=256)
    out = tmp_path / "trace.json"
    deadline = time.time() + 20
    spans = []
    while time.time() < deadline:
        ray_tpu.timeline(str(out))
        events = json.loads(out.read_text())
        spans = [e for e in events
                 if e.get("cat") == "span"
                 and str(e.get("name", "")).startswith("train_step")]
        if len(spans) >= 4:
            break
        time.sleep(0.3)
    assert len(spans) >= 4
    compile_spans = [e for e in spans if e["name"] == "train_step.compile"]
    assert compile_spans and compile_spans[0]["dur"] == pytest.approx(
        0.5e6, rel=0.01)
    step_spans = [e for e in spans if e["name"] == "train_step"]
    assert step_spans[0]["args"]["tokens"] == "256"


def test_trainer_run_exports_prometheus_metrics(ray_start_regular, tmp_path):
    """Acceptance: a CPU-only JaxTrainer run followed by a GCS /metrics
    scrape shows the ray_tpu_train_* series, and the dashboard /api/train
    summarizes them per job."""
    import ray_tpu
    from ray_tpu import train
    from ray_tpu.train import JaxConfig, JaxTrainer, RunConfig, ScalingConfig

    def loop(config):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models.gpt2 import GPT2Config
        from ray_tpu.parallel.mesh import make_mesh
        from ray_tpu.parallel.train_step import TrainStep

        cfg = GPT2Config.tiny(use_flash_attention=False, dtype=jnp.float32)
        ts = TrainStep(cfg, make_mesh({"dp": 1}, devices=jax.devices()[:1]),
                       learning_rate=1e-3)
        state = ts.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        idx = rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int32)
        batch = {"idx": jnp.asarray(idx),
                 "targets": jnp.asarray(np.roll(idx, -1, 1))}
        for _ in range(3):
            state, m = ts.step(state, ts.shard_batch(batch))
        train.report({"loss": float(m["loss"])})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=str(tmp_path), name="telem"),
        jax_config=JaxConfig(distributed=False),
    )
    result = trainer.fit()
    # report() auto-attached the telemetry summary
    assert result.metrics["telemetry/steps"] == 3
    assert 0 < result.metrics["telemetry/goodput"] <= 1.0
    assert result.metrics["telemetry/tokens_per_s"] > 0

    from ray_tpu._private import worker as worker_mod

    port = worker_mod.global_worker.gcs.ping()["metrics_port"]
    deadline = time.time() + 25
    text = ""
    while time.time() < deadline:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
            text = r.read().decode()
        if "ray_tpu_train_step_seconds" in text:
            break
        time.sleep(0.5)
    assert "ray_tpu_train_step_seconds_bucket" in text
    assert "ray_tpu_train_steps_total" in text
    assert "ray_tpu_train_tokens_per_second" in text
    assert "ray_tpu_train_goodput_ratio" in text

    # dashboard /api/train aggregates the same series per job
    from ray_tpu.dashboard.head import DashboardHead

    head = DashboardHead(worker_mod.global_worker.gcs.address)
    status, payload = head._collect("/api/train", "GET", None, {})
    assert status == 200
    jobs = payload["jobs"]
    assert jobs, "no jobs in /api/train"
    job = next(iter(jobs.values()))
    assert job["steps"] >= 3
    assert job["tokens_per_second"] > 0
    assert job["step_seconds"]["count"] >= 1
    assert job["step_seconds"]["p50"] is not None
