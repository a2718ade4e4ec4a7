"""Step-level workload telemetry for training loops: MFU, goodput, HBM.

The control plane is instrumented end to end (GCS/raylet /metrics, task
events, flamegraphs) but the training loop itself — the thing this
framework exists to run — was an observability black hole. This module is
the training counterpart of the serve request metrics: a ``StepRecorder``
captures per-step wall time, first-step compile time, tokens/examples per
second, estimated MFU, goodput and per-device HBM in use, and publishes
them through the three surfacing pipelines that already exist:

  1. ``ray_tpu.util.metrics`` Gauge/Counter/Histogram records, which ride
     the worker's task-event flush to the GCS aggregator and out the
     Prometheus ``/metrics`` endpoint (zero new transport);
  2. one ``SPAN`` task event per step, so ``ray-tpu timeline`` renders
     step boundaries in the Chrome trace next to task execution;
  3. ``session.report`` auto-attaches the rolling summary, so trainer
     results and the dashboard's ``/api/train`` see the same numbers.

Step time is the device's, not the dispatch's: a jitted step returns at
enqueue, so ``TrainStep`` hands each call's metrics output (never the donated
state) to ``StepRecorder.dispatched`` and a watcher thread waits on them in
order. A step lasts from the completion before it — or from its own
dispatch, where the device stood idle by then — to its own completion, and
everything below reads that one clock: step seconds, tokens/s, MFU, the
slow-step flag, the step SPAN and the flight-recorder breadcrumb. Goodput
is the fraction of wall time since the recorder started that was spent
inside productive (post-compile) steps, so a busy device reads about 1.0
and compiles, restarts, input stalls and checkpoint pauses all show up as
lost goodput, which is the number the TPU-scaling literature treats as the
primary scaling diagnostic.

The same boundaries are spans on the profiler's clock
(``jax.profiler.TraceAnnotation``, a flag test while no trace is open), so
a device-trace window holds what the host did beside the device ops:
``ray_tpu.train_step.shard_batch``, ``.dispatch`` with its children ``.jit``
and ``.record``, ``.wait`` (the watcher), and ``ray_tpu.train.report`` with
``.slot_wait``. Each carries ``step``. Beside them two spans say what the host
did to the worker's interpreter, on whichever thread it happened:
``ray_tpu.host.heartbeat`` (one from each wake-up of a 10 ms sleeper to the
next: a long one is a stretch in which no Python ran) and ``ray_tpu.host.gc``
(one a collection, with its ``generation``). A step flagged slow carries the
same two readings over its own interval, whether the device had already
finished the step after it, and the cause those numbers name
(``slow_step_cause``).

Metric names are a stability contract — see ``ray_tpu/util/metrics.py``.
"""

from __future__ import annotations

import atexit
import contextlib
import dataclasses
import gc
import logging
import os
import statistics
import sys
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, NamedTuple, Optional, Tuple

logger = logging.getLogger(__name__)

# Peak dense matmul throughput per chip (bf16 FLOP/s), keyed by substrings
# of jax's ``device_kind``. Used for the MFU estimate; unknown device kinds
# (CPU, new TPU generations) simply don't get an MFU gauge rather than a
# wrong one.
_PEAK_FLOPS_BY_KIND = {
    "TPU v6": 918e12,
    "TPU v5p": 459e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v3": 123e12,
    "TPU v2": 45e12,
}

_HBM_SAMPLE_EVERY = 16  # memory_stats() per step would be pure overhead

# Histogram boundaries for step seconds: log-spaced 1ms .. 60s covers
# everything from dispatch-bound CPU smoke steps to pod-scale LLM steps.
_STEP_SECONDS_BOUNDARIES = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)


def peak_flops_per_device(device_kind: str) -> Optional[float]:
    """Best-effort peak bf16 FLOP/s for a jax ``device_kind`` string."""
    for kind, flops in _PEAK_FLOPS_BY_KIND.items():
        if kind.lower() in device_kind.lower():
            return flops
    return None


def trace_span(name: str, step: Optional[int] = None, **stats):
    """A span on the profiler's clock, with the step number as the identifier
    its spans share. A process that never imported jax has no profiler and
    gets a null context."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    if step is not None:
        stats["step"] = step
    return jax.profiler.TraceAnnotation(name, **stats)


def _finished(handle) -> bool:
    """Whether the program that computes a step's output has completed, or
    failed. The outputs of one program complete together, so one that is
    ready tells for all: the loop may have waited on any of them, and
    another's flag can still be a moment behind."""
    import jax

    leaves = [x for x in jax.tree.leaves(handle) if hasattr(x, "is_ready")]
    try:
        return not leaves or any(x.is_ready() for x in leaves)
    except RuntimeError:
        return True


_TICK_S = 1.0 / os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 0.01


def _host_pressure() -> Optional[Tuple[float, float, float]]:
    """Seconds so far that this process waited on a run queue
    (/proc/self/schedstat, second field, ns) and that the machine's CPUs
    spent stolen by the hypervisor and waiting on I/O (/proc/stat, first
    line, ticks). None off Linux, and under a sandbox kernel that keeps no
    such file (gVisor). The watcher reads it at each completion, so a step
    that was slow says whether the host was: two small reads a step, on a
    thread that is otherwise blocked; a watcher whose read found nothing
    does not ask again (``StepRecorder._host_delta``). Where /proc is
    absent the heartbeat stands in (``_HostWatch``): a process kept off its
    CPUs, which here reads as run-queue wait or steal, reads there as
    ``host_gap_s`` with ``host_gap_cpu_s`` near zero; iowait has no stand-in."""
    try:
        with open("/proc/self/schedstat") as f:
            waited = int(f.read().split()[1]) / 1e9
        with open("/proc/stat") as f:
            cpu = f.readline().split()
        return waited, int(cpu[8]) * _TICK_S, int(cpu[5]) * _TICK_S
    except (OSError, IndexError, ValueError):
        return None


# The watcher thread ends after this long with nothing in flight, so an
# abandoned recorder leaves no thread behind; the next dispatch starts one.
_WATCHER_IDLE_S = 2.0
# The heartbeat thread's sleep between two wake-ups: what a host gap is
# resolved to. It lives as long as the watcher does.
_HEARTBEAT_S = 0.010
# At interpreter exit a watcher is given this long to see its steps complete
# (the runtime's own exit waits for them too). CPython ends a daemon thread
# that returns from a wait while the interpreter is finalizing by unwinding it,
# and inside the runtime's C++ frames that aborts the process.
_EXIT_WAIT_S = 10.0


class _Paused(NamedTuple):
    """What a step's interval held of the host, as the watcher hands it to
    record_step: the longest stretch between two wake-ups of the heartbeat,
    the process's CPU seconds over that stretch, how long before the
    completion was seen it ended (0: it was still open), the collector's
    pauses summed, the longest of them and its generation (-1: none), and
    whether the device had finished the step after this one by then (1 / 0;
    -1: not asked, of a step that came on time, or none was in flight)."""

    host_gap_s: float = 0.0
    host_gap_cpu_s: float = 0.0
    host_gap_end_s: float = 0.0
    gc_pause_s: float = 0.0
    gc_longest_s: float = 0.0
    gc_generation: float = -1.0
    next_done: float = -1.0


SLOW_STEP_CAUSES = ("unknown", "gc", "host_frozen", "interpreter_held",
                    "completion_late", "device")
# A flagged step's train.step flight event, numbers in this order (the cause
# as its index above), then _host_pressure's three where /proc has them.
SLOW_STEP_DETAIL = ("duration_s", "median_s", *_Paused._fields, "cause")
_HOST_PRESSURE = ("sched_wait_s", "steal_s", "iowait_s")


def slow_step_cause(excess_s: float, host_gap_s: float, host_gap_cpu_s: float,
                    gc_pause_s: float, next_done: float) -> str:
    """Why a step completed ``excess_s`` later than the median one, as far as
    the numbers of its own interval say. The collector's pauses cover most of
    the excess: ``gc``. A heartbeat that woke late by most of it: the process
    did not run (``host_frozen``: CPU over the gap under a tenth of it; a
    stopped sandbox, a hypervisor) or a thread of its own held the interpreter
    (``interpreter_held``: CPU burned, no collection to speak of). No gap to
    speak of: the device had already finished the next step, so this one's
    completion was delivered late (``completion_late``), or it had not, and
    the chip itself took long (``device``). A wake-up counts as late from
    twice the heartbeat's sleep, the most a sound host reads."""
    late = host_gap_s - _HEARTBEAT_S
    if excess_s <= 0:
        return "unknown"
    if gc_pause_s >= 0.5 * excess_s:
        return "gc"
    if late >= max(0.5 * excess_s, _HEARTBEAT_S):
        if host_gap_cpu_s < 0.1 * host_gap_s:
            return "host_frozen"
        return "interpreter_held" if gc_pause_s < 0.1 * excess_s else "unknown"
    if late < max(0.1 * excess_s, _HEARTBEAT_S) and next_done >= 0:
        return "completion_late" if next_done else "device"
    return "unknown"


def slow_step_from_detail(detail) -> Optional[Dict[str, Any]]:
    """The numbers of a ``train.step`` flight event under their names, as a
    worker's flight file holds them; None for a step that was not flagged
    (its detail is its seconds alone)."""
    if not isinstance(detail, (list, tuple)) or len(detail) < len(SLOW_STEP_DETAIL):
        return None
    out = dict(zip(SLOW_STEP_DETAIL + _HOST_PRESSURE, map(float, detail)))
    out["cause"] = SLOW_STEP_CAUSES[int(out["cause"])]
    return out


class _GcPauses:
    """The process's one ``gc.callbacks`` hook: every collection timed, opened
    as span ``ray_tpu.host.gc`` with its generation on the thread it runs on,
    and handed to the host watches that are open. In ``gc.callbacks`` while any
    watch is; a collection runs with the interpreter lock held and none starts
    inside another, so one open slot is enough. The threads a collection kept
    waiting ask for the lock meanwhile, and the interpreter hands it over at
    the first bytecode of the hook's ``stop`` call: a watcher often runs before
    the collection that delayed it is booked, so ``open`` says which one is
    under way and since when (``_HostWatch.take`` counts it from there), and
    a pause's reading holds that hand-over too."""

    def __init__(self):
        self._lock = threading.Lock()
        self._watches: list = []
        self.open: Optional[Tuple[int, int]] = None  # started (ns), generation
        self._span = None

    def add(self, watch: "_HostWatch") -> None:
        with self._lock:
            self._watches.append(watch)
            if len(self._watches) == 1:
                gc.callbacks.append(self._hook)

    def remove(self, watch: "_HostWatch") -> None:
        with self._lock:
            if watch in self._watches:
                self._watches.remove(watch)
                if not self._watches:
                    gc.callbacks.remove(self._hook)
                    # one under way on another thread (the lock is handed
                    # over inside the hook) ends unheard: the next watch
                    # must not find it still open
                    self._close()

    def _close(self) -> None:
        span, self._span, self.open = self._span, None, None
        if span is not None:
            span.__exit__(None, None, None)

    def _hook(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            step = self._watches[0].step if self._watches else None
            span = trace_span("ray_tpu.host.gc", step, generation=info["generation"])
            span.__enter__()
            self._span, self.open = span, (time.perf_counter_ns(), info["generation"])
        elif self.open is not None:
            started, generation = self.open
            took = time.perf_counter_ns() - started
            for watch in self._watches:
                watch.collections.append((started, took, generation))
            self._close()  # after the booking: take() looks at `open` first


_gc_pauses = _GcPauses()


class _HostWatch:
    """What the host did to this process's interpreter over the step interval
    that is open: a heartbeat thread that sleeps ``_HEARTBEAT_S`` inside span
    ``ray_tpu.host.heartbeat`` and at each wake-up reads the wall clock and the
    process's CPU time, keeping the longest stretch between two wake-ups (a
    stretch of wall time with no CPU time is a process that did not run; with
    CPU time, a thread of its own that held the interpreter lock), and the
    collections ``_GcPauses`` timed. One a watcher thread, started and ended
    with it; the watcher ``take``s the interval's readings at each completion.
    ``perf_counter`` is the recorder's own ``monotonic`` on Linux."""

    def __init__(self, step: Optional[int], pauses: Optional[_GcPauses] = None):
        self.step = step  # the step the watcher waits on: the spans carry it
        self._pauses = pauses or _gc_pauses  # the process's, but for a test's own
        # (started, nanoseconds, generation) a collection; the hook appends,
        # take() pops
        self.collections: deque = deque(maxlen=4096)
        # the collection take() counted while it was under way and how much of
        # it, and the newest one it found booked
        self._counted_open = (0, 0)
        self._booked = 0
        self._lock = threading.Lock()
        self._stopped = False
        self._beat_ns = time.perf_counter_ns()  # the newest wake-up
        self._beat_cpu_ns = time.process_time_ns()
        self._gap = (0, 0, 0)  # the interval's longest: ns, CPU ns, when it ended
        self._thread = threading.Thread(
            target=self._beat, name="train-host-heartbeat", daemon=True)
        self._pauses.add(self)
        self._thread.start()

    def _beat(self) -> None:
        while not self._stopped:
            # a span lasts from one wake-up to the next
            with trace_span("ray_tpu.host.heartbeat", self.step):
                now, cpu = time.perf_counter_ns(), time.process_time_ns()
                with self._lock:
                    if now - self._beat_ns > self._gap[0]:
                        self._gap = (now - self._beat_ns, cpu - self._beat_cpu_ns, now)
                    self._beat_ns, self._beat_cpu_ns = now, cpu
                time.sleep(_HEARTBEAT_S)

    def take(self, next_done: float) -> _Paused:
        """The open interval's readings, and a new interval. A wake-up that
        is still due counts from its last one to now (the watcher may run
        before the heartbeat does when both were held),
        and the heartbeat then measures its next stretch from here, so one
        pause is not two intervals'. Likewise a collection that is not booked
        yet counts from its start to now, and its booking later less that:
        less what was counted and nothing else, so no pause is lost to a
        thread switch between a clock's reading and its keeping."""
        under_way = self._pauses.open  # before the booked ones: it may be booked meanwhile
        now, cpu = time.perf_counter_ns(), time.process_time_ns()
        with self._lock:
            (gap, gap_cpu, ended), self._gap = self._gap, (0, 0, 0)
            if now - self._beat_ns > gap:
                gap, gap_cpu, ended = now - self._beat_ns, cpu - self._beat_cpu_ns, now
                self._beat_ns, self._beat_cpu_ns = now, cpu
        found = []
        while self.collections:
            self._booked, took, gen = self.collections.popleft()
            if self._booked == self._counted_open[0]:
                took -= self._counted_open[1]
            found.append((took, gen))
        # one that began after the newest booked one: `open` may still name a
        # collection that is booked (the hook held before it clears it), and
        # others may have come and gone since it was read
        if under_way is not None and under_way[0] > self._booked:
            started, gen = under_way
            counted = self._counted_open[1] if started == self._counted_open[0] else 0
            found.append((now - started - counted, gen))
            self._counted_open = (started, now - started)
        paused = sum(took for took, _ in found)
        longest, generation = max(found, default=(0, -1))
        return _Paused(gap / 1e9, gap_cpu / 1e9, (now - ended) / 1e9 if ended else 0.0,
                       paused / 1e9, longest / 1e9, float(generation), next_done)

    def stop(self, join_s: Optional[float] = None) -> None:
        self._stopped = True
        self._pauses.remove(self)
        if join_s is not None:
            self._thread.join(join_s)


@dataclasses.dataclass(slots=True)
class _InFlight:
    """One enqueued step program: what to wait on, when it was enqueued,
    and what to book when it completes."""

    handle: Any
    started: float
    enqueued: float
    step: Optional[int]
    steps: int
    tokens: Optional[int]
    examples: Optional[int]
    flops: Optional[float]
    compile_step: bool


class StepRecorder:
    """Accumulates step-level training telemetry and publishes it.

    Thread-safe; one recorder per training run (``TrainStep`` creates and
    registers one automatically, ``current_recorder()`` hands it to
    ``session.report``). Two ways in: ``dispatched`` for a call that returns
    at enqueue (the completion clock times it), ``record_step`` for a caller
    that timed a finished step itself.

    Clock injection (``clock``/``wall_clock``) exists for deterministic
    unit tests; production uses monotonic time for durations and wall time
    for span boundaries.
    """

    def __init__(
        self,
        *,
        flops_per_step: Optional[float] = None,
        flops_per_token: Optional[float] = None,
        peak_flops: Optional[float] = None,
        n_devices: Optional[int] = None,
        run_name: str = "",
        emit_metrics: bool = True,
        emit_spans: bool = True,
        publish_interval_s: float = 0.5,
        clock=time.monotonic,
        wall_clock=time.time,
        devices=None,
    ):
        self._lock = threading.Lock()
        self._clock = clock
        self._wall = wall_clock
        self._flops_per_step = flops_per_step
        self._flops_per_token = flops_per_token
        self._explicit_peak = peak_flops
        self._n_devices = n_devices
        self._devices = devices
        self.run_name = run_name
        self._emit_metrics = emit_metrics and os.environ.get(
            "RTPU_TRAIN_TELEMETRY", "1") != "0"
        self._emit_spans = emit_spans
        self._start = self._clock()
        self._trace_id = uuid.uuid4().hex
        self.steps = 0
        self.productive_steps = 0
        self.productive_s = 0.0
        self.compile_s = 0.0
        self.compiles = 0
        self.tokens = 0
        self.examples = 0
        self.flops = 0.0  # model FLOPs of the productive steps
        # Host seconds at the two boundaries the loop can wait at: inside
        # TrainStep's dispatch (compile calls apart) and in train.report's
        # queue for the driver.
        self.dispatch_s = 0.0
        self.slot_wait_s = 0.0
        # What the newest completed step said beside its loss and its
        # gradients' norm (its family's metrics, models/__init__.py:Family),
        # read when the watcher saw it done.
        self.step_gauges: Dict[str, float] = {}
        # What the newest compiled step program saves across its blocks'
        # remat (models/remat.py:RematPlan), set by TrainStep at a compile.
        self.remat_plan = None
        # The completion clock: step programs in flight, oldest first, and
        # when the newest finished one was seen complete. The watcher thread
        # waits on them in order and books each at its completion, which a
        # loop that waits for its steps spends inside its own wait.
        self.dispatched_steps = 0
        self._pending: deque = deque()
        self._pending_cond = threading.Condition()
        self._watcher: Optional[threading.Thread] = None
        self._host: Optional[_HostWatch] = None  # the watcher's, as long as it lives
        self._closing = False
        self._last_done = self._start
        # _host_pressure() at the newest completion; False once a read found
        # nothing (this kernel says nothing: not asked again)
        self._pressure = None
        self._last_step_s = 0.0
        self._metrics = None
        self._hbm_bytes: Dict[str, float] = {}
        # Derived gauges (goodput/MFU/throughput) recompute at most every
        # publish_interval_s — the per-step hot cost stays at one histogram
        # observe + one counter inc + one span buffer append (~µs), which
        # matters at millisecond TPU step times.
        self._publish_interval = publish_interval_s
        self._last_gauge_pub = float("-inf")
        self._last_step_at = self._start  # stall-watchdog freshness probe
        # Slow-step detection for the profiling plane: per-step durations
        # feed a trailing window; a step slower than
        # RTPU_profile_slow_step_factor x the window median is flagged and
        # picked up by the stall watchdog (pop_slow_step), which captures a
        # cluster profile while the cause is likely still warm. The factor
        # is snapshotted once — each RTPU_CONFIG read is an os.environ
        # probe, too slow for a per-step path.
        from ray_tpu._private.config import RTPU_CONFIG

        self._slow_factor = RTPU_CONFIG.profile_slow_step_factor
        self._recent_steps: deque = deque(maxlen=32)
        self._median_cache: Optional[float] = None  # refreshed every 8 steps
        self._steps_since_median = 0
        self._slow_step: Optional[Dict[str, Any]] = None
        # steps flagged so far, and the longest host gap and the longest single
        # collection any step's interval has held
        self.slow_steps = 0
        self.host_gap_max_s = 0.0
        self.gc_pause_max_s = 0.0
        # Compile-storm detection: the jit-cache-miss bookkeeping above
        # already *knows* every recompilation; this turns
        # "many compiles long after warmup" — the unstable-shapes/dtypes
        # failure mode that silently halves throughput — into a flag the
        # watchdog promotes to a jit_cache_miss_storm GCS incident. Config
        # snapshotted once (per-step path).
        self._storm_k = int(RTPU_CONFIG.perf_compile_storm_k)
        self._storm_window = float(RTPU_CONFIG.perf_compile_storm_window_s)
        self._storm_warmup = int(RTPU_CONFIG.perf_compile_warmup_steps)
        self._compile_times: deque = deque(maxlen=64)
        self._compile_storm: Optional[Dict[str, float]] = None
        # Device-trace window (jax.profiler) armed via request_device_trace
        # or RTPU_device_trace_steps; driven by TrainStep around dispatch.
        self.device_trace = DeviceTraceController()

    # ------------------------------------------------- the completion clock

    def dispatched(
        self,
        handle,
        *,
        started: float,
        step: Optional[int] = None,
        steps: int = 1,
        tokens: Optional[int] = None,
        examples: Optional[int] = None,
        flops: Optional[float] = None,
        compile_step: bool = False,
    ) -> None:
        """A step program of ``steps`` optimizer steps was enqueued at
        ``started`` (this recorder's ``clock()``). ``handle`` is an output of
        it that is not donated to the next call — its metrics; the watcher
        thread waits on the handles in order and books each step at its
        completion, so the caller gains no wait. A ``compile_step`` call has
        been waited for by its caller: its time from ``started`` to now is
        compile time, and it only moves the clock."""
        now = self._clock()
        entry = _InFlight(handle, started, now, step, steps, tokens, examples,
                          flops, compile_step)
        self.dispatched_steps = (
            step if step is not None else self.dispatched_steps + steps)
        if not compile_step:
            self.dispatch_s += now - started
        with self._pending_cond:
            self._pending.append(entry)
            if self._watcher is None:
                self._host = _HostWatch(entry.step)
                self._watcher = threading.Thread(
                    target=self._watch, args=(self._host,),
                    name="train-step-watcher", daemon=True)
                self._watcher.start()
                atexit.register(self._stop_watcher)
            self._pending_cond.notify_all()

    def clock(self) -> float:
        return self._clock()

    def _watch(self, host: _HostWatch) -> None:
        """Wait on the oldest step in flight, book it at its completion, and
        again; the thread ends when nothing has been in flight for a while,
        and its heartbeat with it."""
        import jax

        while True:
            with self._pending_cond:
                if not self._pending and not self._closing:
                    self._pending_cond.wait(_WATCHER_IDLE_S)
                if not self._pending:
                    self._watcher = self._host = None
                    host.stop()
                    atexit.unregister(self._stop_watcher)
                    return
                entry = self._pending[0]
            host.step = entry.step
            try:
                with trace_span("ray_tpu.train_step.wait", entry.step):
                    jax.block_until_ready(entry.handle)
                done = self._clock()
                # from the completion before it, or from its own dispatch
                # where the device had run dry by then
                took = done - max(entry.started, self._last_done)
                # before anything else, of a step that is late already: had
                # the device gone on by now
                late = not entry.compile_step and self._over_the_factor(
                    took / max(entry.steps, 1))
                paused = host.take(self._next_done() if late else -1.0)
                self._read_gauges(entry.handle)
                host_delta = self._host_delta()
                if entry.compile_step:
                    self.record_step(entry.enqueued - entry.started,
                                     steps=entry.steps, compile_step=True)
                else:
                    self.record_step(
                        took, steps=entry.steps, tokens=entry.tokens,
                        examples=entry.examples, flops=entry.flops,
                        host=host_delta, paused=paused)
            except Exception:
                # the loop's own wait on this step raises the same error;
                # the step is not booked and the clock restarts at the next
                logger.warning("train step %s failed on the device",
                               entry.step, exc_info=True)
                done = self._clock()
            with self._pending_cond:
                self._last_done = done
                self._pending.popleft()
                self._pending_cond.notify_all()

    def _over_the_factor(self, per_step_s: float) -> bool:
        """Whether a step of that length is a slow one: over
        profile_slow_step_factor x the trailing median, once there is one."""
        med = self._median_cache
        return bool(self._slow_factor > 0 and med and per_step_s > self._slow_factor * med)

    def _next_done(self) -> float:
        """Whether the step after the oldest in flight has completed too: 1
        says the device went on while this thread had not heard of the first
        (a late completion, not a slow device), -1 that no other is in flight.
        Asked of a late step alone: it reads every leaf of a program's output
        with the interpreter held, a millisecond where the leaves are many."""
        with self._pending_cond:
            after = self._pending[1] if len(self._pending) > 1 else None
        return -1.0 if after is None else float(_finished(after.handle))

    def _read_gauges(self, metrics) -> None:
        """The completed step's own gauges, every scalar of its metrics
        beside the loss and the gradients' norm, as they are (a scan of
        several steps: its last): scalars of a program that has ended, so
        reading them waits for nothing."""
        import numpy as np

        if not isinstance(metrics, dict):
            return
        found = {k: float(np.asarray(v).reshape(-1)[-1])
                 for k, v in metrics.items() if k not in ("loss", "grad_norm")}
        if found:
            with self._lock:
                self.step_gauges = found

    def _host_delta(self) -> Optional[Tuple[float, ...]]:
        """What the host did to this process since the completion before: a
        slow step's record says whether the host was slow. The watcher's
        alone, and a recorder has one watcher at a time."""
        if self._pressure is False:
            return None
        before, now = self._pressure, _host_pressure()
        self._pressure = False if now is None else now
        if before is None or now is None:
            return None
        return tuple(b - a for a, b in zip(before, now))

    def _stop_watcher(self) -> None:
        with self._pending_cond:
            self._closing = True
            watcher, host = self._watcher, self._host
            self._pending_cond.notify_all()
        if watcher is not None:
            watcher.join(_EXIT_WAIT_S)
        if host is not None:
            host.stop(join_s=1.0)  # a watcher that ended has stopped it already

    def settle(self, timeout_s: float = 5.0) -> None:
        """Return once every step whose program has completed (or failed) is
        booked: the watcher is microseconds behind the device, more on a
        starved host. Steps still running are not waited for. ``summary`` settles first, so a loop
        that waited for a step reads it in its next report."""
        deadline = time.monotonic() + timeout_s
        with self._pending_cond:
            while self._pending and _finished(self._pending[0].handle):
                left = deadline - time.monotonic()
                if left <= 0:
                    return
                self._pending_cond.wait(left)

    def add_slot_wait(self, seconds: float) -> None:
        """train.report waited this long for the driver to take a report."""
        with self._lock:
            self.slot_wait_s += seconds

    # ------------------------------------------------------------ recording

    def record_step(
        self,
        duration_s: float,
        *,
        steps: int = 1,
        tokens: Optional[int] = None,
        examples: Optional[int] = None,
        flops: Optional[float] = None,
        compile_step: bool = False,
        start_wall: Optional[float] = None,
        host: Optional[Tuple[float, float, float]] = None,
        paused: Optional[_Paused] = None,
    ) -> None:
        """Record ``steps`` finished optimizer steps that took ``duration_s``
        in total, to completion (a call timed to its return at enqueue goes
        through ``dispatched``). ``compile_step`` marks a jit-cache-miss call
        whose duration is compile + one step — it's booked as compile time,
        not productive step time, so MFU/throughput aren't poisoned by it.
        ``flops`` is the model FLOPs of these steps where the caller knows
        them; otherwise flops_per_step or flops_per_token x tokens. ``host``
        is what ``_host_pressure`` read over these steps (seconds on a run
        queue, stolen, waiting on I/O) and ``paused`` what the watcher's
        ``_HostWatch`` read (``_Paused``): a step flagged slow carries both,
        and the cause they name."""
        duration_s = max(0.0, float(duration_s))
        from ray_tpu._private import flight_recorder as _fr

        with self._lock:
            self.steps += steps
            self._last_step_at = self._clock()
            per_step = duration_s / max(steps, 1)
            med = self._median_cache
            slow = not compile_step and self._over_the_factor(per_step)
            if paused is not None:
                self.host_gap_max_s = max(self.host_gap_max_s, paused.host_gap_s)
                self.gc_pause_max_s = max(self.gc_pause_max_s, paused.gc_longest_s)
            # numbers, not text: nothing is formatted before a dump
            detail = duration_s
            if compile_step and self.remat_plan is not None:
                detail = (duration_s, *self.remat_plan)
            elif slow:
                # a caller that timed the step itself had no watcher beside it
                paused = paused or _Paused()
                cause = slow_step_cause(
                    duration_s - med * steps, paused.host_gap_s, paused.host_gap_cpu_s,
                    paused.gc_pause_s, paused.next_done)
                detail = (per_step, med, *paused,
                          float(SLOW_STEP_CAUSES.index(cause)), *(host or ()))
            _fr.record("train.compile" if compile_step else "train.step",
                       self.steps, detail)
            if compile_step:
                self.compile_s += duration_s
                self.compiles += 1
                if self._storm_k > 0 and self.steps > self._storm_warmup:
                    now_m = self._clock()
                    self._compile_times.append(now_m)
                    recent = [t for t in self._compile_times
                              if now_m - t <= self._storm_window]
                    if len(recent) >= self._storm_k:
                        self._compile_storm = {
                            "compiles": len(recent),
                            "window_s": self._storm_window,
                            "step": self.steps,
                            "compile_s": self.compile_s,
                            "time": self._wall(),
                        }
            else:
                self.productive_s += duration_s
                self.productive_steps += steps
                self._last_step_s = per_step
                # flagged (above) BEFORE appending: the outlier must not
                # dilute the median it is judged against. The median itself
                # refreshes every 8 steps — a per-step O(1) compare, not a
                # per-step sort (this path runs at millisecond step times).
                if slow:
                    self.slow_steps += 1
                    # why, as far as the process can see: what its own
                    # heartbeat, collector and queue say, and the host's
                    # share of the step where /proc keeps it
                    self._slow_step = {
                        "step": self.steps,
                        "duration_s": per_step,
                        "median_s": med,
                        "ratio": per_step / med,
                        "time": self._wall(),
                        **paused._asdict(),
                        "cause": cause,
                        **dict(zip(_HOST_PRESSURE, host or ())),
                    }
                self._recent_steps.append(per_step)
                self._steps_since_median += 1
                if (self._steps_since_median >= 8
                        and len(self._recent_steps) >= 8):
                    self._median_cache = statistics.median(self._recent_steps)
                    self._steps_since_median = 0
            if tokens:
                self.tokens += tokens
            if examples:
                self.examples += examples
            if not compile_step:
                if flops is None and self._flops_per_step is not None:
                    flops = self._flops_per_step * steps
                elif flops is None and self._flops_per_token and tokens:
                    flops = self._flops_per_token * tokens
                self.flops += flops or 0.0
            sample_hbm = (
                self.steps <= steps or self.steps % _HBM_SAMPLE_EVERY == 0
            )
        if sample_hbm:
            self._sample_hbm()
        if self._emit_metrics:
            self._publish(duration_s, steps, compile_step)
        if self._emit_spans:
            self._emit_step_span(duration_s, steps, tokens, compile_step,
                                 start_wall)

    def seconds_since_last_step(self) -> Optional[float]:
        """Age of the newest recorded step; None before the first step.
        The stall watchdog (_private/watchdog.py) reads this to detect a
        training loop that recorded steps and then went silent."""
        with self._lock:
            if self.steps == 0:
                return None
            return self._clock() - self._last_step_at

    def pop_slow_step(self) -> Optional[Dict[str, Any]]:
        """Latest pending slow-step flag (step slower than
        profile_slow_step_factor x trailing median), cleared on read: its
        seconds, the median's, ``_Paused``'s readings over its interval,
        ``cause`` (``slow_step_cause``, the one text among numbers) and
        ``_host_pressure``'s three where /proc has them. The watchdog polls
        this and answers with an automatic cluster-profile capture +
        ``slow_step`` incident."""
        with self._lock:
            out, self._slow_step = self._slow_step, None
            return out

    def pop_compile_storm(self) -> Optional[Dict[str, float]]:
        """Pending compile-storm flag (> K post-warmup jit compiles within
        the configured window), cleared on read. The watchdog polls this and
        publishes a ``jit_cache_miss_storm`` incident with an attached
        cluster capture + auto-analysis."""
        with self._lock:
            out, self._compile_storm = self._compile_storm, None
            return out

    # ------------------------------------------------------------- derived

    def _elapsed(self) -> float:
        return max(self._clock() - self._start, 1e-9)

    def goodput(self) -> float:
        """Fraction of elapsed wall time spent in productive steps."""
        return min(1.0, self.productive_s / self._elapsed())

    def tokens_per_second(self) -> Optional[float]:
        if not self.tokens or self.productive_s <= 0:
            return None
        return self.tokens / self.productive_s

    def examples_per_second(self) -> Optional[float]:
        if not self.examples or self.productive_s <= 0:
            return None
        return self.examples / self.productive_s

    def _total_peak_flops(self) -> Optional[float]:
        if self._explicit_peak is not None:
            n = self._n_devices or len(self._jax_devices() or []) or 1
            return self._explicit_peak * n
        devices = self._jax_devices()
        if not devices:
            return None
        per = peak_flops_per_device(getattr(devices[0], "device_kind", ""))
        if per is None:
            return None
        return per * (self._n_devices or len(devices))

    def mfu(self) -> Optional[float]:
        """Model FLOPs utilization: achieved FLOP/s over peak FLOP/s.

        Needs the steps' model FLOPs (given with each step, or from
        flops_per_step or flops_per_token x observed tokens) and a known
        device peak; returns None otherwise
        (e.g. on CPU) rather than a fabricated number."""
        peak = self._total_peak_flops()
        if peak is None or self.productive_s <= 0 or not self.flops:
            return None
        return self.flops / self.productive_s / peak

    def hbm_bytes_in_use(self) -> Dict[str, float]:
        """Latest per-device HBM bytes in use ({} on CPU — memory_stats()
        is absent there)."""
        with self._lock:
            return dict(self._hbm_bytes)

    def summary(self) -> Dict[str, Any]:
        """Rolling summary dict, also what session.report auto-attaches."""
        self.settle()
        with self._lock:
            out = {
                "steps": self.steps,
                "step_time_s": self._last_step_s,
                "productive_time_s": round(self.productive_s, 6),
                "compile_time_s": round(self.compile_s, 6),
                "compiles": self.compiles,
                "dispatch_time_s": round(self.dispatch_s, 6),
                "slot_wait_time_s": round(self.slot_wait_s, 6),
                "slow_steps": self.slow_steps,
                "host_gap_max_s": round(self.host_gap_max_s, 6),
                "gc_pause_max_s": round(self.gc_pause_max_s, 6),
                **self.step_gauges,
            }
        out["goodput"] = round(self.goodput(), 6)
        tps = self.tokens_per_second()
        if tps is not None:
            out["tokens_per_s"] = round(tps, 3)
        eps = self.examples_per_second()
        if eps is not None:
            out["examples_per_s"] = round(eps, 3)
        mfu = self.mfu()
        if mfu is not None:
            out["mfu"] = round(mfu, 6)
        hbm = self.hbm_bytes_in_use()
        if hbm:
            out["hbm_bytes_in_use"] = max(hbm.values())
        if self.remat_plan is not None:
            out["remat_saved_bytes"] = self.remat_plan.saved_bytes
        if self.device_trace.profile is not None:
            out["device_profile"] = self.device_trace.profile
        return out

    # ------------------------------------------------------------ emission

    def _jax_devices(self):
        if self._devices is not None:
            return self._devices
        try:
            import jax

            self._devices = jax.local_devices()
        except Exception:
            self._devices = []
        return self._devices

    def _sample_hbm(self):
        """Per-device HBM bytes in use via device.memory_stats() —
        gracefully absent on CPU (memory_stats() returns None there)."""
        for d in self._jax_devices() or []:
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats or "bytes_in_use" not in stats:
                continue
            key = f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', 0)}"
            with self._lock:
                self._hbm_bytes[key] = float(stats["bytes_in_use"])

    def _metric_objects(self):
        if self._metrics is None:
            from ray_tpu.util.metrics import Counter, Gauge, Histogram

            tags = ("run",)
            self._metrics = {
                "step_seconds": Histogram(
                    "ray_tpu_train_step_seconds",
                    "device time per optimizer step, completion to completion",
                    boundaries=_STEP_SECONDS_BOUNDARIES, tag_keys=tags),
                "steps_total": Counter(
                    "ray_tpu_train_steps_total",
                    "optimizer steps completed", tag_keys=tags),
                "tokens_per_s": Gauge(
                    "ray_tpu_train_tokens_per_second",
                    "training throughput, tokens/s", tag_keys=tags),
                "examples_per_s": Gauge(
                    "ray_tpu_train_examples_per_second",
                    "training throughput, examples/s", tag_keys=tags),
                "mfu": Gauge(
                    "ray_tpu_train_mfu_ratio",
                    "estimated model FLOPs utilization (0-1)", tag_keys=tags),
                "goodput": Gauge(
                    "ray_tpu_train_goodput_ratio",
                    "productive step time / elapsed wall time (0-1)",
                    tag_keys=tags),
                "compile_s": Gauge(
                    "ray_tpu_train_compile_seconds",
                    "cumulative jit compile time", tag_keys=tags),
                "hbm": Gauge(
                    "ray_tpu_train_hbm_bytes_in_use",
                    "per-device HBM bytes in use",
                    tag_keys=tags + ("device",)),
            }
        return self._metrics

    def _publish(self, duration_s: float, steps: int, compile_step: bool):
        try:
            m = self._metric_objects()
            tags = {"run": self.run_name}
            if compile_step:
                m["compile_s"].set(self.compile_s, tags=tags)
            else:
                # one observation per step CALL (a multi_step scan is one
                # dispatch) at the per-step duration — quantiles stay
                # representative and a 10k-step scan costs one bucket bump
                m["step_seconds"].observe(
                    duration_s / max(steps, 1), tags=tags)
            m["steps_total"].inc(steps, tags=tags)
            now = self._clock()
            if (now - self._last_gauge_pub < self._publish_interval
                    and not compile_step):
                return
            self._last_gauge_pub = now
            m["goodput"].set(self.goodput(), tags=tags)
            tps = self.tokens_per_second()
            if tps is not None:
                m["tokens_per_s"].set(tps, tags=tags)
            eps = self.examples_per_second()
            if eps is not None:
                m["examples_per_s"].set(eps, tags=tags)
            mfu = self.mfu()
            if mfu is not None:
                m["mfu"].set(mfu, tags=tags)
            for dev, used in self.hbm_bytes_in_use().items():
                m["hbm"].set(used, tags={**tags, "device": dev})
        except Exception:
            pass  # telemetry must never fail a training step

    def _emit_step_span(self, duration_s, steps, tokens, compile_step,
                        start_wall):
        """One SPAN task event per step call: ``ray-tpu timeline`` renders
        step boundaries in the Chrome trace beside task execution."""
        try:
            from ray_tpu._private import worker as worker_mod

            w = worker_mod.global_worker
            if w is None:
                return
            end = self._wall()
            start = start_wall if start_wall is not None else end - duration_s
            ctx = {
                "trace_id": self._trace_id,
                "span_id": uuid.uuid4().hex[:16],
                "parent_span_id": "",
            }
            name = "train_step.compile" if compile_step else "train_step"
            attrs = {"step": self.steps, "num_steps": steps}
            if tokens:
                attrs["tokens"] = tokens
            w.task_events.record_span(name, start, end, ctx, attrs)
        except Exception:
            pass


# ------------------------------------------------------ device-trace window
# The host-side sampler (profiling plane) sees Python; XLA device time is a
# black box to it. This controller arms ``jax.profiler.trace`` around a
# window of N train steps (TrainStep calls on_step_begin/on_step_end around
# each dispatch). When the window closes the trace is reduced to a device
# profile (train/_device_profile.py: ms a step by the program's own scopes
# and passes), written as device_profile.json beside the raw trace, and the
# record registered with the GCS carries its path and largest rows, so the
# listings print numbers; the recorder's summary and a flight-recorder event
# carry them too.


class DeviceTraceController:
    """Arm-once device-trace windows; inert (two attribute reads per step)
    unless armed via ``request()`` or ``RTPU_device_trace_steps=N``."""

    def __init__(self):
        self._lock = threading.Lock()
        self._active = False
        self._dir: Optional[str] = None
        self._count = 0
        self._target = 0
        self._requested_dir: Optional[str] = None
        from ray_tpu._private.config import RTPU_CONFIG

        self._armed = max(0, int(RTPU_CONFIG.device_trace_steps))
        # What the newest closed window reduced to (_device_profile.brief),
        # the child process that reduces one and the thread that waits for it.
        self.profile: Optional[Dict[str, Any]] = None
        self._reducer: Optional[threading.Thread] = None
        self._child = None

    # ------------------------------------------------------------- control

    def request(self, num_steps: int = 3,
                trace_dir: Optional[str] = None) -> None:
        """Arm a trace window around the next ``num_steps`` step calls."""
        with self._lock:
            if not self._active:
                self._armed = max(1, int(num_steps))
                self._requested_dir = trace_dir

    @staticmethod
    def supported() -> bool:
        """Device tracing is a no-op on CPU or without a usable jax
        profiler — RTPU_device_trace_force=1 overrides (tests, host-trace
        debugging)."""
        from ray_tpu._private.config import RTPU_CONFIG

        if RTPU_CONFIG.device_trace_force:
            return True
        try:
            import jax

            if not hasattr(jax.profiler, "start_trace"):
                return False
            return any(d.platform != "cpu" for d in jax.local_devices())
        except Exception:
            return False

    def _trace_dir(self) -> str:
        if self._requested_dir:
            return self._requested_dir
        base = ""
        try:
            from ray_tpu._private import worker as worker_mod

            w = worker_mod.global_worker
            if w is not None and w.session_dir:
                base = os.path.join(w.session_dir, "logs", "device_traces")
        except Exception:
            pass
        if not base:
            import tempfile

            base = os.path.join(tempfile.gettempdir(), "ray_tpu_device_traces")
        return os.path.join(base, f"trace_{int(time.time() * 1000)}")

    # ----------------------------------------------------------- per step

    def on_step_begin(self) -> None:
        if not self._armed or self._active:
            return
        with self._lock:
            if not self._armed or self._active:
                return
            target, self._armed = self._armed, 0
            if not self.supported():
                return  # silently disarm: no-op on CPU/absent profiler
            try:
                import jax

                path = self._trace_dir()
                os.makedirs(path, exist_ok=True)
                # the program's spans and the device's lines, no Python
                # frames: nothing reads them and they bury the host threads
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(path, profiler_options=options)
            except Exception:
                return
            self._active = True
            self._dir = path
            self._target = target
            self._count = 0

    def on_step_end(self, out=None) -> None:
        if not self._active:
            return
        with self._lock:
            if not self._active:
                return
            self._count += 1
            if self._count < self._target:
                return
            self._active = False
            path, self._dir = self._dir, None
            try:
                import jax

                if out is not None:
                    # drain the async dispatch backlog so the window holds
                    # the whole last step, not its launch
                    jax.block_until_ready(out)
                jax.profiler.stop_trace()
            except Exception:
                return
        # the directory is listed at once; its numbers follow under the same
        # key when the trace is reduced. That takes seconds and holds an
        # interpreter: a child process does it, and a daemon thread waits for
        # the child, for a bounded time (_device_profile.CHILD_WAIT_S)
        key = self._register(path, None, None)
        self._reducer = threading.Thread(
            target=self._reduce, args=(path, key), name="device-profile", daemon=True)
        atexit.register(self._stop_reducer)
        self._reducer.start()

    def wait_profile(self, timeout_s: Optional[float] = None) -> Optional[Dict[str, Any]]:
        """The newest window's profile, once its reduction has ended."""
        reducer = self._reducer
        if reducer is not None:
            reducer.join(timeout_s)
        return self.profile

    def _reduce(self, path: str, key: Optional[str]) -> None:
        """What the window held (train/_device_profile.py), said four ways: a
        file beside the trace, the GCS record, the recorder's summary, one
        flight-recorder event."""
        try:
            from ray_tpu._private import flight_recorder as _fr
            from ray_tpu.train import _device_profile

            self._child = _device_profile.start_child(path)
            profile = _device_profile.profile_from_child(self._child, path)
            self.profile = _device_profile.brief(profile)
            # numbers, not text: nothing is formatted before a dump
            _fr.record("train.device_profile", profile["steps"], (
                profile["busy_ms"], profile["idle_share"], profile["reduce_s"],
                *profile["shares"].values(),
                profile["devices"] if profile["platform"] == "tpu" else 0))
            self._register(path, self.profile, key)
        except Exception:
            logger.warning("device trace %s was not reduced", path, exc_info=True)
        finally:
            self._child = None
            atexit.unregister(self._stop_reducer)

    def _stop_reducer(self) -> None:
        """At interpreter exit: a reduction under way is given the watcher's
        few seconds, then its child is ended with the process."""
        reducer = self._reducer
        if reducer is not None:
            reducer.join(_EXIT_WAIT_S)
        child = self._child
        if child is not None and child.poll() is None:
            child.kill()

    def _register(self, path: str, profile, key: Optional[str]) -> Optional[str]:
        try:
            from ray_tpu._private import profiling, worker as worker_mod

            w = worker_mod.global_worker
            if w is not None:
                return profiling.register_device_trace(
                    w.gcs, path, steps=self._target, profile=profile, key=key)
        except Exception:
            pass
        return None


def request_device_trace(num_steps: int = 3,
                         trace_dir: Optional[str] = None) -> bool:
    """Arm a device-trace window on the current recorder; False when no
    recorder is registered in this process."""
    rec = current_recorder()
    if rec is None:
        return False
    rec.device_trace.request(num_steps, trace_dir)
    return True


# ----------------------------------------------------- process-global hookup
# TrainStep registers its recorder here; session.report auto-attaches the
# summary of whatever recorder is current in this process.

_current: Optional[StepRecorder] = None
_current_lock = threading.Lock()


def set_current_recorder(recorder: Optional[StepRecorder]) -> None:
    global _current
    with _current_lock:
        _current = recorder


def current_recorder() -> Optional[StepRecorder]:
    return _current


def get_or_create_recorder(**kwargs) -> StepRecorder:
    global _current
    with _current_lock:
        if _current is None:
            _current = StepRecorder(**kwargs)
        return _current


def auto_report_metrics() -> Dict[str, Any]:
    """Telemetry keys merged into every session.report() (namespaced so they
    never collide with user metrics)."""
    rec = current_recorder()
    if rec is None:
        return {}
    return {f"telemetry/{k}": v for k, v in rec.summary().items()}


_REPORT_GAUGES = {
    "telemetry/goodput": "ray_tpu_train_goodput_ratio",
    "telemetry/tokens_per_s": "ray_tpu_train_tokens_per_second",
    "telemetry/examples_per_s": "ray_tpu_train_examples_per_second",
    "telemetry/mfu": "ray_tpu_train_mfu_ratio",
    "telemetry/compile_time_s": "ray_tpu_train_compile_seconds",
    "telemetry/step_time_s": "ray_tpu_train_last_step_seconds",
    "telemetry/hbm_bytes_in_use": "ray_tpu_train_hbm_bytes_in_use",
}
_report_gauge_objs: Dict[str, Any] = {}


def publish_report_summary(metrics: Dict[str, Any], run_name: str = ""):
    """Re-publish a report's auto-attached telemetry/* keys as gauges from
    the CALLING process (trainer driver). The GCS drops a dead worker's
    gauges (stale last-writes poison aggregations), so without this the
    run's final throughput/goodput/MFU would vanish from /metrics the
    moment the worker group shuts down; the driver outlives the run."""
    try:
        from ray_tpu.util.metrics import Gauge

        for key, name in _REPORT_GAUGES.items():
            value = metrics.get(key)
            if not isinstance(value, (int, float)):
                continue
            g = _report_gauge_objs.get(name)
            if g is None:
                g = _report_gauge_objs[name] = Gauge(
                    name, "driver-side rolling train telemetry",
                    tag_keys=("run",))
            g.set(float(value), tags={"run": run_name})
    except Exception:
        pass  # telemetry must never fail a report round
