"""Unified profiling plane: cluster-wide CPU sampling + merged Perfetto
timeline + automatic slow-step capture.

Contracts under test:
  - the sampling profiler attributes a known hot loop correctly and its
    timestamped samples stay inside the capture window;
  - an idle (never-started) profiler costs nothing on the small-task hot
    path — nothing consults it, and probing it is sub-microsecond
    (tier-1 overhead bound);
  - `ray-tpu profile` on a 2-node cluster produces ONE Perfetto-loadable
    JSON containing CPU samples from BOTH nodes' workers time-aligned
    with task/span events (shared wall-clock µs axis);
  - a train step slower than profile_slow_step_factor x the trailing
    median raises a slow_step incident carrying a capture path whose file
    is a loadable merged trace;
  - merged-trace alignment: device-trace links, task flow events
    (SUBMITTED -> RUNNING), and CPU slices share the clock;
  - the device-trace window produces + registers a jax.profiler trace dir
    (forced on CPU);
  - timeline filters (job_id server-side, trace_id) and the trace_ctx
    enabled bit (fresh/stale workers record spans immediately);
  - incident auto-analysis extracts top stacks / compile share / scheduling
    delay from an attached merged-profile capture and writes a
    human-readable summary into the incident record.
"""

import json
import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private import perf_analysis as pa
from ray_tpu._private import sampling_profiler as sp


# ------------------------------------------------------------ the sampler


def _burn_loop(stop, tag="x"):
    x = 0
    while not stop.is_set():
        x += sum(i * i for i in range(100))
    return x


@pytest.mark.fast
def test_sampler_accuracy_on_hot_loop():
    stop = threading.Event()
    t = threading.Thread(target=_burn_loop, args=(stop,), name="hotloop")
    t.start()
    try:
        prof = sp.SamplingProfiler(hz=200, role="test")
        t0 = time.time()
        prof.start(0.6)
        result = prof.collect()
    finally:
        stop.set()
        t.join()
    assert result["role"] == "test" and result["pid"] == os.getpid()
    assert not prof.running
    # the hot loop dominates the hotloop thread's samples
    folded = sp.fold_samples(result)
    assert folded, "no samples at all"
    burn = sum(c for s, c in folded.items() if "_burn_loop" in s)
    hot_thread = sum(c for s, c in folded.items() if s.startswith("hotloop;"))
    assert hot_thread > 0.25 * 0.6 * 200, folded  # ≥25% of expected ticks
    assert burn >= 0.9 * hot_thread, folded
    # timestamped samples stay inside the capture window
    for dt, ti, si in result["samples"]:
        assert -0.01 <= dt <= (result["t1"] - result["t0"]) + 0.25
        assert 0 <= ti < len(result["threads"])
        assert 0 <= si < len(result["stacks"])
    assert result["t0"] >= t0 - 0.1 and result["t1"] >= result["t0"]


@pytest.mark.fast
def test_sampler_single_capture_per_process_and_truncation():
    # only one concurrent capture per process
    sp.start_profile(0.3, hz=50)
    with pytest.raises(RuntimeError):
        sp.start_profile(0.3, hz=50)
    first = sp.collect_profile()
    assert first is not None
    assert sp.collect_profile() is None  # cleared on read
    # sample cap: aggregation keeps going, the timeline list stops
    prof = sp.SamplingProfiler(hz=500, max_samples=5, include_idle=True)
    prof.start(0.3)
    r = prof.collect()
    assert len(r["samples"]) <= 5
    if r["truncated"]:
        assert len(r["samples"]) == 5


@pytest.mark.fast
def test_idle_profiler_costs_nothing_on_hot_path():
    """Tier-1 overhead bound. The plane is pull-only: no task/put/step hot
    path consults the profiler, so the idle cost is (a) no resident
    sampler thread and (b) the is_active probe itself being nanoseconds —
    bounded here so a regression that adds per-event work trips loudly."""
    assert not sp.is_active()
    assert not any(
        th.name.startswith("rtpu-sampler") for th in threading.enumerate())
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        sp.is_active()
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 2e-6, (
        f"idle profiler probe costs {per_call * 1e6:.2f} µs")


# ---------------------------------------------------- merged-trace builder


@pytest.mark.fast
def test_merged_trace_alignment_and_links():
    from ray_tpu._private.timeline import merged_profile_trace

    t0 = 5000.0
    bundle = {
        "t0": t0, "duration": 1.0, "hz": 100.0, "errors": [], "gcs": None,
        "drivers": [],
        "nodes": [{
            "node_id": "ab" * 20,
            "profiles": [{
                "t0": t0, "t1": t0 + 1, "hz": 100.0, "pid": 7,
                "role": "worker", "threads": ["MainThread"],
                "stacks": ["f (m.py:1);g (m.py:9)"],
                "samples": [[0.10, 0, 0], [0.11, 0, 0], [0.12, 0, 0]],
                "truncated": False,
            }],
        }],
    }
    task_events = [
        {"task_id": "t1", "name": "work", "state": "SUBMITTED",
         "ts": t0 + 0.05, "node_id": "dr", "worker_id": "w0", "job_id": "j"},
        {"task_id": "t1", "name": "work", "state": "RUNNING",
         "ts": t0 + 0.10, "node_id": "ab" * 4, "worker_id": "w1",
         "job_id": "j"},
        {"task_id": "t1", "name": "work", "state": "FINISHED",
         "ts": t0 + 0.50, "node_id": "ab" * 4, "worker_id": "w1",
         "job_id": "j"},
    ]
    device = [{"path": "/tmp/dtrace", "steps": 3, "time": t0 + 0.2,
               "host": "h1"}]
    trace = merged_profile_trace(bundle, task_events, device)
    evs = trace["traceEvents"]
    # device trace is linked, not lost
    link = [e for e in evs if e.get("cat") == "device_trace"]
    assert len(link) == 1 and link[0]["args"]["path"] == "/tmp/dtrace"
    assert trace["metadata"]["device_traces"][0]["path"] == "/tmp/dtrace"
    # CPU slices and task X events share the wall-clock µs axis
    cpu = [e for e in evs if e.get("cat") == "cpu_sample"]
    task = [e for e in evs if e.get("cat") == "task" and e["ph"] == "X"]
    assert len(cpu) == 1 and len(task) == 1
    assert cpu[0]["ts"] == pytest.approx((t0 + 0.10) * 1e6, abs=1)
    assert task[0]["ts"] == pytest.approx((t0 + 0.10) * 1e6, abs=1)
    # consecutive same-stack samples collapsed into one slice
    assert cpu[0]["args"]["samples"] == 3
    # lanes group under the same node pid as the task events
    assert cpu[0]["pid"] == f"node:{'ab' * 4}" == task[0]["pid"]
    # flow events draw the SUBMITTED -> RUNNING causality edge
    flows = sorted((e for e in evs if e.get("cat") == "task_flow"),
                   key=lambda e: e["ts"])
    assert [f["ph"] for f in flows] == ["s", "f"]
    assert flows[0]["id"] == flows[1]["id"] == "t1"
    assert flows[0]["ts"] == pytest.approx((t0 + 0.05) * 1e6, abs=1)
    assert flows[1]["ts"] == pytest.approx((t0 + 0.10) * 1e6, abs=1)
    json.dumps(trace)  # serializes cleanly


# -------------------------------------------- cluster-wide capture (2 nodes)


def test_cluster_profile_two_nodes(tmp_path, shutdown_only):
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu import scripts

    cluster = Cluster(
        initialize_head=True,
        head_node_args={"resources": {"CPU": 2, "n1": 1}},
    )
    cluster.add_node(resources={"CPU": 2, "n2": 1}, node_name="n2")
    try:
        cluster.wait_for_nodes()
        ray_tpu.init(address=cluster.address)

        @ray_tpu.remote
        class Burner:
            def ping(self):
                return os.getpid()

            def spin_hard(self, s):
                t0 = time.time()
                x = 0
                while time.time() - t0 < s:
                    x += sum(i * i for i in range(200))
                return x

        burners = [
            Burner.options(resources={"n1": 1}).remote(),
            Burner.options(resources={"n2": 1}).remote(),
        ]
        ray_tpu.get([b.ping.remote() for b in burners])  # both workers up
        refs = [b.spin_hard.remote(12.0) for b in burners]
        time.sleep(0.3)

        out = tmp_path / "prof.json"
        scripts.main([
            "profile", "--address", cluster.address,
            "--duration", "1.2", "--hz", "150", "-o", str(out),
        ])
        trace = json.loads(out.read_text())
        evs = trace["traceEvents"]
        cpu = [e for e in evs if e.get("cat") == "cpu_sample"]
        # CPU samples from BOTH nodes' workers in one file
        worker_nodes = {
            e["pid"] for e in cpu
            if e["args"]["process"].startswith("worker:")
        }
        assert len(worker_nodes) == 2, worker_nodes
        assert any("spin_hard" in (e["args"].get("stack") or "")
                   for e in cpu), "burner frames missing"
        # ...time-aligned with task/span events: same wall-clock µs axis
        task_ts = [e["ts"] for e in evs if e.get("cat") == "task"]
        cpu_ts = [e["ts"] for e in cpu]
        assert task_ts, "no task events in merged trace"
        assert abs(min(cpu_ts) - max(task_ts)) < 300e6  # same clock epoch
        # the capture window itself brackets every CPU slice
        t0us = trace["metadata"]["capture_t0"] * 1e6
        dur_us = (trace["metadata"]["capture_duration_s"] + 2.0) * 1e6
        assert all(t0us - 1e6 <= t <= t0us + dur_us for t in cpu_ts)
        # --flame emits cluster-folded stacks with per-process attribution
        flame = tmp_path / "prof.folded"
        scripts.main([
            "profile", "--address", cluster.address,
            "--duration", "0.5", "--flame", "-o", str(flame),
        ])
        folded = flame.read_text()
        assert "spin_hard" in folded
        assert any(line.startswith("node:") for line in folded.splitlines())
        ray_tpu.get(refs)
    finally:
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        cluster.shutdown()


# ------------------------------------------------- automatic slow-step capture


def test_slow_step_triggers_incident_with_profile(monkeypatch, shutdown_only):
    monkeypatch.setenv("RTPU_watchdog_interval_s", "0.5")
    monkeypatch.setenv("RTPU_watchdog_task_timeout_s", "600")
    monkeypatch.setenv("RTPU_watchdog_step_timeout_s", "600")
    monkeypatch.setenv("RTPU_profile_slow_step_factor", "2")
    monkeypatch.setenv("RTPU_profile_trigger_duration_s", "0.5")
    from ray_tpu.train import _telemetry
    from ray_tpu.util import state

    ray_tpu.init(num_cpus=2)
    rec = _telemetry.StepRecorder(emit_metrics=False, emit_spans=False)
    _telemetry.set_current_recorder(rec)
    try:
        for _ in range(10):
            rec.record_step(0.01, tokens=64)
        rec.record_step(0.5, tokens=64)  # 50x the trailing median
        deadline = time.time() + 40
        found = []
        while time.time() < deadline:
            found = [i for i in state.list_incidents()
                     if i["kind"] == "slow_step"]
            if found:
                break
            time.sleep(0.3)
        assert found, "slow_step incident never published"
        inc = found[0]
        assert "median" in inc["detail"]
        # why, as far as the recorder could see: a caller that timed the step
        # itself had no watcher beside it, so the cause is not known
        assert "cause unknown" in inc["detail"]
        assert inc["slow_step"]["cause"] == "unknown" and inc["slow_step"]["next_done"] == -1.0
        assert inc["slow_step"]["host_gap_s"] == inc["slow_step"]["gc_pause_s"] == 0.0
        # the incident carries the capture path, and the capture is a
        # loadable merged trace with CPU samples
        path = inc.get("profile_path")
        assert path and os.path.isfile(path), inc
        trace = json.load(open(path))
        assert any(e.get("cat") == "cpu_sample"
                   for e in trace["traceEvents"])
        # the capture is registered: dashboard ?latest=1 lists it
        from ray_tpu import api
        from ray_tpu.dashboard import start_dashboard
        import urllib.request

        _, port = start_dashboard(api._local_node.gcs_address)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/profile?latest=1", timeout=30
        ) as resp:
            latest = json.loads(resp.read())
        assert any(c["path"] == path for c in latest["captures"])
    finally:
        _telemetry.set_current_recorder(None)


def test_slow_step_detection_median_and_cooldown():
    """Pure-recorder check: the outlier is judged against (and does not
    dilute) the trailing median; pop clears the flag."""
    from ray_tpu.train import _telemetry

    rec = _telemetry.StepRecorder(emit_metrics=False, emit_spans=False)
    rec._slow_factor = 3.0
    for _ in range(8):
        rec.record_step(0.010)
    assert rec.pop_slow_step() is None  # steady state: no flag
    rec.record_step(0.200)
    slow = rec.pop_slow_step()
    assert slow is not None
    assert slow["ratio"] == pytest.approx(20.0, rel=0.01)
    assert slow["median_s"] == pytest.approx(0.010, rel=0.01)
    assert rec.pop_slow_step() is None  # cleared on read
    # compile steps never count as slow steps
    rec.record_step(5.0, compile_step=True)
    assert rec.pop_slow_step() is None


# ------------------------------------------------------ device-trace window


def test_device_trace_window_forced_on_cpu(monkeypatch, tmp_path,
                                           shutdown_only):
    monkeypatch.setenv("RTPU_device_trace_force", "1")
    from ray_tpu._private import profiling
    from ray_tpu._private.worker import get_global_worker
    from ray_tpu.train import _telemetry

    ray_tpu.init(num_cpus=2)
    ctl = _telemetry.DeviceTraceController()
    assert ctl.supported()
    trace_dir = str(tmp_path / "dtrace")
    ctl.request(num_steps=2, trace_dir=trace_dir)
    import jax
    import jax.numpy as jnp

    for _ in range(3):  # window covers exactly 2 of these
        ctl.on_step_begin()
        out = jax.block_until_ready(jnp.ones((32, 32)) @ jnp.ones((32, 32)))
        ctl.on_step_end(out)
    # the jax profiler wrote an xplane dir
    found = []
    for root, _dirs, files in os.walk(trace_dir):
        found += [f for f in files if f.endswith(".xplane.pb")]
    assert found, f"no xplane files under {trace_dir}"
    # ...and it is registered with the GCS for the merged timeline
    regs = profiling.list_registered(get_global_worker().gcs, "device_trace")
    assert any(r["path"] == trace_dir for r in regs), regs


def test_device_trace_noop_without_force(shutdown_only):
    """On CPU (no force), arming is a silent no-op — the training loop
    must never pay for an unusable device trace."""
    from ray_tpu.train import _telemetry

    assert os.environ.get("RTPU_device_trace_force") != "1"
    ctl = _telemetry.DeviceTraceController()
    ctl.request(num_steps=1)
    ctl.on_step_begin()
    assert not ctl._active
    ctl.on_step_end()  # no crash, nothing started


# --------------------------------------------- timeline filters + tracing bit


def test_timeline_job_and_trace_filters(ray_start_regular):
    from ray_tpu._private.worker import get_global_worker
    from ray_tpu.util import tracing

    @ray_tpu.remote
    def tick(i):
        return i

    ray_tpu.get([tick.remote(i) for i in range(4)])
    tracing.enable()
    try:
        with tracing.span("filter-root") as root:
            pass
    finally:
        tracing.disable()
    my_job = get_global_worker().job_id.hex()
    deadline = time.time() + 20
    events = []
    while time.time() < deadline:
        events = ray_tpu.timeline(job_id=my_job)
        if (sum(1 for e in events if e.get("ph") == "X"
                and e.get("cat") == "task") >= 4
                and any(e.get("cat") == "span" for e in events)):
            break
        time.sleep(0.3)
    assert sum(1 for e in events if e.get("cat") == "task") >= 4
    # flow events connect submit to run for the finished tasks
    flows = [e for e in events if e.get("cat") == "task_flow"]
    assert {f["ph"] for f in flows} >= {"s", "f"}
    # a bogus job id filters everything server-side
    assert ray_tpu.timeline(job_id="ff" * 4) == []
    # trace_id keeps only that trace's spans
    spans = [e for e in events if e.get("cat") == "span"]
    tid = spans[0]["args"]["trace_id"]
    only = ray_tpu.timeline(trace_id=tid)
    assert only and all(e["args"]["trace_id"] == tid for e in only)


def test_trace_ctx_enabled_bit(ray_start_regular):
    """The spec-borne enabled bit defeats a stale disabled cache: spans in
    a worker that cached 'tracing off' still record once a traced spec
    arrives (previously dropped for up to the 5s KV TTL)."""
    from ray_tpu.util import tracing

    tracing.enable()
    try:
        ctx = tracing.context_for_spec()
        assert ctx is not None and ctx["enabled"] is True

        @ray_tpu.remote
        def stale_then_span():
            from ray_tpu.util import tracing as t

            # the executor restored this task's ctx and marked enabled
            # BEFORE user code ran — even with the KV unreachable a span
            # records immediately
            assert t.is_enabled()
            # the wire-only bit is stripped from the restored context
            assert "enabled" not in (t.current_context() or {})
            with t.span("immediate") as s:
                return s is not None

        assert ray_tpu.get(stale_then_span.remote())
        # stale-disabled cache + spec bit == enabled again (executor path)
        tracing._local_enabled, tracing._checked_at = False, time.time()
        assert not tracing.is_enabled()
        tracing._mark_enabled()
        assert tracing.is_enabled()
    finally:
        tracing.disable()


# ------------------------------------------------------ incident analysis


class _StubGcs:
    def __init__(self):
        self.calls = []

    def call(self, method, payload, timeout=None):
        self.calls.append((method, payload))
        return {"ok": True}

    def get_all_node_info(self):
        return []


class _StubCore:
    mode = "driver"
    node_id = None
    is_shutdown = False
    worker_id = b"\x01" * 16
    tasks_completed = 0
    _pending_tasks = {}
    session_dir = ""

    def __init__(self):
        self.gcs = _StubGcs()


def _synthetic_trace():
    node = {"pid": "node:aa", "tid": "cpu:worker:1:MainThread"}
    return {"traceEvents": [
        {"cat": "cpu_sample", "ph": "X", "ts": 0.0, "dur": 600_000.0,
         "name": "compile",
         "args": {"stack": "MainThread;train;jax;pxla;backend_compile",
                  "samples": 60}, **node},
        {"cat": "cpu_sample", "ph": "X", "ts": 0.0, "dur": 400_000.0,
         "name": "read_batch",
         "args": {"stack": "MainThread;input;read_batch", "samples": 40},
         **node},
        {"cat": "span", "ph": "X", "ts": 0.0, "dur": 500_000.0,
         "name": "train_step.compile", **node},
        {"cat": "span", "ph": "X", "ts": 500_000.0, "dur": 500_000.0,
         "name": "train_step", **node},
        {"cat": "task_flow", "ph": "s", "id": "t1", "ts": 0.0, **node},
        {"cat": "task_flow", "ph": "f", "id": "t1", "ts": 250_000.0, **node},
        {"cat": "task", "ph": "X", "ts": 250_000.0, "dur": 750_000.0,
         "name": "f", **node},
    ]}


@pytest.mark.fast
def test_analyze_trace_extracts_shares():
    a = pa.analyze_trace(_synthetic_trace())
    assert a["cpu_seconds"] == pytest.approx(1.0)
    assert a["top_stacks"][0]["stack"].endswith("backend_compile")
    assert a["top_stacks"][0]["share"] == pytest.approx(0.6)
    assert a["compile_share"] == pytest.approx(0.6)
    assert a["compile_span_share"] == pytest.approx(0.5)
    assert a["sched_delay"]["count"] == 1
    assert a["sched_delay"]["max_ms"] == pytest.approx(250.0)
    assert a["sched_delay"]["share"] == pytest.approx(0.25)


@pytest.mark.fast
def test_attach_analysis_writes_summary_into_incident(tmp_path):
    path = tmp_path / "capture.json"
    path.write_text(json.dumps(_synthetic_trace()))
    inc = {"kind": "jit_cache_miss_storm", "profile_path": str(path)}
    assert pa.attach_analysis(inc)
    summary = inc["analysis"]["summary"]
    assert "compile" in summary and "scheduling delay" in summary
    assert "recompilation" in summary  # storm-specific hint
    assert inc["analysis"]["top_stacks"]
    # no capture / unreadable capture leaves the incident untouched
    assert not pa.attach_analysis({"kind": "slow_step"})
    assert not pa.attach_analysis(
        {"kind": "slow_step", "profile_path": str(tmp_path / "gone.json")})


def test_watchdog_incident_carries_analysis(monkeypatch, tmp_path):
    """The full wiring: the watchdog's publish path attaches the analysis
    derived from the incident's capture before it reaches the GCS."""
    monkeypatch.setenv("RTPU_profile_on_incident", "0")
    from ray_tpu._private.watchdog import StallWatchdog

    path = tmp_path / "capture.json"
    path.write_text(json.dumps(_synthetic_trace()))
    core = _StubCore()
    wd = StallWatchdog(core)
    incident = {"kind": "slow_step", "detail": "x", "status": "open",
                "profile_path": str(path)}
    wd._publish(incident, b"")
    sent = [p["incident"] for m, p in core.gcs.calls
            if m == "ReportIncident"][0]
    assert "analysis" in sent
    assert "compile" in sent["analysis"]["summary"]
