"""Flight recorder, stall watchdog, incidents, and `ray-tpu debug` forensics.

Contracts under test:
  - the ring buffer keeps the TAIL under overflow, in order, cheaply
    (tier-1 overhead guard: always-on recording must stay <2% of
    small-task throughput — bounded here per-event);
  - an artificially stuck task raises a GCS incident with captured stacks;
  - `debug dump` on a 2-node cluster yields one archive containing
    flight-recorder events from BOTH raylets plus state listings/stacks;
  - a SIGKILLed actor's ActorDiedError carries the worker's last
    flight-recorder events (periodic flush → raylet tail attach);
  - timeline: a terminal task event whose RUNNING was dropped renders as
    a Chrome instant event instead of vanishing;
  - state API: `limit` applies server-side; list_tasks has a
    detail=False fast path.
"""

import json
import os
import signal
import time

import pytest

from ray_tpu._private import flight_recorder as fr


# ------------------------------------------------------------- ring buffer


@pytest.mark.fast
def test_ring_overflow_keeps_ordered_tail():
    r = fr.FlightRecorder(64)
    for i in range(1000):
        r.record("task.running", i.to_bytes(4, "big"), f"t{i}")
    snap = r.snapshot()
    assert len(snap) == 64
    seqs = [t[0] for t in snap]
    assert seqs == sorted(seqs)  # append order preserved
    # the TAIL survives: the newest event is the last recorded one
    assert snap[-1][4] == "t999"
    assert snap[0][4] == f"t{1000 - 64}"
    dumped = r.dump()
    assert dumped[-1]["event"] == "task.running"
    assert dumped[-1]["a"] == (999).to_bytes(4, "big").hex()


@pytest.mark.fast
def test_ring_dump_limit_and_formatting():
    r = fr.FlightRecorder(128)
    r.record("obj.put", b"\xab\xcd", 4096)
    r.record("actor.state", b"\x01", "ALIVE")
    out = r.dump(limit=1)
    assert len(out) == 1 and out[0]["event"] == "actor.state"
    full = r.dump()
    assert full[0]["a"] == "abcd" and full[0]["b"] == 4096


@pytest.mark.fast
def test_ring_flush_to_file_is_incremental(tmp_path):
    r = fr.FlightRecorder(32)
    path = str(tmp_path / "flight.jsonl")
    r.record("task.pending", b"\x01", "a")
    assert r.flush_to_file(path) == 1
    r.record("task.running", b"\x01", "a")
    r.record("task.finished", b"\x01", "a")
    assert r.flush_to_file(path) == 2  # only the new events append
    assert r.flush_to_file(path) == 0  # idempotent when nothing new
    lines = [json.loads(l) for l in open(path)]
    assert [l["event"] for l in lines] == [
        "task.pending", "task.running", "task.finished"]
    tail = fr.read_tail_file(path, limit=2)
    assert [e["event"] for e in tail] == ["task.running", "task.finished"]
    assert "task.finished" in fr.format_tail(tail)


@pytest.mark.fast
def test_recorder_overhead_smoke():
    """Tier-1 guard for the always-on recorder: bound the per-event cost.

    Budget: the control plane runs ~1k-10k small tasks/s with ~6 recorded
    events per task; <2% of a 1 ms task is 20 µs, i.e. ~3.3 µs/event. The
    ring append is an order of magnitude under that; trip only on a
    catastrophic regression (a lock, formatting on the hot path...).
    """
    r = fr.FlightRecorder(4096)
    tid = b"\x01" * 16
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        r.record("task.running", tid, "bench")
    per_event = (time.perf_counter() - t0) / n
    assert per_event < 3.3e-6, (
        f"flight-recorder append costs {per_event * 1e6:.2f} µs/event — "
        "over the <2%-of-small-task budget")


# ----------------------------------------------------------- runtime events


def test_runtime_populates_ring_and_dump_rpc(ray_start_regular):
    import ray_tpu
    from ray_tpu._private import worker as worker_mod

    @ray_tpu.remote
    def f(x):
        return x + 1

    assert ray_tpu.get([f.remote(i) for i in range(3)]) == [1, 2, 3]
    ray_tpu.put(b"x" * (1 << 20))
    events = fr.dump()
    names = {e["event"] for e in events}
    assert "task.pending" in names and "obj.put" in names
    # the raylet's DumpFlightRecorder fans in its workers' rings
    w = worker_mod.global_worker
    node = w.gcs.get_all_node_info()[0]
    from ray_tpu.util.state import _fanout_raylets

    [(n, reply)] = _fanout_raylets(
        None, "DumpFlightRecorder", timeout=30,
        payload={"limit": 500, "include_workers": True})
    raylet_names = {e["event"] for e in reply["events"]}
    assert "lease.grant" in raylet_names or "worker.spawn" in raylet_names
    assert reply["workers"], "no worker rings collected"
    worker_names = {
        e["event"] for wrep in reply["workers"] for e in wrep["events"]
    }
    assert "task.running" in worker_names


# ------------------------------------------------------------- watchdog


def test_watchdog_fires_incident_with_stacks(monkeypatch, shutdown_only):
    """An artificially stuck task must surface as a GCS incident with
    captured stacks while it is still hanging."""
    monkeypatch.setenv("RTPU_watchdog_interval_s", "0.5")
    monkeypatch.setenv("RTPU_watchdog_task_timeout_s", "2")
    import ray_tpu
    from ray_tpu.util import state

    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    def stuck():
        time.sleep(120)

    ref = stuck.remote()
    # Both watchdogs (driver + raylet) fire for this hang; under load the
    # driver one can trip while the task is still queued (no lease → no
    # remote stack yet), so poll until SOME incident's stacks show the
    # stuck task's frame — the raylet-side probe guarantees one appears
    # once the task is actually executing.
    deadline = time.time() + 60
    incidents = []

    def all_stacks():
        return [s for i in incidents for s in (i.get("stacks") or [])]

    while time.time() < deadline:
        incidents = state.list_incidents(detail=True)
        if any("stuck" in (s.get("folded") or "") for s in all_stacks()):
            break
        time.sleep(0.5)
    assert incidents, "watchdog never published an incident"
    kinds = {i["kind"] for i in incidents}
    assert kinds & {"stuck_task", "no_progress"}
    assert all(i["status"] == "open" for i in incidents)
    assert any(i.get("ring") for i in incidents), \
        "no incident carries a flight-recorder snapshot"
    stacks = all_stacks()
    assert any(s.get("folded") for s in stacks), f"no stacks captured: {stacks}"
    # the hang itself is visible: the stuck task's frame appears in a
    # captured stack (time.sleep is a C frame; its Python caller `stuck`
    # is what sample_stacks sees)
    assert any("stuck" in (s.get("folded") or "") for s in stacks), stacks
    # `ray-tpu status`-style count sees it without fetching detail
    assert state.count_open_incidents() >= 1
    del ref


def test_watchdog_train_stall(monkeypatch, shutdown_only):
    """A StepRecorder that recorded steps and went silent raises a
    train_stall incident from the process hosting it."""
    monkeypatch.setenv("RTPU_watchdog_interval_s", "0.5")
    monkeypatch.setenv("RTPU_watchdog_step_timeout_s", "1")
    monkeypatch.setenv("RTPU_watchdog_task_timeout_s", "600")
    import ray_tpu
    from ray_tpu.train import _telemetry
    from ray_tpu.util import state

    ray_tpu.init(num_cpus=2)
    rec = _telemetry.StepRecorder(emit_metrics=False, emit_spans=False)
    _telemetry.set_current_recorder(rec)
    try:
        rec.record_step(0.01, tokens=128)
        # ... then silence: the driver-side watchdog hosts this recorder
        deadline = time.time() + 30
        found = []
        while time.time() < deadline:
            found = [i for i in state.list_incidents()
                     if i["kind"] == "train_stall"]
            if found:
                break
            time.sleep(0.5)
        assert found, "train_stall incident never published"
        assert "silent" in found[0]["detail"]
    finally:
        _telemetry.set_current_recorder(None)


# ------------------------------------------------- dead-actor forensics


def test_sigkilled_actor_error_carries_flight_tail(shutdown_only):
    import ray_tpu
    from ray_tpu.exceptions import ActorDiedError
    from ray_tpu.util import state

    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    class A:
        def ping(self):
            return os.getpid()

    a = A.remote()
    pid = ray_tpu.get(a.ping.remote())
    # generate some flight events in the actor worker, then let the 1s
    # flush cadence persist them before the un-catchable SIGKILL
    for _ in range(3):
        ray_tpu.get(a.ping.remote())
    time.sleep(2.5)
    os.kill(pid, signal.SIGKILL)
    # the raylet reaps the worker, reads its flight file tail, and the
    # death cause (with the tail) reaches the next caller's error
    deadline = time.time() + 40
    msg = ""
    while time.time() < deadline:
        try:
            ray_tpu.get(a.ping.remote(), timeout=5)
        except ActorDiedError as e:
            msg = str(e)
            if "flight-recorder" in msg:
                break
        except Exception:
            pass
        time.sleep(0.5)
    assert "flight-recorder" in msg, f"no flight tail in: {msg!r}"
    assert "task." in msg  # the tail shows actual task events
    # the failure is also on the state API
    dead = state.list_actors(filters=[("state", "=", "DEAD")])
    assert any("flight-recorder" in (d.get("death_cause") or "")
               for d in dead)


# --------------------------------------------------- debug dump (2 nodes)


def test_debug_dump_two_node_archive(tmp_path, shutdown_only):
    import zipfile

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.scripts import collect_debug_dump, cmd_debug

    cluster = Cluster(
        initialize_head=True,
        head_node_args={"resources": {"CPU": 2, "n1": 1}},
    )
    cluster.add_node(resources={"CPU": 2, "n2": 1}, node_name="n2")
    try:
        cluster.wait_for_nodes()
        ray_tpu.init(address=cluster.address)

        @ray_tpu.remote
        def where():
            return os.getpid()

        # touch BOTH nodes so both raylets have flight events
        ray_tpu.get([
            where.options(resources={"n1": 1}).remote(),
            where.options(resources={"n2": 1}).remote(),
        ])
        files = collect_debug_dump(cluster.address, ring_limit=500,
                                   stack_duration=0.2)
        flight = {k: v for k, v in files.items()
                  if k.startswith("flight/node_")}
        assert len(flight) == 2, f"expected 2 per-node rings, got {list(files)}"
        for name, text in flight.items():
            payload = json.loads(text)
            assert payload["raylet_events"], f"{name} has an empty raylet ring"
            events = {e["event"] for e in payload["raylet_events"]}
            assert events & {"lease.grant", "worker.spawn", "lease.return"}
        assert "incidents.json" in files
        assert "state/tasks.json" in files and "state/nodes.json" in files
        assert len(json.loads(files["state/nodes.json"])) == 2
        stacks = [k for k in files if k.startswith("stacks/")]
        assert len(stacks) == 2
        assert any("==" in files[k] for k in stacks), "no worker stacks sampled"

        # the CLI wraps the same collection into one zip archive
        class Args:
            debug_cmd = "dump"
            address = cluster.address
            output = str(tmp_path / "dump.zip")
            ring_limit = 500

        cmd_debug(Args())
        with zipfile.ZipFile(Args.output) as z:
            names = z.namelist()
            assert sum(1 for n in names
                       if n.startswith("flight/node_")) == 2
            assert "flight/gcs.json" in names  # the control plane's ring
            assert "incidents.json" in names
    finally:
        import ray_tpu as _rt

        if _rt.is_initialized():
            _rt.shutdown()
        cluster.shutdown()


# ----------------------------------------------------- timeline satellite


@pytest.mark.fast
def test_timeline_terminal_without_running_renders_instant():
    from ray_tpu._private.timeline import chrome_trace_events

    events = [
        # RUNNING dropped (ring overflow / flush loss): only the terminal
        # event survived
        {"task_id": "t1", "name": "lost", "state": "FINISHED", "ts": 10.0,
         "node_id": "n", "worker_id": "w", "job_id": "j"},
        # healthy pair still renders the X duration event
        {"task_id": "t2", "name": "ok", "state": "RUNNING", "ts": 11.0,
         "node_id": "n", "worker_id": "w", "job_id": "j"},
        {"task_id": "t2", "name": "ok", "state": "FINISHED", "ts": 12.0,
         "node_id": "n", "worker_id": "w", "job_id": "j"},
    ]
    out = chrome_trace_events(events)
    instants = [e for e in out if e["ph"] == "i" and "lost" in e["name"]]
    assert len(instants) == 1
    assert instants[0]["args"]["state"] == "FINISHED"
    assert "missing" in instants[0]["args"]["note"]
    assert any(e["ph"] == "X" and e["name"] == "ok" for e in out)
    # a FAILED terminal without RUNNING is visible too
    out2 = chrome_trace_events([
        {"task_id": "t3", "name": "boom", "state": "FAILED", "ts": 1.0,
         "node_id": "n", "worker_id": "w", "job_id": "j", "error": "x"},
    ])
    assert any(e["ph"] == "i" and "boom" in e["name"] for e in out2)


# ------------------------------------------------------ state satellites


def test_list_tasks_server_side_limit_and_detail(ray_start_regular):
    import ray_tpu
    from ray_tpu.util import state

    @ray_tpu.remote
    def tick(i):
        return i

    ray_tpu.get([tick.remote(i) for i in range(6)])
    deadline = time.time() + 15
    tasks = []
    while time.time() < deadline:
        tasks = state.list_tasks()
        if sum(1 for t in tasks if t["state"] == "FINISHED") >= 6:
            break
        time.sleep(0.3)
    assert len(tasks) >= 6
    # server-side limit: exactly N rows cross the wire
    assert len(state.list_tasks(limit=2)) == 2
    # detail=False fast path: identity/state only
    lite = state.list_tasks(detail=False)
    assert lite and "error_message" not in lite[0]
    assert {"task_id", "name", "state"} <= set(lite[0])
    # detail rows keep attribution
    full = state.list_tasks()
    assert "error_message" in full[0] and "worker_id" in full[0]
    # other listings accept server-side limits too
    assert len(state.list_nodes(limit=1)) == 1
