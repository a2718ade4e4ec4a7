"""Memory observability plane: object ownership ledger, leak detection,
OOM forensics, and the `ray-tpu memory` surfaces.

Contracts under test:
  - the ReferenceCounter ledger records size/callsite/owner-task/pin-state
    per owned ref, pull-only, and a write makes a bounded count of calls
    whatever the ledger holds;
  - `state.memory_report` joins every raylet's plasma/pin/spill tables
    with worker+driver ownership ledgers, and `memory_rollup` folds it
    per job/actor/node unifying plasma bytes, RSS and HBM;
  - a seeded leak (pinned primary whose owner ref was dropped without the
    free path running) raises exactly ONE `object_leak` incident with
    job/callsite attribution, after the two-sweep cross-check;
  - a SIGKILLed actor's death report carries its final memory snapshot
    (top holders), via the periodic on-disk snapshot the raylet reads;
  - `ray-tpu memory` / `--leaks` / `status` / `timeline` object instants
    render from the same aggregation path (tier-1 CLI smoke).
"""

import contextlib
import io
import os
import signal
import sys
import time
import types

import pytest

from ray_tpu._private import memory_report as mr
from ray_tpu._private.ids import ObjectID
from ray_tpu._private.reference_counter import ReferenceCounter


# ------------------------------------------------------------- unit: ledger


@pytest.mark.fast
def test_ledger_tracks_metadata_and_frees():
    freed = []
    rc = ReferenceCounter(freed.append)
    oid = ObjectID(b"a" * 20)
    rc.add_owned(oid, size=100, callsite="user.py:7", task_id=b"t1")
    rc.add_local_ref(oid)
    rc.note_size(oid, 4096, plasma=True)
    rows = rc.ledger()
    assert len(rows) == 1
    row = rows[0]
    assert row["size"] == 4096 and row["plasma"] is True
    assert row["callsite"] == "user.py:7" and row["task_id"] == b"t1"
    assert row["age_s"] >= 0.0 and row["local_refs"] == 1
    assert rc.owned_bytes() == (4096, 4096)
    assert rc.owns_many([oid, ObjectID(b"b" * 20)]) == [True, False]
    # the free path drops the ledger entry with the ref
    rc.remove_local_ref(oid)
    assert freed == [oid]
    assert rc.ledger() == [] and rc.owned_bytes() == (0, 0)


@pytest.mark.fast
def test_ledger_limit_keeps_top_holders():
    rc = ReferenceCounter(lambda _: None)
    for i in range(10):
        rc.add_owned(ObjectID(bytes([i]) * 20), size=i * 100)
    rows = rc.ledger(limit=3)
    assert [r["size"] for r in rows] == [900, 800, 700]


def _calls_of(fn):
    """Every Python and C call `fn` makes, by name, in order."""
    seen = []

    def profile(frame, event, arg):
        if event == "call":
            seen.append(frame.f_code.co_name)
        elif event == "c_call":
            seen.append(arg.__name__)

    sys.setprofile(profile)
    try:
        fn()
    finally:
        sys.setprofile(None)
    return seen[1:-1]  # less fn's own frame and the setprofile(None) that ends it


@pytest.mark.fast
def test_ledger_overhead_bound():
    """Tier-1 guard: the ledger must not add hot-path cost beyond what
    reference_counter already pays. A write (add_owned with full metadata
    plus note_size) makes a bounded count of calls, the same calls whether
    the ledger holds one entry or two thousand: no scan, no sort, no report
    built on the write path. (The flight recorder's budget is 3.3 µs an
    event; a count of calls holds under six workers, a count of microseconds
    on a shared host's clock did not.)"""
    rc = ReferenceCounter(lambda _: None)

    def write(oid):
        rc.add_owned(oid, size=1024, callsite="task:bench", task_id=b"t")
        rc.note_size(oid, 2048, plasma=True)

    first = ObjectID(os.urandom(20))
    alone = _calls_of(lambda: write(first))
    for _ in range(2000):
        write(ObjectID(os.urandom(20)))
    assert rc.stats()["owned"] == 2001
    assert _calls_of(lambda: write(first)) == alone  # an entry written again
    new = ObjectID(os.urandom(20))
    assert _calls_of(lambda: write(new)) == alone  # a new entry beside 2,001
    assert alone[:2] == ["write", "add_owned"] and "note_size" in alone
    assert len(alone) <= 12, alone
    # pull-only: building the report does not mutate the ledger
    before = rc.stats()
    rc.ledger(limit=10)
    assert rc.stats() == before


@pytest.mark.fast
def test_callsite_capture_and_toggle(monkeypatch):
    def user_frame():
        return mr.callsite()

    site = user_frame()
    assert site.startswith("test_memory_plane.py:"), site
    monkeypatch.setenv("RTPU_memory_ledger_callsite", "0")
    assert user_frame() == ""


@pytest.mark.fast
def test_snapshot_roundtrip_and_rendering(tmp_path):
    rc = ReferenceCounter(lambda _: None)
    rc.add_owned(ObjectID(b"c" * 20), size=1 << 20, callsite="hoard.py:3")
    rc.note_size(ObjectID(b"c" * 20), 1 << 20, plasma=True)
    core = types.SimpleNamespace(
        refs=rc,
        worker_id=types.SimpleNamespace(binary=lambda: b"w" * 16),
        actor_id=b"a" * 16,
        job_id=types.SimpleNamespace(binary=lambda: b"j" * 4),
        mode="worker",
        memory_store=types.SimpleNamespace(size=lambda: 2),
        session_dir=str(tmp_path),
    )
    os.makedirs(tmp_path / "logs", exist_ok=True)
    assert mr.write_snapshot(core, top_n=5)
    snap = mr.read_snapshot(str(tmp_path), os.getpid())
    assert snap is not None
    assert snap["owned_plasma_bytes"] == 1 << 20
    assert snap["ledger"][0]["callsite"] == "hoard.py:3"
    text = mr.format_top_holders(snap)
    assert "1.0MiB" in text and "hoard.py:3" in text and "rss=" in text
    # stale snapshots are rejected when an age bound is given
    assert mr.read_snapshot(str(tmp_path), os.getpid(), max_age_s=1e-9) is None


# ------------------------------------------------------------ unit: rollups


def _synthetic_report():
    return {
        "nodes": [
            {
                "node_id": "n1",
                "plasma": {"used_bytes": 500, "capacity_bytes": 1000},
                "pinned_bytes": 300, "pinned_count": 1,
                "spilled_bytes": 0, "spilled_count": 0,
                "raylet_rss": 10, "agent_rss": 0,
                "leaks": [{"object_id": "aa", "size": 50,
                           "job_id": "j1", "actor_id": "", "node_id": "n1"}],
                "leak_candidates": 1,
                "objects": [
                    {"object_id": "o1", "size": 300, "pinned": True,
                     "spilled": False, "job_id": "j1", "actor_id": "ac1"},
                    {"object_id": "o2", "size": 200, "pinned": False,
                     "spilled": True, "job_id": "j2", "actor_id": ""},
                ],
                "workers": [
                    {"worker_id": "w" * 40, "job_id": "j1",
                     "actor_id": "ac1", "rss_bytes": 111,
                     "owned_bytes": 300, "ledger": []},
                ],
            }
        ],
        "drivers": [
            {"worker_id": "d" * 40, "job_id": "j1", "actor_id": "",
             "rss_bytes": 77, "owned_bytes": 5, "ledger": []},
        ],
        "hbm": [
            {"name": "ray_tpu_train_hbm_bytes_in_use", "value": 1000,
             "labels": {"JobId": "j1", "WorkerId": "w" * 12}},
        ],
    }


@pytest.mark.fast
def test_memory_rollup_group_bys():
    from ray_tpu.util.state import memory_rollup

    report = _synthetic_report()
    by_job = memory_rollup(report, "job")
    assert by_job["j1"]["plasma_bytes"] == 300
    assert by_job["j1"]["leaked_bytes"] == 50
    assert by_job["j1"]["rss_bytes"] == 111 + 77  # worker + driver
    assert by_job["j1"]["hbm_bytes"] == 1000
    assert by_job["j2"]["spilled_bytes"] == 200
    by_actor = memory_rollup(report, "actor")
    assert by_actor["ac1"]["plasma_bytes"] == 300
    assert by_actor["ac1"]["hbm_bytes"] == 1000  # WorkerId -> actor mapping
    assert by_actor["-"]["spilled_bytes"] == 200
    by_node = memory_rollup(report, "node")
    assert by_node["n1"]["plasma_bytes"] == 300
    assert by_node["n1"]["objects"] == 2
    assert by_node["(driver)"]["rss_bytes"] == 77
    with pytest.raises(ValueError):
        memory_rollup(report, "nope")


@pytest.mark.fast
def test_timeline_flight_instants():
    from ray_tpu._private.timeline import flight_instant_events

    events = [
        {"seq": 1, "ts": 100.0, "event": "obj.spill", "a": "ab" * 10,
         "b": 4096},
        {"seq": 2, "ts": 101.0, "event": "obj.restore", "a": "ab" * 10,
         "b": 4096},
        {"seq": 3, "ts": 102.0, "event": "obj.leak", "a": "cd" * 10,
         "b": 128},
        {"seq": 4, "ts": 103.0, "event": "lease.grant", "a": "", "b": ""},
    ]
    out = flight_instant_events("deadbeef1234", events)
    assert [e["name"] for e in out] == ["obj.spill", "obj.restore",
                                       "obj.leak"]
    for e in out:
        assert e["ph"] == "i" and e["pid"] == "node:deadbeef"
        assert e["tid"] == "object_store"
    assert out[0]["ts"] == 100.0 * 1e6
    assert out[0]["args"]["object_id"] == "ab" * 10


# --------------------------------------------------- cluster: report + CLI


def test_memory_report_rollups_and_cli_smoke(shutdown_only):
    """Tier-1 `ray-tpu memory` smoke + live rollup/attribution checks."""
    import numpy as np

    import ray_tpu
    from ray_tpu import scripts
    from ray_tpu._private import worker as worker_mod
    from ray_tpu.util import state

    ray_tpu.init(num_cpus=2)
    addr = worker_mod.global_worker.gcs_address
    job_hex = worker_mod.global_worker.job_id.hex()

    big = ray_tpu.put(np.zeros(300_000, dtype=np.uint8))  # plasma-bound

    @ray_tpu.remote
    class Holder:
        def __init__(self):
            self.refs = []

        def hoard(self):
            self.refs.append(ray_tpu.put(np.ones(200_000, dtype=np.uint8)))
            return True

    h = Holder.remote()
    assert ray_tpu.get(h.hoard.remote())

    report = state.memory_report(addr)
    assert len(report["nodes"]) == 1
    node = report["nodes"][0]
    assert node["pinned_count"] >= 2
    assert node["plasma"]["used_bytes"] >= 500_000
    # objects carry pin-meta attribution: job id + callsite
    objs = {o["object_id"]: o for o in node["objects"]}
    mine = objs[big.object_id().hex()]
    assert mine["job_id"] == job_hex
    assert mine["callsite"].startswith("test_memory_plane.py:")
    # the actor's put is attributed to the actor worker in its ledger
    actor_rows = [
        row for w in node["workers"] if w.get("actor_id")
        for row in w["ledger"] if row["plasma"]
    ]
    assert actor_rows, "actor ledger should hold its plasma put"
    # driver ledger reaches the report too
    assert any(
        row["object_id"] == big.object_id().hex()
        for d in report["drivers"] for row in d["ledger"]
    )
    # rollups: job view unifies plasma + rss; actor view splits the actor
    by_job = state.memory_rollup(report, "job")
    assert by_job[job_hex]["plasma_bytes"] >= 500_000
    assert by_job[job_hex]["rss_bytes"] > 0
    by_actor = state.memory_rollup(report, "actor")
    assert any(k not in ("-", "(driver)", "?") and v["plasma_bytes"] > 0
               for k, v in by_actor.items())

    # ---- CLI smoke: memory (all group-bys), --leaks, status ----
    class Args:
        address = addr
        group_by = "job"
        sort_by = "size"
        leaks = False

    for group in ("job", "actor", "node"):
        a = Args()
        a.group_by = group
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            scripts.cmd_memory(a)
        out = buf.getvalue()
        assert "object store" in out and f"by {group}:" in out, out
        assert "top owned objects" in out
        assert "test_memory_plane.py:" in out  # callsites surface in the CLI
    a = Args()
    a.leaks = True
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        scripts.cmd_memory(a)
    assert "no leaked objects" in buf.getvalue()
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        scripts.cmd_status(Args())
    out = buf.getvalue()
    assert "object store:" in out and "top job:" in out, out
    ray_tpu.shutdown()


def test_worker_memory_report_rpc_limit(shutdown_only):
    """The worker-side RPC caps ledger rows at the requested top-N."""
    import ray_tpu
    from ray_tpu._private import worker as worker_mod

    ray_tpu.init(num_cpus=1)
    core = worker_mod.global_worker
    refs = [ray_tpu.put(i) for i in range(8)]
    reply = core.io.run(core.handle_GetMemoryReport({"limit": 3}))
    report = reply["report"]
    assert len(report["ledger"]) == 3
    assert report["owned_refs"] >= 8
    assert report["rss_bytes"] > 0
    # CheckRefs: owned vs freed
    oid = refs[0].object_id().binary()
    reply = core.io.run(core.handle_CheckRefs(
        {"ids": [oid, b"\x00" * 20]}))
    assert reply["owned"] == [True, False]
    del refs
    ray_tpu.shutdown()


# ------------------------------------------------------ cluster: leaks


def test_leak_detector_two_node_incident(monkeypatch, shutdown_only):
    """Seeded leak on a 2-node cluster -> exactly one `object_leak`
    incident with job/callsite attribution (cooldown respected)."""
    import numpy as np

    import ray_tpu
    from ray_tpu._private import worker as worker_mod
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util import state

    monkeypatch.setenv("RTPU_memory_leak_sweep_period_s", "0.4")
    monkeypatch.setenv("RTPU_memory_leak_min_age_s", "0")
    monkeypatch.setenv("RTPU_memory_leak_cooldown_s", "300")
    cluster = Cluster(
        initialize_head=True,
        head_node_args={"resources": {"CPU": 2, "n1": 1}},
    )
    cluster.add_node(resources={"CPU": 2, "n2": 1}, node_name="n2")
    try:
        cluster.wait_for_nodes()
        ray_tpu.init(address=cluster.address)
        core = worker_mod.global_worker
        job_hex = core.job_id.hex()

        @ray_tpu.remote(resources={"n2": 1})
        def leaky():
            return np.zeros(300_000, dtype=np.uint8)

        ref = leaky.remote()
        ray_tpu.get(ref)  # materialized: pinned on node 2, owner = driver
        oid = ref.object_id()
        # seed the leak: drop the owner's ledger entry WITHOUT running the
        # free path — exactly what a lost FreeObjects / refcount bug does
        with core.refs._lock:
            assert core.refs._owned.pop(oid, None) is not None

        deadline = time.time() + 30
        incident = None
        while time.time() < deadline:
            incs = [i for i in state.list_incidents(
                cluster.address, detail=True)
                if i.get("kind") == "object_leak"]
            if incs:
                incident = incs[-1]
                break
            time.sleep(0.3)
        assert incident is not None, "no object_leak incident raised"
        leaks = incident.get("leaks") or []
        assert any(l["object_id"] == oid.hex() for l in leaks), leaks
        rec = next(l for l in leaks if l["object_id"] == oid.hex())
        assert rec["job_id"] == job_hex[: len(rec["job_id"])]
        assert rec["callsite"].startswith("task:")
        assert rec["callsite"].endswith("leaky")
        assert rec["size"] >= 300_000
        # attribution names the node that holds the primary (node 2)
        n2 = [n for n in state.list_nodes(cluster.address)
              if n["resources_total"].get("n2")]
        assert rec["node_id"] == n2[0]["node_id"]
        # exactly once: more sweeps must not re-open the same leak
        time.sleep(1.5)
        count = len([i for i in state.list_incidents(cluster.address)
                     if i.get("kind") == "object_leak"])
        assert count == 1, f"leak incident fired {count} times"
        # the leak also surfaces on the state/CLI path with attribution
        found = state.find_memory_leaks(cluster.address, sweep=False)
        assert any(l["object_id"] == oid.hex() for l in found)
        # and in the prometheus gauge's source data
        report = state.memory_report(cluster.address,
                                     include_objects=False)
        leaked_total = sum(l.get("size") or 0
                           for n in report["nodes"] for l in n["leaks"])
        assert leaked_total >= 300_000
    finally:
        import ray_tpu as _rt

        if _rt.is_initialized():
            _rt.shutdown()
        cluster.shutdown()


# ------------------------------------------------- cluster: OOM forensics


def test_sigkilled_worker_death_report_carries_memory_snapshot(
        monkeypatch, shutdown_only):
    """The periodic on-disk ledger snapshot reaches a SIGKILLed actor's
    ActorDiedError — the OOM-forensics path (the memory monitor rides the
    same attach, plus a live grab, when it does the killing)."""
    import numpy as np

    import ray_tpu
    from ray_tpu.exceptions import ActorDiedError
    from ray_tpu.util import state

    monkeypatch.setenv("RTPU_memory_snapshot_period_s", "0.5")
    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    class Hoarder:
        def __init__(self):
            self.refs = []

        def hoard(self):
            self.refs.append(
                ray_tpu.put(np.zeros(400_000, dtype=np.uint8)))
            return os.getpid()

    a = Hoarder.remote()
    pid = ray_tpu.get(a.hoard.remote())
    ray_tpu.get(a.hoard.remote())
    time.sleep(2.5)  # let the snapshot cadence persist the ledger
    os.kill(pid, signal.SIGKILL)
    deadline = time.time() + 40
    msg = ""
    while time.time() < deadline:
        try:
            ray_tpu.get(a.hoard.remote(), timeout=5)
        except ActorDiedError as e:
            msg = str(e)
            if "memory snapshot" in msg:
                break
        except Exception:
            pass
        time.sleep(0.5)
    assert "memory snapshot at death" in msg, f"no snapshot in: {msg!r}"
    assert "rss=" in msg
    assert "plasma" in msg  # the hoarded plasma objects are the top holders
    assert "test_memory_plane.py:" in msg  # with their creation callsites
    # the same text is on the state API's death record
    dead = state.list_actors(filters=[("state", "=", "DEAD")])
    assert any("memory snapshot at death" in (d.get("death_cause") or "")
               for d in dead)
    ray_tpu.shutdown()
