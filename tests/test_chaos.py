"""Chaos plane scenario suite.

Three layers (reference: _private/test_utils.py RayletKiller :1536 +
nightly chaos suites):

  1. the deterministic fault-injection plane itself (`_private/chaos.py`):
     seeded plans replay the same schedule, rules match/gate correctly,
     and the rpc/plasma injection sites actually fire;
  2. serve.llm stream failover: a replica killed mid-generation surfaces
     as a transparent resubmission (prompt + tokens-so-far) to a
     surviving replica, byte-equal to a fault-free run, with exactly one
     attributed worker_crash incident per induced kill;
  3. storm-survival scenarios (@pytest.mark.slow): replica-kill storms
     under >= 32 concurrent streams, backpressure floods and slow-client
     stalls driven by the open-loop load generator — each asserting the
     end-to-end invariants (byte-equal streams, zero leaked KV blocks,
     zero leaked plasma objects, incident counts, replica-set
     reconvergence).

The seed cases (raylet SIGKILL mid-task-stream, malformed frames,
concurrent drivers, spillback) keep running unchanged at the bottom.
"""

import asyncio
import json
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import chaos
from ray_tpu.cluster_utils import Cluster

PROMPT = [3, 1, 4, 1, 5]


def _reference_tokens(max_tokens: int, vocab: int = 97):
    """Fault-free reference: the same deterministic fake model driven by a
    local engine — the byte-equality oracle for every failover scenario."""
    from ray_tpu.serve.llm.adapters import build_adapter
    from ray_tpu.serve.llm.engine import LLMEngine, SamplingParams

    eng = LLMEngine(build_adapter("fake", {"vocab_size": vocab}),
                    num_blocks=64, block_size=4, max_batch=4)
    rid = eng.submit(PROMPT, SamplingParams(max_tokens=max_tokens))
    eng.run_until_drained()
    toks, done, reason = eng.pull(rid)
    assert done and reason == "length" and len(toks) == max_tokens
    return toks


def _poll(fn, timeout=30.0, interval=0.25):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = fn()
        if v:
            return v
        time.sleep(interval)
    return fn()


def _worker_crash_incidents():
    from ray_tpu.util import state

    return [i for i in state.list_incidents(limit=200)
            if i.get("kind") == "worker_crash"]


def _llm_integrity_all(dep: str = "llm#LLMReplica"):
    """Invariant probe on every live replica: KV refcount/free-list
    consistency + zero pinned blocks (the serve-plane leak sweep). Dead
    replicas still in the controller's set (it prunes them within one
    health window) are skipped — their blocks died with them."""
    controller = ray_tpu.get_actor("SERVE_CONTROLLER")
    names = ray_tpu.get(controller.get_replica_names.remote(dep), timeout=30)
    out = {}
    for n in names:
        try:
            a = ray_tpu.get_actor(n)
            out[n] = ray_tpu.get(a.llm_call.remote("llm_integrity", (), {}),
                                 timeout=30)
        except Exception:
            continue
    return out


def _live_replicas(dep: str = "llm#LLMReplica"):
    controller = ray_tpu.get_actor("SERVE_CONTROLLER")
    names = ray_tpu.get(controller.get_replica_names.remote(dep), timeout=30)
    live = []
    for n in names:
        try:
            ray_tpu.get_actor(n)
            live.append(n)
        except Exception:
            pass
    return live


# ------------------------------------------------ the fault-injection plane


def test_chaos_plan_seeded_schedule_replays_identically():
    """Same plan + same hit sequence => same injection schedule, twice
    (the acceptance bar for deterministic storms). Probabilistic rules
    draw from the rule's seeded RNG, never from global randomness."""
    plan = {"seed": 7, "rules": [
        {"site": "rpc.send", "action": "drop", "prob": 0.3, "count": 0,
         "every_n": 1},
        {"site": "replica.step", "replica": "0", "action": "kill",
         "after_steps": 5},
    ]}

    def run_schedule():
        chaos.load_plan(plan)
        fired = [bool(chaos.hit("rpc.send", method="X"))
                 for _ in range(200)]
        fired += [bool(chaos.hit("replica.step", replica="1"))
                  for _ in range(10)]  # non-matching replica: never
        fired += [bool(chaos.hit("replica.step", replica="0"))
                  for _ in range(10)]  # fires exactly once, at hit 6
        n = chaos.injections_total()
        chaos.clear()
        return fired, n

    a, na = run_schedule()
    b, nb = run_schedule()
    assert a == b and na == nb
    drops = a[:200]
    assert any(drops) and not all(drops)        # prob in (0, 1) behaved
    kills = a[210:]
    assert kills == [False] * 5 + [True] + [False] * 4


def test_chaos_rule_gating_after_every_count_and_match():
    chaos.load_plan({"rules": [
        {"site": "s", "action": "delay", "after_n": 2, "every_n": 2,
         "count": 2, "delay_s": 0.5, "tag": ["a", "b*"]},
    ]})
    try:
        seq = ["a", "bx", "c", "a", "a", "bz", "a", "a", "a"]
        fired = [bool(chaos.hit("s", tag=t)) for t in seq]
        # "c" never matches; the rest are matching hits 1..8; skip the
        # first 2 (after_n), then fire on every 2nd eligible hit
        # (eligible 2 and 4 = matching hits 4 and 6), capped at 2 fires
        assert fired == [False, False, False, False, True, False,
                         True, False, False]
        act = None
        chaos.load_plan({"rules": [
            {"site": "s", "action": "hang", "delay_s": 1.5}]})
        act = chaos.hit("s")
        assert act == {"action": "hang", "delay_s": 1.5, "rule": 0}
        assert chaos.hit("s") is None          # count defaults to 1
        assert chaos.hit("other") is None      # unknown site: no-op
    finally:
        chaos.clear()
    assert not chaos.ARMED and chaos.hit("s") is None


def test_chaos_rpc_sites_drop_dup_delay():
    """The rpc.send / rpc.recv seams against a live RpcServer: drop makes
    the caller time out (the server never sees it), recv-dup dispatches
    the handler twice for one frame, send-delay stalls the round-trip."""
    from ray_tpu._private.rpc import IoThread, RpcClient, RpcServer

    io = IoThread.current()
    calls = {"n": 0}

    async def echo(payload):
        calls["n"] += 1
        return {"v": payload["v"]}

    srv = RpcServer("127.0.0.1")
    srv.register("Echo", echo)
    port = io.run(srv.start(0))
    cli = RpcClient("127.0.0.1", port)
    io.run(cli.connect())
    try:
        chaos.load_plan({"rules": [
            {"site": "rpc.send", "method": "Echo", "action": "drop"}]})
        with pytest.raises(asyncio.TimeoutError):
            io.run(cli.call("Echo", {"v": 1}, timeout=0.5), timeout=5)
        assert calls["n"] == 0                      # never reached the wire
        assert io.run(cli.call("Echo", {"v": 2}, timeout=5),
                      timeout=10) == {"v": 2}       # rule spent: flows again
        assert chaos.injections_total() == 1

        chaos.load_plan({"rules": [
            {"site": "rpc.recv", "method": "Echo", "action": "dup"}]})
        calls["n"] = 0
        assert io.run(cli.call("Echo", {"v": 3}, timeout=5),
                      timeout=10) == {"v": 3}
        _poll(lambda: calls["n"] >= 2, timeout=5)
        assert calls["n"] == 2                      # one frame, two dispatches

        chaos.load_plan({"rules": [
            {"site": "rpc.send", "method": "Echo", "action": "delay",
             "delay_s": 0.3}]})
        t0 = time.perf_counter()
        io.run(cli.call("Echo", {"v": 4}, timeout=5), timeout=10)
        assert time.perf_counter() - t0 >= 0.28
    finally:
        chaos.clear()
        io.run(cli.close())
        io.run(srv.stop())


def test_loadgen_open_loop_schedule_and_tail():
    """The load generator is open-loop: a stalled request shows up in the
    tail (latency from the SCHEDULED arrival) without delaying later
    arrivals — coordinated omission cannot hide it."""
    from ray_tpu.util.loadgen import OpenLoopLoadGen

    a = OpenLoopLoadGen._schedule(100.0, 0.5, "poisson", 3)
    assert a == OpenLoopLoadGen._schedule(100.0, 0.5, "poisson", 3)
    assert 10 < len(a) < 200 and all(0 <= t < 0.5 for t in a)
    assert OpenLoopLoadGen._schedule(50.0, 0.2, "uniform", 0) == [
        i / 50.0 for i in range(10)]

    gate = threading.Event()

    def fn(i):
        if i == 0:
            gate.wait(5.0)
        return i

    gen = OpenLoopLoadGen(fn, rate_hz=50, duration_s=0.2, arrival="uniform")
    threading.Timer(1.0, gate.set).start()
    rep = gen.run(join_timeout_s=10)
    assert rep["completed"] == 10 and rep["failed"] == 0 and not rep["shed"]
    assert rep["max_s"] >= 0.9          # request 0's stall is in the tail
    assert rep["p50_s"] < 0.5           # nobody queued behind it


# ------------------------------------------------- failover (unit layer)


def test_llm_stream_timeout_is_structured(monkeypatch):
    """Satellite: the per-pull timeout comes from RTPU_llm_stream_timeout_s
    and surfaces as LlmStreamTimeoutError carrying stream id + tokens
    received, not a raw transport timeout."""
    import concurrent.futures

    from ray_tpu.serve import rpc_ingress as ri

    class _Io:
        def run(self, coro, timeout=None):
            coro.close()
            raise concurrent.futures.TimeoutError()

    class _Rpc:
        def call(self, *a, **k):
            async def _c():
                pass

            return _c()

    client = ri.RpcIngressClient.__new__(ri.RpcIngressClient)
    client._io = _Io()
    client._client = _Rpc()
    monkeypatch.setenv("RTPU_llm_stream_timeout_s", "7")
    s = ri.LlmStream(client, "sid-1", timeout=300.0, app="llm",
                     prompt_ids=[1, 2], sampling={"max_tokens": 8})
    s._received = [5, 6, 7]
    with pytest.raises(ri.LlmStreamTimeoutError) as ei:
        next(s)
    e = ei.value
    assert (e.stream_id == "sid-1" and e.tokens_received == 3
            and e.timeout_s == 7.0 and isinstance(e, TimeoutError))


def test_llm_stream_failover_resubmits_prompt_plus_generated(monkeypatch):
    """replica_died mid-pull => transparent reopen with prompt + tokens
    generated so far and ONLY the remaining token budget."""
    from ray_tpu.serve import rpc_ingress as ri

    monkeypatch.setenv("RTPU_serve_failover_retries", "3")
    monkeypatch.setenv("RTPU_serve_failover_backoff_s", "0.01")
    monkeypatch.setenv("RTPU_serve_failover_backoff_max_s", "0.02")

    class FakeClient:
        def __init__(self):
            self.opens = []
            self.pulls = 0
            self._io = self
            self._client = self

        def run(self, value, timeout=None):
            return value

        def call(self, method, payload, timeout=None, **kw):
            assert method == "ServeLlmNext"
            self.pulls += 1
            if self.pulls == 1:
                return {"done": False,
                        "_oob": np.asarray([11, 12], np.int32).tobytes()}
            if self.pulls == 2:
                return {"error": "actor died", "replica_died": True,
                        "app_error": True}
            return {"done": True, "finish_reason": "length",
                    "_oob": np.asarray([13], np.int32).tobytes()}

        def _llm_open(self, app, prompt, sampling, timeout):
            self.opens.append((app, list(prompt), dict(sampling)))
            return {"stream_id": f"s{len(self.opens) + 1}"}

    c = FakeClient()
    s = ri.LlmStream(c, "s1", timeout=30.0, app="llm", prompt_ids=[1, 2, 3],
                     sampling={"max_tokens": 3})
    assert list(s) == [11, 12, 13]
    assert s.failovers == 1 and s.finish_reason == "length"
    (app, prompt, sampling), = c.opens
    assert app == "llm"
    assert prompt == [1, 2, 3, 11, 12]       # prompt + generated-so-far
    assert sampling["max_tokens"] == 1       # remaining budget only


def test_llm_stream_failover_exhaustion_carries_tokens(monkeypatch):
    from ray_tpu.serve import rpc_ingress as ri

    monkeypatch.setenv("RTPU_serve_failover_retries", "2")
    monkeypatch.setenv("RTPU_serve_failover_backoff_s", "0.01")
    monkeypatch.setenv("RTPU_serve_failover_backoff_max_s", "0.02")

    class FakeClient:
        def __init__(self):
            self.pulls = 0
            self._io = self
            self._client = self

        def run(self, value, timeout=None):
            return value

        def call(self, method, payload, timeout=None, **kw):
            self.pulls += 1
            if self.pulls == 1:
                return {"done": False,
                        "_oob": np.asarray([9], np.int32).tobytes()}
            return {"error": "actor died", "replica_died": True,
                    "app_error": True}

        def _llm_open(self, app, prompt, sampling, timeout):
            raise ri.RpcIngressError("no replicas")

    s = ri.LlmStream(FakeClient(), "s1", timeout=30.0, app="llm",
                     prompt_ids=[1], sampling={"max_tokens": 4})
    assert next(s) == 9
    with pytest.raises(ri.ReplicaDiedMidStreamError) as ei:
        next(s)
    assert ei.value.tokens_generated == [9]


def test_proxy_llm_error_classifies_death_and_backpressure():
    from ray_tpu.exceptions import ActorDiedError, TaskError
    from ray_tpu.serve._proxy import ProxyActor
    from ray_tpu.serve.llm.engine import LLMBackpressure

    out = ProxyActor._llm_error(ActorDiedError(b"x", "actor died"))
    assert out["replica_died"] and out["app_error"]
    wrapped = TaskError(ActorDiedError(b"x", "dead"), "tb")
    assert ProxyActor._llm_error(wrapped)["replica_died"]
    bp = ProxyActor._llm_error(LLMBackpressure(3, 2, 0.5))
    assert bp["backpressure"] and bp["queue_depth"] == 3
    assert "replica_died" not in bp
    plain = ProxyActor._llm_error(ValueError("bad prompt"))
    assert "replica_died" not in plain


def test_proxy_llm_slot_released_exactly_once():
    """Satellite audit: every death path releases the p2c in-flight slot
    exactly once — the record pop makes a double drop a no-op."""
    from ray_tpu.serve._proxy import ProxyActor

    p = ProxyActor()
    released = []

    class H:
        def release(self, name):
            released.append(name)

    p._llm_handles = {"ing": H()}
    p._llm_streams = {"sid": {"replica": None, "name": "r0", "rid": "x",
                              "ingress": "ing", "ts": time.time()}}
    p._drop_llm_stream("sid", cancel=False)
    p._drop_llm_stream("sid", cancel=True)   # already dropped: no-op
    p._drop_llm_stream("nope", cancel=True)  # unknown: no-op
    assert released == ["r0"]


def test_handle_idempotent_retry_on_actor_died(monkeypatch):
    """DeploymentHandle bounded ActorDiedError retry: an idempotent call
    that dies with its replica re-dispatches to a survivor."""
    from ray_tpu.exceptions import ActorDiedError
    from ray_tpu.serve import _handle as H

    monkeypatch.setenv("RTPU_serve_failover_backoff_s", "0.01")
    monkeypatch.setenv("RTPU_serve_failover_backoff_max_s", "0.02")

    h = H.DeploymentHandle("dep", idempotent=True)
    assert h.options(idempotent=False)._idempotent is False
    assert h.ping._idempotent is True        # attr handles inherit it
    calls = {"redispatch": 0, "refreshed": 0}

    class GoodResp:
        def result(self, timeout=None):
            return 42

    monkeypatch.setattr(
        h, "_refresh_replicas",
        lambda force=False: calls.__setitem__(
            "refreshed", calls["refreshed"] + 1))
    monkeypatch.setattr(
        h, "_remote",
        lambda args, kwargs, died_retries=0: (
            calls.__setitem__("redispatch", calls["redispatch"] + 1),
            GoodResp())[1])

    resp = H.DeploymentResponse(object())
    resp.result = lambda timeout=None: (_ for _ in ()).throw(
        ActorDiedError(b"a", "actor died"))
    H._attach_done(resp, h, "r0", time.time(), args=(), kwargs={},
                   died_retries=2)
    assert resp.result(timeout=1) == 42
    assert calls["redispatch"] == 1 and calls["refreshed"] >= 1

    # without retries the death surfaces unchanged
    resp2 = H.DeploymentResponse(object())
    resp2.result = lambda timeout=None: (_ for _ in ()).throw(
        ActorDiedError(b"a", "actor died"))
    H._attach_done(resp2, h, "r0", time.time(), args=(), kwargs={},
                   died_retries=0)
    with pytest.raises(ActorDiedError):
        resp2.result(timeout=1)


# ------------------------------------------- failover (tier-1 fast, live)


@pytest.mark.timeout(170)
def test_llm_single_kill_failover_byte_equal(monkeypatch, shutdown_only):
    """One replica SIGKILLed mid-generation (seeded chaos plan): the
    stream transparently fails over to the controller's replacement and
    completes byte-equal to a fault-free run; exactly ONE worker_crash
    incident is published for the induced kill; the replica set
    reconverges; the surviving replica's KV is leak-free. Also exercises
    the plasma.write error site on the driver's first large put."""
    plan = {"seed": 1, "rules": [
        {"site": "replica.step", "deployment": "llm#LLMReplica", "replica": "0",
         "action": "kill", "after_steps": 6},
        {"site": "plasma.write", "action": "error", "count": 1},
    ]}
    monkeypatch.setenv("RTPU_chaos_plan", json.dumps(plan))
    monkeypatch.setenv("RTPU_serve_failover_retries", "12")
    monkeypatch.setenv("RTPU_serve_failover_backoff_s", "0.5")
    monkeypatch.setenv("RTPU_serve_failover_backoff_max_s", "2.0")
    ray_tpu.init(num_cpus=6)
    from ray_tpu import serve
    from ray_tpu.serve import llm as sllm

    try:
        # the plasma.write error rule fires on the driver's first large
        # put and ONLY that one (count=1)
        big = np.zeros(300_000, dtype=np.uint8)
        with pytest.raises(OSError, match="chaos"):
            ray_tpu.put(big)
        assert ray_tpu.get(ray_tpu.put(big)).nbytes == big.nbytes

        sllm.deploy(model="fake",
                    model_config={"vocab_size": 97, "step_cost_s": 0.05},
                    app_name="llm", num_blocks=64, block_size=4,
                    max_batch=4, max_waiting=32)
        ref = _reference_tokens(max_tokens=20)
        s = sllm.stream(PROMPT, app_name="llm", max_tokens=20)
        out = list(s)
        assert out == ref, (out, ref)
        assert s.failovers >= 1          # the kill landed mid-stream
        assert s.finish_reason == "length"

        incs = _poll(_worker_crash_incidents, timeout=30)
        assert len(incs) == 1, incs      # exactly one attributed incident
        assert incs[0].get("node_id") and incs[0].get("pid")

        # replica set reconverged to target=1 with a LIVE replica (the
        # completed stream already proves it serves)
        names = _poll(lambda: (_live_replicas()
                               if len(_live_replicas()) == 1 else None),
                      timeout=60)
        assert names and len(names) == 1

        # zero leaked KV blocks on every surviving replica
        def _clean():
            reps = _llm_integrity_all()
            return reps if all(
                not r["problems"] and r["used_blocks"] == 0
                and r["running"] == 0 for r in reps.values()) else None

        reps = _poll(_clean, timeout=30)
        assert reps, _llm_integrity_all()
    finally:
        chaos.clear()
        try:
            serve.shutdown()
        except Exception:
            pass


# --------------------------------------------- storm scenarios (slow tier)


def _run_streams(n, max_tokens, app="llm", timeout_s=240.0):
    from ray_tpu.serve import llm as sllm

    results = [None] * n
    errors = [None] * n

    def worker(i):
        try:
            results[i] = list(sllm.stream(PROMPT, app_name=app,
                                          max_tokens=max_tokens))
        except Exception as e:  # noqa: BLE001
            errors[i] = e

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    deadline = time.time() + timeout_s
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.time()))
    assert not any(t.is_alive() for t in threads), "streams hung"
    return results, errors


def _storm_invariants(expected_kills, ref, results, errors, target_replicas):
    """The invariant block every storm scenario asserts: byte-equal
    survivors, exact incident attribution, replica-set reconvergence,
    zero leaked KV blocks, zero leaked plasma objects."""
    from ray_tpu.util import state

    assert all(e is None for e in errors), [e for e in errors if e]
    assert all(r == ref for r in results), (
        f"{sum(r != ref for r in results)} streams diverged")

    incs = _poll(lambda: (_worker_crash_incidents()
                          if len(_worker_crash_incidents())
                          >= expected_kills else None), timeout=30)
    assert len(incs) == expected_kills, incs

    def _converged():
        names = _live_replicas()
        return names if len(names) == target_replicas else None

    assert _poll(_converged, timeout=90)

    def _clean():
        reps = _llm_integrity_all()
        return reps if reps and all(
            not r["problems"] and r["used_blocks"] == 0
            for r in reps.values()) else None

    assert _poll(_clean, timeout=30), _llm_integrity_all()

    # zero leaked plasma objects: the PR 7 forced two-sweep cross-check
    leaks = state.find_memory_leaks(sweep=True, confirm_pause_s=1.0)
    assert leaks == [], leaks


@pytest.mark.slow
@pytest.mark.timeout(280)
def test_replica_kill_storm_32_streams(monkeypatch, shutdown_only):
    """The acceptance scenario: a seeded replica-kill storm under >= 32
    concurrent llm streams. Two of three replicas are SIGKILLed at
    deterministic step counts while every stream is mid-generation; all
    32 streams must complete byte-equal to the fault-free run."""
    plan = {"seed": 5, "rules": [
        {"site": "replica.step", "deployment": "llm#LLMReplica", "replica": "0",
         "action": "kill", "after_steps": 8},
        {"site": "replica.step", "deployment": "llm#LLMReplica", "replica": "1",
         "action": "kill", "after_steps": 16},
    ]}
    monkeypatch.setenv("RTPU_chaos_plan", json.dumps(plan))
    monkeypatch.setenv("RTPU_serve_failover_retries", "20")
    monkeypatch.setenv("RTPU_serve_failover_backoff_s", "0.5")
    monkeypatch.setenv("RTPU_serve_failover_backoff_max_s", "2.0")
    ray_tpu.init(num_cpus=8)
    from ray_tpu import serve
    from ray_tpu.serve import llm as sllm

    try:
        sllm.deploy(model="fake",
                    model_config={"vocab_size": 97, "step_cost_s": 0.01},
                    app_name="llm", num_replicas=3, num_blocks=256,
                    block_size=4, max_batch=16, max_waiting=64)
        ref = _reference_tokens(max_tokens=24)
        results, errors = _run_streams(32, max_tokens=24)
        _storm_invariants(expected_kills=2, ref=ref, results=results,
                          errors=errors, target_replicas=3)
    finally:
        chaos.clear()
        try:
            serve.shutdown()
        except Exception:
            pass


@pytest.mark.slow
@pytest.mark.timeout(170)
def test_chaos_mini_storm(monkeypatch, shutdown_only):
    """CI chaos smoke: a seeded ~30s mini-storm — one replica of two
    killed under 8 concurrent streams while the raylet's heartbeat is
    chaos-delayed (the node must survive the tolerance window) — with the
    full invariant block."""
    plan = {"seed": 11, "rules": [
        {"site": "replica.step", "deployment": "llm#LLMReplica", "replica": "0",
         "action": "kill", "after_steps": 10},
        {"site": "raylet.heartbeat", "action": "drop", "count": 2},
    ]}
    monkeypatch.setenv("RTPU_chaos_plan", json.dumps(plan))
    monkeypatch.setenv("RTPU_serve_failover_retries", "15")
    monkeypatch.setenv("RTPU_serve_failover_backoff_s", "0.5")
    monkeypatch.setenv("RTPU_serve_failover_backoff_max_s", "2.0")
    ray_tpu.init(num_cpus=6)
    from ray_tpu import serve
    from ray_tpu.serve import llm as sllm
    from ray_tpu.util import state

    try:
        sllm.deploy(model="fake",
                    model_config={"vocab_size": 97, "step_cost_s": 0.02},
                    app_name="llm", num_replicas=2, num_blocks=128,
                    block_size=4, max_batch=8, max_waiting=32)
        ref = _reference_tokens(max_tokens=20)
        results, errors = _run_streams(8, max_tokens=20, timeout_s=120.0)
        _storm_invariants(expected_kills=1, ref=ref, results=results,
                          errors=errors, target_replicas=2)
        # the heartbeat drops stayed inside the failure tolerance: the
        # node is still alive in the GCS view and still schedules work
        assert state.count_open_incidents() >= 1  # the worker_crash above

        @ray_tpu.remote
        def ok():
            return 1

        assert ray_tpu.get(ok.remote(), timeout=60) == 1
    finally:
        chaos.clear()
        try:
            serve.shutdown()
        except Exception:
            pass


@pytest.mark.slow
@pytest.mark.timeout(220)
def test_backpressure_flood_sheds_cleanly(monkeypatch, shutdown_only):
    """Open-loop flood far past capacity against a tiny admission window:
    overload must shed with STRUCTURED backpressure errors (never OOM,
    never hang), completed streams stay byte-equal, and the KV pool and
    plasma store come back empty."""
    ray_tpu.init(num_cpus=6)
    from ray_tpu import serve
    from ray_tpu.serve import llm as sllm
    from ray_tpu.serve.rpc_ingress import (
        RpcBackpressureError,
        RpcIngressClient,
    )
    from ray_tpu.util.loadgen import OpenLoopLoadGen

    try:
        sllm.deploy(model="fake",
                    model_config={"vocab_size": 97, "step_cost_s": 0.05},
                    app_name="llm", num_blocks=64, block_size=4,
                    max_batch=2, max_waiting=4)
        ref = _reference_tokens(max_tokens=8)
        port = serve.start_rpc_ingress()
        client = RpcIngressClient("127.0.0.1", port)

        def fire(i):
            toks = list(client.llm_stream(PROMPT, app="llm", max_tokens=8))
            assert toks == ref
            return len(toks)

        gen = OpenLoopLoadGen(fire, rate_hz=25, duration_s=4.0,
                              arrival="poisson", seed=9)
        rep = gen.run(join_timeout_s=120)
        assert rep["completed"] >= 10
        assert rep["failed"] > 0, "flood never tripped admission control"
        # every failure is the structured shed, nothing else broke
        assert set(rep["errors"]) == {"RpcBackpressureError"}, rep["errors"]
        # the structured error itself carries the backoff numbers
        streams = []
        with pytest.raises(RpcBackpressureError) as ei:
            for _ in range(50):
                streams.append(
                    client.llm_stream(PROMPT, app="llm", max_tokens=64))
        assert ei.value.max_waiting == 4 and ei.value.queue_depth >= 4
        for s in streams:
            s.close()  # mid-stream cancels free the queued KV
        client.close()

        def _clean():
            reps = _llm_integrity_all()
            return reps if reps and all(
                not r["problems"] and r["used_blocks"] == 0
                and r["waiting"] == 0 and r["running"] == 0
                for r in reps.values()) else None

        assert _poll(_clean, timeout=90), _llm_integrity_all()
        from ray_tpu.util import state

        assert state.find_memory_leaks(sweep=True) == []
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass


@pytest.mark.slow
@pytest.mark.timeout(170)
def test_slow_client_stall_does_not_block_others(shutdown_only):
    """A client that pulls one token every 300 ms must not head-of-line
    block the batch: fast streams admitted alongside it finish promptly
    and byte-equal, the slow stream still completes, and abandoning a
    stream mid-generation frees its KV."""
    ray_tpu.init(num_cpus=6)
    from ray_tpu import serve
    from ray_tpu.serve import llm as sllm

    try:
        sllm.deploy(model="fake",
                    model_config={"vocab_size": 97, "step_cost_s": 0.01},
                    app_name="llm", num_blocks=128, block_size=4,
                    max_batch=8, max_waiting=32)
        ref = _reference_tokens(max_tokens=16)
        slow = sllm.stream(PROMPT, app_name="llm", max_tokens=16,
                           max_tokens_per_pull=1)
        slow_out = [next(slow)]
        t0 = time.time()
        results, errors = _run_streams(6, max_tokens=16, timeout_s=60.0)
        fast_elapsed = time.time() - t0
        assert all(e is None for e in errors), errors
        assert all(r == ref for r in results)
        assert fast_elapsed < 30.0, (
            f"fast streams waited {fast_elapsed:.1f}s behind a slow client")
        for t in slow:
            slow_out.append(t)
            time.sleep(0.05)
        assert slow_out == ref

        # abandonment: a stream closed mid-generation frees its blocks
        drop = sllm.stream(PROMPT, app_name="llm", max_tokens=4096)
        next(drop)
        drop.close()

        def _clean():
            reps = _llm_integrity_all()
            return reps if reps and all(
                not r["problems"] and r["used_blocks"] == 0
                for r in reps.values()) else None

        assert _poll(_clean, timeout=60), _llm_integrity_all()
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass


# ----------------------------------------------------------- seed cases


def test_raylet_killed_mid_task_stream():
    """Tasks in flight on a dying node retry elsewhere; the stream of
    submissions keeps completing (owner-side retries,
    reference: task_manager.h max_retries)."""
    cluster = Cluster(
        initialize_head=True, head_node_args={"resources": {"CPU": 3}}
    )
    victim = cluster.add_node(resources={"CPU": 3})
    cluster.wait_for_nodes()
    ray_tpu.init(address=cluster.address)
    try:
        @ray_tpu.remote(max_retries=4)
        def work(i):
            time.sleep(0.05)
            return i * 3

        # a continuous stream: submit in waves, kill the raylet mid-wave
        refs = [work.remote(i) for i in range(60)]
        time.sleep(0.5)  # some running on the victim now
        victim.kill_raylet()
        refs += [work.remote(i) for i in range(60, 90)]
        out = ray_tpu.get(refs, timeout=180)
        assert out == [i * 3 for i in range(90)]
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_malformed_rpc_frames_do_not_kill_servers():
    """Garbage bytes, huge length prefixes, and truncated frames against
    the raylet + GCS sockets: the servers drop the bad connection and keep
    serving legit traffic (reference: the gRPC layer's framing guarantees;
    our msgpack framing must be as defensive)."""
    ray_tpu.init(num_cpus=2)
    try:
        from ray_tpu import api

        node = api._local_node
        gcs_host, gcs_port = node.gcs_address.rsplit(":", 1)
        targets = [(gcs_host, int(gcs_port))]
        raylet_port = getattr(node, "raylet_port", None)
        if raylet_port:
            targets.append((gcs_host, int(raylet_port)))

        payloads = [
            b"\x00" * 64,                                 # zero-length spam
            b"GET / HTTP/1.1\r\nHost: x\r\n\r\n",         # wrong protocol
            struct.pack("<I", (1 << 31) - 1) + b"x" * 64,  # huge frame claim
            struct.pack("<I", 100) + b"y" * 10,           # truncated body
            struct.pack("<I", 8) + b"\xc1" * 8,           # invalid msgpack
        ]
        for host, port in targets:
            for p in payloads:
                s = socket.create_connection((host, port), timeout=5)
                try:
                    s.sendall(p)
                    time.sleep(0.05)
                finally:
                    s.close()

        # the cluster still works
        @ray_tpu.remote
        def ok():
            return "alive"

        assert ray_tpu.get(ok.remote(), timeout=60) == "alive"
        assert ray_tpu.get(ok.remote(), timeout=60) == "alive"
    finally:
        ray_tpu.shutdown()


def test_two_concurrent_drivers():
    """Two independent driver processes against one cluster: both run
    tasks and actors simultaneously, with correct results and no
    cross-talk (reference: multi-driver job isolation)."""
    cluster = Cluster(
        initialize_head=True, head_node_args={"resources": {"CPU": 6}}
    )
    cluster.wait_for_nodes()

    script = """
import sys
import ray_tpu
tag = sys.argv[1]
ray_tpu.init(address=sys.argv[2])

@ray_tpu.remote
def f(i):
    return f"{tag}-{i}"

@ray_tpu.remote
class Acc:
    def __init__(self):
        self.items = []
    def add(self, x):
        self.items.append(x)
        return len(self.items)

a = Acc.remote()
outs = ray_tpu.get([f.remote(i) for i in range(40)])
assert outs == [f"{tag}-{i}" for i in range(40)], outs
ns = ray_tpu.get([a.add.remote(i) for i in range(20)])
assert ns == list(range(1, 21))
ray_tpu.shutdown()
print(f"DRIVER-{tag}-OK")
"""
    try:
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", script, tag, cluster.address],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
            for tag in ("one", "two")
        ]
        for tag, p in zip(("one", "two"), procs):
            out, _ = p.communicate(timeout=180)
            assert f"DRIVER-{tag}-OK" in out, out[-3000:]
    finally:
        cluster.shutdown()


def test_spillback_under_contention():
    """When the preferred node is saturated, lease requests spill to
    peers instead of queueing behind long tasks (reference:
    hybrid_scheduling_policy.cc spillback)."""
    cluster = Cluster(
        initialize_head=True, head_node_args={"resources": {"CPU": 2}}
    )
    cluster.add_node(resources={"CPU": 2})
    cluster.wait_for_nodes()
    ray_tpu.init(address=cluster.address)
    try:
        @ray_tpu.remote(num_cpus=1)
        def hog():
            time.sleep(8)
            return ray_tpu.get_runtime_context().get_node_id()

        @ray_tpu.remote(num_cpus=1)
        def quick(i):
            return (i, ray_tpu.get_runtime_context().get_node_id())

        # saturate two slots (they land somewhere), then submit quick
        # tasks: they must run on the remaining free slots promptly, not
        # wait 8s behind the hogs
        hogs = [hog.remote() for _ in range(2)]
        time.sleep(1.0)
        t0 = time.time()
        out = ray_tpu.get([quick.remote(i) for i in range(8)], timeout=60)
        quick_elapsed = time.time() - t0
        assert quick_elapsed < 6.0, (
            f"quick tasks waited {quick_elapsed:.1f}s — no spillback past "
            "the saturated node"
        )
        assert [i for i, _ in out] == list(range(8))
        # both nodes participated overall
        hog_nodes = set(ray_tpu.get(hogs, timeout=60))
        quick_nodes = {n for _, n in out}
        assert len(hog_nodes | quick_nodes) == 2
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
