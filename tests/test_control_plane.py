"""Control-plane parallelism: sharded RPC reactor, lease-grant batching,
and the plasma-backed submit ring.

Unit layers (ring byte-format, reactor dispatch contract, the FIFO
starvation barrier) run against plain buffers and hand-built NodeManagers;
the live layers boot real clusters and assert the paths end-to-end —
including the fallbacks (ring full → RPC, dead consumer → resubmit
without loss or duplication).
"""

import asyncio
import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private import chaos as _chaos
from ray_tpu._private.rpc import RpcClient, RpcServer
from ray_tpu._private.submit_ring import (
    HEADER_BYTES,
    RingConsumer,
    RingCorrupt,
    RingProducer,
    ring_bytes,
)


# ------------------------------------------------------------- ring format


@pytest.mark.fast
def test_ring_roundtrip_and_doorbell_transitions():
    buf = bytearray(HEADER_BYTES + 256)
    prod = RingProducer(memoryview(buf), init=True)
    cons = RingConsumer(memoryview(buf))
    # first push of an empty ring reports the empty→non-empty transition
    assert prod.try_push(b"alpha") is True
    # second push while non-empty does not
    assert prod.try_push(b"beta") is False
    assert cons.drain() == [b"alpha", b"beta"]
    assert cons.empty()
    # drained-empty ring transitions again
    assert prod.try_push(b"gamma") is True
    assert cons.drain() == [b"gamma"]


@pytest.mark.fast
def test_ring_wraparound_exact_sequence():
    buf = bytearray(HEADER_BYTES + 128)
    prod = RingProducer(memoryview(buf), init=True)
    cons = RingConsumer(memoryview(buf))
    expected = []
    produced = consumed = 0
    for i in range(200):
        p = (b"%03d" % i) * (1 + i % 7)
        while prod.try_push(p) is None:
            got = cons.drain(max_items=1)
            assert got, "full ring must drain"
            assert got[0] == expected[consumed]
            consumed += 1
        expected.append(p)
        produced += 1
    for g in cons.drain(max_items=1000):
        assert g == expected[consumed]
        consumed += 1
    assert consumed == produced == 200


@pytest.mark.fast
def test_ring_full_returns_none_and_oversize_rejected():
    buf = bytearray(HEADER_BYTES + 128)
    prod = RingProducer(memoryview(buf), init=True)
    # oversize: can never fit
    assert prod.try_push(b"x" * 4096) is None
    pushes = 0
    while prod.try_push(b"y" * 40) is not None:
        pushes += 1
        assert pushes < 100
    assert pushes > 0  # some fit, then clean full signal
    cons = RingConsumer(memoryview(buf))
    assert len(cons.drain()) == pushes


@pytest.mark.fast
def test_ring_closed_flag_and_heartbeat():
    buf = bytearray(HEADER_BYTES + 128)
    prod = RingProducer(memoryview(buf), init=True)
    cons = RingConsumer(memoryview(buf))
    assert not cons.closed()
    assert prod.consumer_beat() == 0.0
    cons.beat(123.5)
    assert prod.consumer_beat() == 123.5
    prod.close()
    assert cons.closed()
    # attaching to garbage fails loudly
    with pytest.raises((RingCorrupt, ValueError)):
        RingConsumer(memoryview(bytearray(HEADER_BYTES + 128)))


@pytest.mark.fast
def test_ring_dead_consumer_fallback_exactly_once():
    """The raylet-restart contract (unit-level): specs the consumer never
    executed are resubmitted via the fallback path; specs that replied are
    not — every task executes exactly once."""
    buf = bytearray(ring_bytes(8))
    prod = RingProducer(memoryview(buf), init=True)
    cons = RingConsumer(memoryview(buf))

    pending = {}  # task_id -> spec (the driver-side _ring_pending analogue)
    executed = []

    for i in range(5):
        tid = b"task-%d" % i
        pending[tid] = {"task_id": tid}
        assert prod.try_push(tid) is not None

    # consumer executes two entries, replies for them, then "dies"
    for tid in cons.drain(max_items=2):
        executed.append(tid)
        pending.pop(tid)  # reply landed driver-side

    # driver detects the stale heartbeat -> fallback resubmit of the rest
    assert prod.consumer_beat() == 0.0  # never beat: dead
    fallback = list(pending.values())
    pending.clear()
    for spec in fallback:
        executed.append(spec["task_id"])  # RPC path executes it

    assert sorted(executed) == sorted(b"task-%d" % i for i in range(5))
    assert len(executed) == len(set(executed))  # no duplicates


# --------------------------------------------------------- sharded reactor


def _run_sharded_server(test_body):
    """Boot an RpcServer with 2 reactor shards inside a private loop and
    run ``test_body(server, port, home_thread_id)`` as a coroutine."""

    async def main():
        server = RpcServer("127.0.0.1", shards=2)
        home_tid = threading.get_ident()
        handler_tids = []

        async def echo(payload):
            handler_tids.append(threading.get_ident())
            return {"echo": payload["x"]}

        server.register("Echo", echo)
        port = await server.start(0)
        assert server.num_shards == 2
        try:
            await test_body(server, port, home_tid, handler_tids)
        finally:
            await server.stop()

    asyncio.run(main())


@pytest.mark.fast
def test_sharded_reactor_serves_many_connections():
    """Connections land on different shard loops; handlers still run on
    the HOME loop (the dispatch contract protecting shared state), and
    every response routes back on the right connection."""

    async def body(server, port, home_tid, handler_tids):
        clients = []
        # 4 connections round-robin over 2 shards: at least one serves on
        # a non-home thread
        for _ in range(4):
            c = RpcClient("127.0.0.1", port)
            await c.connect()
            clients.append(c)
        results = await asyncio.gather(*(
            c.call("Echo", {"x": i}, timeout=10)
            for i, c in enumerate(clients)
            for _ in range(5)
        ))
        assert [r["echo"] for r in results] == [i for i in range(4)
                                                for _ in range(5)]
        assert set(handler_tids) == {home_tid}  # home-loop dispatch held
        for c in clients:
            await c.close()

    _run_sharded_server(body)


@pytest.mark.fast
def test_set_shard_safe_rejects_unresolved_names():
    """A typo'd set_shard_safe name used to silently keep the handler
    hopping home — correct but quietly defeating the optimization. Now it
    raises at registration, and the lint plane's shard-safe-unresolved
    rule catches the literal form before a cluster even boots."""
    server = RpcServer("127.0.0.1")

    async def ping(payload):
        return {"ok": True}

    server.register("Ping", ping)
    server.set_shard_safe({"Ping"})  # resolves: fine
    with pytest.raises(ValueError, match="PingTypo"):
        server.set_shard_safe({"PingTypo"})
    # the failed call must not have poisoned the good registration
    assert "Ping" in server._shard_safe
    assert "PingTypo" not in server._shard_safe


@pytest.mark.fast
def test_shard_safe_handler_runs_on_shard_thread():
    async def main():
        server = RpcServer("127.0.0.1", shards=2)
        home_tid = threading.get_ident()
        tids = []

        async def probe(payload):
            tids.append(threading.get_ident())
            return {"ok": True}

        server.register("Probe", probe)
        server.set_shard_safe({"Probe"})
        port = await server.start(0)
        try:
            # two connections: one on the home loop (shard 0), one on a
            # shard thread — the shard-safe handler runs in place on both
            for _ in range(2):
                c = RpcClient("127.0.0.1", port)
                await c.connect()
                assert (await c.call("Probe", {}, timeout=10))["ok"]
                await c.close()
            assert home_tid in tids
            assert any(t != home_tid for t in tids)
        finally:
            await server.stop()

    asyncio.run(main())


@pytest.mark.fast
def test_sharded_reactor_errors_oob_and_notify():
    """RemoteError propagation, OOB sinks, and notifies all work from a
    shard loop (connection #2 of 2 shards is off-home)."""

    async def main():
        server = RpcServer("127.0.0.1", shards=2)
        landed = {}
        notified = asyncio.Event()
        home_loop = asyncio.get_running_loop()

        async def boom(payload):
            raise ValueError("kaboom")

        async def land(payload):
            return {"oob": payload.get("_oob")}

        async def note(payload):
            home_loop  # noqa: B018 — handler runs here thanks to the hop
            notified.set()

        def sink(payload, nbytes):
            buf = bytearray(nbytes)
            landed["buf"] = buf
            return memoryview(buf), None

        server.register("Boom", boom)
        server.register("Land", land)
        server.register("Note", note)
        server.set_oob_sink("Land", sink)
        port = await server.start(0)
        try:
            # burn connection 1 (home shard), test on connection 2 (shard)
            c0 = RpcClient("127.0.0.1", port)
            await c0.connect()
            c = RpcClient("127.0.0.1", port)
            await c.connect()
            from ray_tpu._private.rpc import RemoteError

            with pytest.raises(RemoteError) as ei:
                await c.call("Boom", {}, timeout=10)
            assert isinstance(ei.value.exception, ValueError)
            r = await c.call("Land", {}, oob=b"payload!", timeout=10)
            assert r["oob"] == 8 and bytes(landed["buf"]) == b"payload!"
            await c.notify("Note", {})
            await asyncio.wait_for(notified.wait(), 10)
            await c.close()
            await c0.close()
        finally:
            await server.stop()

    asyncio.run(main())


@pytest.mark.fast
def test_sharded_reactor_chaos_recv_seam():
    """The chaos rpc.recv seam fires per-shard: a drop rule swallows the
    request on a shard connection exactly like on the home loop."""

    async def main():
        _chaos.load_plan({"seed": 1, "rules": [
            {"site": "rpc.recv", "action": "drop", "method": "Flaky",
             "count": 1}]})
        try:
            server = RpcServer("127.0.0.1", shards=2)
            calls = []

            async def flaky(payload):
                calls.append(1)
                return {"ok": True}

            server.register("Flaky", flaky)
            port = await server.start(0)
            try:
                c0 = RpcClient("127.0.0.1", port)
                await c0.connect()
                c = RpcClient("127.0.0.1", port)  # lands on shard 1
                await c.connect()
                with pytest.raises(asyncio.TimeoutError):
                    await c.call("Flaky", {}, timeout=0.5)
                # rule count exhausted: next call goes through
                r = await c.call("Flaky", {}, timeout=10)
                assert r["ok"] and calls == [1]
                await c.close()
                await c0.close()
            finally:
                await server.stop()
        finally:
            _chaos.clear()

    asyncio.run(main())


@pytest.mark.fast
def test_upgrade_flush_and_adopt_on_shard():
    """The direct-channel upgrade handshake works from a shard loop, and
    the response is fully flushed (no busy-wait: _flush_transport rides
    the transport's flow-control signal) before the socket is adopted."""

    async def main():
        server = RpcServer("127.0.0.1", shards=2)
        adopted = {}

        def hook(payload):
            def adopt(sock):
                adopted["sock"] = sock

                def serve():
                    # trivial protocol on the adopted blocking socket (the
                    # real direct channel hands it to a thread the same way)
                    data = sock.recv(5)
                    sock.sendall(data.upper())

                threading.Thread(target=serve, daemon=True).start()

            return {"ok": True, "blob": b"z" * 200_000}, adopt

        server.set_upgrade_hook("Upgrade", hook)
        port = await server.start(0)
        try:
            c0 = RpcClient("127.0.0.1", port)
            await c0.connect()
            c = RpcClient("127.0.0.1", port)  # shard connection
            await c.connect()
            r = await c.call("Upgrade", {}, timeout=10)
            # the large response survived the pre-abort flush intact
            assert r["ok"] and len(r["blob"]) == 200_000
            # the connection is now a raw socket owned by the adopter —
            # talk over a blocking dup of the client fd off-loop. The
            # client's own transport still selects on the same socket: stop
            # it reading, or under load the loop takes the five bytes of the
            # reply before the blocking recv below does, which then waits
            # for ever (seen as this test's 10 s timeout, PR 25).
            c._writer.transport.pause_reading()
            raw = c._writer.get_extra_info("socket").dup()
            raw.setblocking(True)
            loop = asyncio.get_running_loop()

            def ping():
                raw.sendall(b"hello")
                return raw.recv(5)

            reply = await asyncio.wait_for(
                loop.run_in_executor(None, ping), 10)
            assert reply == b"HELLO"
            assert "sock" in adopted
            raw.close()
            await c0.close()
            try:
                await c.close()
            except Exception:
                pass
        finally:
            await server.stop()

    asyncio.run(main())


# --------------------------------------------- lease-grant batching (unit)


def _mini_node_manager(cpus=4.0):
    """A NodeManager skeleton with just the lease-pass state (no sockets,
    no plasma) — enough to drive _lease_grant_pass/_kick_waiters."""
    from ray_tpu._private.raylet.main import NodeManager
    from ray_tpu._private.raylet.resources import ResourceSet

    nm = NodeManager.__new__(NodeManager)
    nm.total = ResourceSet({"CPU": cpus})
    nm.available = ResourceSet({"CPU": cpus})
    nm.bundles = {}
    nm._resources_dirty = False
    nm._lease_waiters = []
    nm._lease_pass_scheduled = False
    nm._starve_limit = 3  # small so tests exercise the barrier quickly
    nm._rings = {}
    nm._ring_event = None
    return nm


def _waiter(res, strat=None):
    return {"event": asyncio.Event(), "res": dict(res),
            "strat": strat or {}, "skips": 0}


@pytest.mark.fast
def test_lease_pass_grants_fifo_and_batches():
    nm = _mini_node_manager(cpus=2.0)
    w1, w2, w3 = (_waiter({"CPU": 1}) for _ in range(3))
    nm._lease_waiters = [w1, w2, w3]
    nm._lease_grant_pass()
    # one pass granted the two that fit, FIFO order, left the third queued
    assert w1["event"].is_set() and "grant" in w1
    assert w2["event"].is_set() and "grant" in w2
    assert not w3["event"].is_set()
    assert nm._lease_waiters == [w3]
    assert nm.available.to_dict().get("CPU", 0) == 0


@pytest.mark.fast
def test_lease_pass_starvation_barrier():
    """A large waiter skipped `lease_starvation_passes` times becomes a
    FIFO barrier: later small waiters stop leapfrogging it, and fresh
    requests are told to queue behind it."""
    nm = _mini_node_manager(cpus=2.0)
    big = _waiter({"CPU": 2})
    nm.available.acquire(__import__(
        "ray_tpu._private.raylet.resources",
        fromlist=["ResourceSet"]).ResourceSet({"CPU": 1}))  # 1 of 2 busy
    nm._lease_waiters = [big]
    # passes 1..3: big can't fit (needs 2, 1 available) -> skips accumulate
    for expected_skips in (1, 2, 3):
        nm._lease_grant_pass()
        assert not big["event"].is_set()
        assert big["skips"] == expected_skips
    # big is now starving: a later small waiter may NOT leapfrog it even
    # though 1 CPU is free
    small = _waiter({"CPU": 1})
    nm._lease_waiters.append(small)
    nm._lease_grant_pass()
    assert not small["event"].is_set(), "small leapfrogged a starving waiter"
    # ...and fresh small requests are diverted into the queue too
    assert nm._blocked_by_starving({"CPU": 1}, {})
    # disjoint resources are unaffected by the barrier
    assert not nm._blocked_by_starving({"TPU": 1}, {})
    # the blocking release arrives: the very next pass serves BIG first
    nm.available.release(__import__(
        "ray_tpu._private.raylet.resources",
        fromlist=["ResourceSet"]).ResourceSet({"CPU": 1}))
    nm._lease_grant_pass()
    assert big["event"].is_set() and "grant" in big
    assert not small["event"].is_set()  # nothing left after the big grant


@pytest.mark.fast
def test_lease_waiter_abandon_returns_raced_grant():
    nm = _mini_node_manager(cpus=1.0)
    w = _waiter({"CPU": 1})
    nm._lease_waiters = [w]
    nm._lease_grant_pass()
    assert w["event"].is_set() and "grant" in w
    # the handler timed out before consuming the grant: abandon returns it

    async def drive():
        nm._waiter_abandon(w)

    asyncio.run(drive())
    assert nm.available.to_dict().get("CPU") == 1.0


@pytest.mark.fast
def test_kick_waiters_coalesces_into_one_pass():
    nm = _mini_node_manager(cpus=4.0)
    passes = []
    orig = nm._lease_grant_pass
    nm._lease_grant_pass = lambda: (passes.append(1), orig())

    async def drive():
        nm._lease_waiters = [_waiter({"CPU": 1}) for _ in range(3)]
        # K releases in one tick -> ONE scheduled pass
        for _ in range(5):
            nm._kick_waiters()
        await asyncio.sleep(0)  # let call_soon run

    asyncio.run(drive())
    assert sum(passes) == 1
    assert all(w["event"].is_set() for w in nm._lease_waiters) or \
        not nm._lease_waiters


# ------------------------------------------------------------- live layers


@pytest.fixture
def fresh_cluster():
    yield
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


def test_submit_ring_live_end_to_end(fresh_cluster):
    """Default config: eligible tiny tasks ride the ring; results land via
    the batched reply notify; nothing leaks in the pending table."""

    @ray_tpu.remote
    def f(x):
        return x * 2

    ray_tpu.init(num_cpus=4)
    from ray_tpu._private.worker import get_global_worker

    w = get_global_worker()
    assert ray_tpu.get([f.remote(i) for i in range(40)]) == \
        [2 * i for i in range(40)]
    deadline = time.time() + 10
    while w._ring is None and time.time() < deadline:
        time.sleep(0.05)
    assert w._ring is not None, "submit ring never attached"
    assert ray_tpu.get([f.remote(i) for i in range(400)]) == \
        [2 * i for i in range(400)]
    assert w._ring_submitted > 0, "no task rode the ring"
    assert not w._ring_pending, "ring reply leak"
    assert not w._ring_dead


def test_submit_ring_full_falls_back_to_rpc(fresh_cluster):
    """A deliberately tiny ring forces constant ring-full fallbacks; every
    task still completes exactly once with correct results."""
    os.environ["RTPU_submit_ring_slots"] = "1"  # ~1 KiB: a couple entries
    try:
        @ray_tpu.remote
        def f(x):
            return x + 7

        ray_tpu.init(num_cpus=4)
        from ray_tpu._private.worker import get_global_worker

        w = get_global_worker()
        assert ray_tpu.get([f.remote(i) for i in range(300)]) == \
            [i + 7 for i in range(300)]
        assert not w._ring_pending
    finally:
        os.environ.pop("RTPU_submit_ring_slots", None)


def test_submit_ring_disabled_via_flag(fresh_cluster):
    os.environ["RTPU_submit_ring_slots"] = "0"
    try:
        @ray_tpu.remote
        def f(x):
            return x

        ray_tpu.init(num_cpus=2)
        from ray_tpu._private.worker import get_global_worker

        w = get_global_worker()
        assert ray_tpu.get([f.remote(i) for i in range(50)]) == list(range(50))
        assert w._ring is None and w._ring_submitted == 0
    finally:
        os.environ.pop("RTPU_submit_ring_slots", None)


def test_large_lease_not_starved_by_small_stream(fresh_cluster):
    """Regression (satellite): a CPU-2 task queued behind a continuous
    stream of CPU-1 tasks that fit first must still get scheduled — the
    batched pass's starvation barrier guarantees it."""
    os.environ["RTPU_lease_starvation_passes"] = "4"
    try:
        ray_tpu.init(num_cpus=2)

        @ray_tpu.remote(num_cpus=1)
        def small():
            time.sleep(0.05)
            return 1

        @ray_tpu.remote(num_cpus=2)
        def big():
            return "BIG"

        # keep both slots churning with small tasks...
        stream = [small.remote() for _ in range(80)]
        time.sleep(0.1)
        # ...then ask for the whole node
        big_ref = big.remote()
        more = [small.remote() for _ in range(80)]
        assert ray_tpu.get(big_ref, timeout=60) == "BIG"
        assert sum(ray_tpu.get(stream + more, timeout=120)) == 160
    finally:
        os.environ.pop("RTPU_lease_starvation_passes", None)


def test_cluster_smoke_with_two_reactor_shards(fresh_cluster):
    """Whole-cluster smoke with RTPU_rpc_reactor_shards=2 in every process
    (driver, GCS, raylet, workers): tasks, actors, plasma round-trips and
    the submit ring all function across shard boundaries."""
    os.environ["RTPU_rpc_reactor_shards"] = "2"
    try:
        import numpy as np

        @ray_tpu.remote
        def f(x):
            return x + 1

        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        ray_tpu.init(num_cpus=4)
        from ray_tpu._private.worker import get_global_worker

        assert get_global_worker().server.num_shards == 2
        assert ray_tpu.get([f.remote(i) for i in range(200)]) == \
            list(range(1, 201))
        c = Counter.remote()
        assert ray_tpu.get([c.bump.remote() for _ in range(30)])[-1] == 30
        arr = np.arange(1 << 18)
        assert (ray_tpu.get(ray_tpu.put(arr)) == arr).all()
    finally:
        os.environ.pop("RTPU_rpc_reactor_shards", None)
