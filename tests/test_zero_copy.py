"""Zero-copy data plane: single-copy put, out-of-band RPC frames, and
copy-free chunked transfer.

The acceptance contract is structural, not timing-based: the put path and
the chunk send path must never materialize an out-of-band buffer as Python
bytes — asserted here by buffer identity (np.shares_memory) and by the
"_oob" landed-in-place markers of the RPC layer, at the put-bandwidth
rep's own size too (the bottom of this file).
"""

import asyncio
import hashlib

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import rpc as rpc_mod
from ray_tpu._private import serialization
from ray_tpu._private.rpc import (
    OobPayload,
    RpcClient,
    RpcServer,
    _pack_oob,
)


# --------------------------------------------------------------- rpc frames


@pytest.mark.fast
def test_pack_oob_no_copy():
    """The frame builder returns the caller's buffer view itself — the bulk
    bytes are never copied into the packed header."""
    arr = np.arange(1_000_000, dtype=np.uint8)
    view = memoryview(arr)
    hdr, mv = _pack_oob(rpc_mod.MSG_REQUEST_OOB, 7, "ReceiveChunk",
                        {"offset": 0}, view)
    assert mv is view  # identity: zero copies on the send side
    assert len(hdr) < 100  # header is just the msgpack envelope
    # a bytes-like that is not a memoryview gets wrapped, not copied
    buf = bytearray(b"x" * 4096)
    hdr2, mv2 = _pack_oob(rpc_mod.MSG_RESPONSE_OOB, 1, None, {}, buf)
    assert isinstance(mv2, memoryview) and mv2.obj is buf
    assert np.shares_memory(np.frombuffer(mv2, dtype=np.uint8),
                            np.frombuffer(buf, dtype=np.uint8))


@pytest.mark.fast
def test_oob_request_lands_in_sink_buffer():
    """An OOB request's payload streams from the socket straight into the
    buffer the server's sink provides; the handler sees only the int
    byte-count marker (proof nothing was buffered on the heap)."""

    async def main():
        landing = bytearray(1 << 20)
        seen = {}
        done_calls = []

        def sink(payload, nbytes):
            seen["sink"] = (dict(payload), nbytes)
            return (memoryview(landing)[payload["offset"]:
                                        payload["offset"] + nbytes],
                    lambda ok: done_calls.append(ok))

        async def handler(payload):
            seen["handler"] = payload
            return {"ok": True, "oob_was": payload.get("_oob")}

        server = RpcServer("127.0.0.1")
        server.register("Land", handler)
        server.set_oob_sink("Land", sink)
        port = await server.start(0)
        client = RpcClient("127.0.0.1", port)
        await client.connect()

        data = np.arange(512 * 1024, dtype=np.uint8)
        r = await client.call("Land", {"offset": 4096},
                              oob=memoryview(data), timeout=10)
        assert r["ok"] and r["oob_was"] == data.nbytes
        assert seen["handler"]["_oob"] == data.nbytes  # int marker: landed
        assert done_calls == [True]
        assert bytes(landing[4096:4096 + data.nbytes]) == data.tobytes()

        # no sink match (bad offset) -> payload buffers into a bytearray,
        # stream stays framed, handler still runs
        def sink_reject(payload, nbytes):
            return None

        server.set_oob_sink("Land", sink_reject)
        r = await client.call("Land", {"offset": 0},
                              oob=b"hello world", timeout=10)
        assert bytes(r["oob_was"]) == b"hello world"

        await client.close()
        await server.stop()

    asyncio.run(main())


@pytest.mark.fast
def test_oob_response_lands_in_client_buffer():
    """A handler returning OobPayload streams its buffer raw; the client's
    oob_dest receives it in place (the pull path's chunk landing)."""

    async def main():
        src = np.arange(256 * 1024, dtype=np.uint8)
        released = []

        async def handler(payload):
            return OobPayload({"found": True}, memoryview(src),
                              release=lambda: released.append(True))

        server = RpcServer("127.0.0.1")
        server.register("Fetch", handler)
        port = await server.start(0)
        client = RpcClient("127.0.0.1", port)
        await client.connect()

        dest = bytearray(src.nbytes)
        r = await client.call("Fetch", {}, timeout=10,
                              oob_dest=memoryview(dest))
        assert r["found"] and r["_oob"] == src.nbytes  # landed in dest
        assert bytes(dest) == src.tobytes()
        assert released == [True]  # handler's pin released after flush

        # without oob_dest the payload still arrives (buffered fallback)
        r = await client.call("Fetch", {}, timeout=10)
        assert bytes(r["_oob"]) == src.tobytes()

        # interleave OOB with plain requests on one connection: framing holds
        async def plain(payload):
            return {"echo": payload["x"]}

        server.register("Plain", plain)
        dest2 = bytearray(src.nbytes)
        results = await asyncio.gather(
            client.call("Fetch", {}, timeout=10, oob_dest=memoryview(dest2)),
            client.call("Plain", {"x": 42}, timeout=10),
            client.call("Plain", {"x": 43}, timeout=10),
        )
        assert results[0]["_oob"] == src.nbytes
        assert bytes(dest2) == src.tobytes()
        assert [results[1]["echo"], results[2]["echo"]] == [42, 43]

        await client.close()
        await server.stop()

    asyncio.run(main())


@pytest.mark.fast
def test_oob_zero_length_payload():
    """Zero-byte OOB payloads (empty tail chunk edge) keep the stream
    framed on both directions."""

    async def main():
        async def handler(payload):
            return OobPayload({"n": payload["_oob"]}, b"")

        server = RpcServer("127.0.0.1")
        server.register("Zero", handler)
        port = await server.start(0)
        client = RpcClient("127.0.0.1", port)
        await client.connect()
        r = await client.call("Zero", {}, oob=b"", timeout=10)
        assert bytes(r["n"]) == b"" and bytes(r["_oob"]) == b""
        r = await client.call("Zero", {}, oob=b"", timeout=10)
        assert bytes(r["n"]) == b""
        await client.close()
        await server.stop()

    asyncio.run(main())


# ---------------------------------------------------------------- put path


def test_put_streams_raw_buffers_into_plasma(ray_start_regular):
    """ray.put of a plasma-bound array hands write_blob the RAW protocol-5
    buffer aliasing the user's array — buffer identity, not timing, is the
    zero-copy proof (a reintroduced bytes() breaks shares_memory)."""
    captured = []
    orig = serialization.write_blob

    def spy(dest, pickle_bytes, buffers):
        captured.append(list(buffers))
        return orig(dest, pickle_bytes, buffers)

    arr = np.arange(2 * 1024 * 1024 // 8, dtype=np.float64)  # 2 MiB
    arr_bytes = arr.view(np.uint8)
    serialization.write_blob, write_blob = spy, orig
    try:
        ref = ray_tpu.put(arr)
    finally:
        serialization.write_blob = write_blob
    assert len(captured) == 1 and len(captured[0]) == 1
    buf = captured[0][0]
    assert not isinstance(buf, (bytes, bytearray))
    alias = np.frombuffer(memoryview(buf).cast("B"), dtype=np.uint8)
    assert np.shares_memory(alias, arr_bytes)
    # and the stored object reads back intact (zero-copy view of plasma)
    out = ray_tpu.get(ref)
    assert np.array_equal(out, arr)


def test_large_task_return_streams_raw_buffers(ray_start_regular):
    """Large task returns ride the same single-copy path: value -> plasma,
    no intermediate bytes of the array on the worker heap."""

    @ray_tpu.remote
    def make():
        return np.full(1_000_000, 3.25)  # 8 MB -> plasma

    out = ray_tpu.get(make.remote())
    assert out.shape == (1_000_000,) and float(out[0]) == 3.25
    # the value aliases the store (zero-copy get): read-only-safe check
    # that its deep base is a memoryview over shared memory, not a heap copy
    base = out
    while getattr(base, "base", None) is not None:
        base = base.base
    assert isinstance(base, memoryview)


def test_zero_copy_get_pin_survives_store_churn(ray_start_regular):
    """A value read zero-copy from plasma stays intact while later puts
    evict/spill around it — the pin must ride the value's actual buffer
    retention chain (regression: the finalizer used to sit on the
    PickleBuffer, which numpy drops at unpickle time, so the store could
    recycle pinned memory under churn)."""
    rng = np.random.default_rng(3)
    arr = rng.integers(0, 255, size=2 * 1024 * 1024, dtype=np.uint8)
    ref = ray_tpu.put(arr)
    out = ray_tpu.get(ref)
    # churn the store with ~3x its working set of unrelated objects
    for i in range(24):
        ray_tpu.put(rng.integers(0, 255, size=8 * 1024 * 1024, dtype=np.uint8))
    assert np.array_equal(out, arr)


# --------------------------------------------- two-raylet chunked transfer


@pytest.fixture
def two_nodes_small_chunks(monkeypatch):
    """Head + one worker node with a 64 KiB transfer chunk so moderate
    objects span many chunks (chunk-boundary coverage without big data)."""
    from ray_tpu.cluster_utils import Cluster

    monkeypatch.setenv("RTPU_object_manager_chunk_size", str(64 * 1024))
    cluster = Cluster(
        initialize_head=True, head_node_args={"resources": {"CPU": 2}}
    )
    cluster.add_node(resources={"CPU": 1, "n0": 1})
    cluster.wait_for_nodes()
    ray_tpu.init(address=cluster.address)
    yield cluster
    ray_tpu.shutdown()
    cluster.shutdown()


def test_push_integrity_across_chunk_boundaries(two_nodes_small_chunks):
    """PushObject over out-of-band frames: byte-for-byte integrity of an
    object spanning many chunks with a ragged tail (off-by-one at any
    chunk boundary, or a mislanded offset, flips the digest)."""
    from ray_tpu._private.worker import get_global_worker

    chunk = 64 * 1024
    n = 17 * chunk + 4321  # 17 full chunks + ragged tail
    data = (np.arange(n, dtype=np.int64) % 251).astype(np.uint8)
    ref = ray_tpu.put(data)
    want = hashlib.sha256(data.tobytes()).hexdigest()

    worker = get_global_worker()
    oid = ref.object_id()

    async def push():
        nodes = await worker.gcs_aio.get_all_node_info()
        by_res = {}
        for node in nodes:
            by_res[node["node_id"]] = node
        src = worker.node_id.binary()
        dst = next(nid for nid in by_res if nid != src)
        client = await worker.pool.get(
            by_res[src]["ip"], by_res[src]["raylet_port"]
        )
        return dst, await client.call(
            "PushObject",
            {"object_id": oid.binary(), "target": dst,
             "owner_addr": list(worker.address)},
            timeout=120,
        )

    dst, reply = worker.io.run(push())
    assert reply.get("ok"), reply

    # read it back ON the target node (no further transfer: n0 resource)
    @ray_tpu.remote(resources={"n0": 1})
    def digest(v):
        import hashlib as _h

        return _h.sha256(np.asarray(v).tobytes()).hexdigest()

    assert ray_tpu.get(digest.remote(ref), timeout=120) == want


def test_pull_integrity_across_chunk_boundaries(two_nodes_small_chunks):
    """The pull path (FetchChunk out-of-band responses landing straight in
    the puller's plasma buffer) reassembles a multi-chunk object exactly."""
    chunk = 64 * 1024
    n = 9 * chunk + 1  # 9 chunks + 1-byte tail: worst-case ragged boundary
    rng = np.random.default_rng(11)
    data = rng.integers(0, 255, size=n, dtype=np.uint8)
    ref = ray_tpu.put(data)
    want = hashlib.sha256(data.tobytes()).hexdigest()

    @ray_tpu.remote(resources={"n0": 1})
    def digest(v):
        import hashlib as _h

        return _h.sha256(np.asarray(v).tobytes()).hexdigest()

    # dependency resolution on n0 pulls the object chunk-by-chunk
    assert ray_tpu.get(digest.remote(ref), timeout=120) == want


# ------------------------------------------------------ the bench's put size


def test_large_puts_take_the_single_copy_path_every_time(ray_start_regular, monkeypatch):
    """The put-bandwidth rep's 64 MiB array, put again and again: every put
    hands write_blob one raw buffer that aliases the user's array and a
    pickle of a few hundred bytes beside it, and the store gives the last
    one back whole after the earlier ones went. What a rate on this host's
    clock guarded (a copy come back into the fast path) is held by the
    buffer's identity; a CPU run gives no rate."""
    captured = []
    orig = serialization.write_blob

    def spy(dest, pickle_bytes, buffers):
        captured.append((len(pickle_bytes), list(buffers)))
        return orig(dest, pickle_bytes, buffers)

    big = np.arange(64 * 1024 * 1024 // 8, dtype=np.float64)  # 64 MiB
    big_bytes = big.view(np.uint8)
    monkeypatch.setattr(serialization, "write_blob", spy)
    for _ in range(4):
        ref = ray_tpu.put(big)  # the one before it goes with its only reference
    monkeypatch.undo()
    assert len(captured) == 4
    for in_band, buffers in captured:
        assert in_band < 4096 and len(buffers) == 1, (in_band, len(buffers))
        raw = memoryview(buffers[0]).cast("B")
        assert raw.nbytes == big.nbytes
        assert np.shares_memory(np.frombuffer(raw, dtype=np.uint8), big_bytes)
    assert np.array_equal(ray_tpu.get(ref), big)
