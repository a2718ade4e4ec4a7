"""Multi-node tests over cluster_utils.Cluster
(modeled on reference python/ray/tests/test_multi_node.py)."""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.util.placement_group import placement_group, remove_placement_group
from ray_tpu.util.scheduling_strategies import (
    NodeAffinitySchedulingStrategy,
    PlacementGroupSchedulingStrategy,
)


@ray_tpu.remote
def node_of():
    return ray_tpu.get_runtime_context().get_node_id()


@pytest.fixture(scope="module")
def three_node_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(
        initialize_head=True, head_node_args={"resources": {"CPU": 2}}
    )
    cluster.add_node(resources={"CPU": 2, "special": 1})
    cluster.add_node(resources={"CPU": 2})
    cluster.wait_for_nodes()
    ray_tpu.init(address=cluster.address)
    time.sleep(1.0)
    yield cluster
    cluster.shutdown()


def test_cluster_visible(three_node_cluster):
    assert len([n for n in ray_tpu.nodes() if n["Alive"]]) == 3
    assert ray_tpu.cluster_resources()["CPU"] == 6.0


def test_custom_resource_routing(three_node_cluster):
    @ray_tpu.remote(resources={"special": 1})
    def special():
        return ray_tpu.get_runtime_context().get_node_id()

    nid = ray_tpu.get(special.remote())
    info = next(n for n in ray_tpu.nodes() if n["NodeID"] == nid)
    assert info["Resources"].get("special") == 1.0


def test_tasks_spread_across_nodes(three_node_cluster):
    @ray_tpu.remote
    def spot(t):
        time.sleep(t)
        return ray_tpu.get_runtime_context().get_node_id()

    t0 = time.time()
    nodes_used = ray_tpu.get([spot.remote(2) for _ in range(6)])
    assert len(set(nodes_used)) >= 2
    assert time.time() - t0 < 8


def test_cross_node_object_transfer(three_node_cluster):
    @ray_tpu.remote(resources={"special": 0.5})
    def produce():
        return np.ones((1200, 1200), dtype=np.float32)

    @ray_tpu.remote
    def consume(a):
        return float(a.sum())

    ref = produce.remote()
    assert ray_tpu.get(consume.remote(ref)) == 1200 * 1200
    # driver-side pull of the same remote object
    assert ray_tpu.get(ref).shape == (1200, 1200)


def test_node_affinity(three_node_cluster):
    target = [n for n in ray_tpu.nodes() if not n["IsHead"]][0]["NodeID"]
    nid = ray_tpu.get(
        node_of.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(target)
        ).remote()
    )
    assert nid == target


def test_strict_spread_pg(three_node_cluster):
    pg = placement_group([{"CPU": 1}] * 3, strategy="STRICT_SPREAD")
    assert pg.wait(30)
    nodes = ray_tpu.get(
        [
            node_of.options(
                scheduling_strategy=PlacementGroupSchedulingStrategy(pg, i)
            ).remote()
            for i in range(3)
        ]
    )
    assert len(set(nodes)) == 3
    remove_placement_group(pg)


def test_strict_pack_pg(three_node_cluster):
    pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="STRICT_PACK")
    assert pg.wait(30)
    nodes = ray_tpu.get(
        [
            node_of.options(
                scheduling_strategy=PlacementGroupSchedulingStrategy(pg, i)
            ).remote()
            for i in range(2)
        ]
    )
    assert len(set(nodes)) == 1
    remove_placement_group(pg)


def test_pg_infeasible_stays_pending(three_node_cluster):
    pg = placement_group([{"CPU": 100}], strategy="PACK")
    assert not pg.wait(1.5)


def test_actor_on_remote_node(three_node_cluster):
    @ray_tpu.remote(resources={"special": 1})
    class Pinned:
        def where(self):
            return ray_tpu.get_runtime_context().get_node_id()

    p = Pinned.remote()
    nid = ray_tpu.get(p.where.remote())
    info = next(n for n in ray_tpu.nodes() if n["NodeID"] == nid)
    assert info["Resources"].get("special") == 1.0


def test_node_death_detected(three_node_cluster):
    cluster = three_node_cluster
    victim = cluster.nodes[-1]
    victim_id = victim.node_id.hex()
    victim.kill_raylet()
    deadline = time.time() + 30
    while time.time() < deadline:
        info = {n["NodeID"]: n["Alive"] for n in ray_tpu.nodes()}
        if info.get(victim_id) is False:
            break
        time.sleep(0.5)
    else:
        pytest.fail("node death not detected")



def test_lease_request_survives_spill_to_a_dead_node():
    """Between a raylet's death and the GCS missing its heartbeats the cluster
    view still offers it as a spill target. The refused connection must send
    the lease request back to the local raylet, not end it and leave the
    queued tasks without one (they then hung for good)."""
    import asyncio
    import types

    from ray_tpu._private.worker import CoreWorker, _LeaseState

    replies = [{"spill": {"ip": "127.0.0.1", "port": 1}}, {"error": "stop"}]
    failed = []

    class Raylet:
        async def call(self, method, req, timeout=None):
            return replies.pop(0)

    class Pool:
        async def get(self, ip, port):
            raise ConnectionRefusedError(111, "Connect call failed")

    core = types.SimpleNamespace(
        raylet=Raylet(), pool=Pool(),
        _fail_task=lambda spec, err: failed.append(str(err)))
    core._request_lease = types.MethodType(CoreWorker._request_lease, core)
    state = _LeaseState()
    state.queue.append({"resources": {"CPU": 1}, "strategy": {}, "job_id": b"j"})
    state.requests_in_flight = 1

    async def drive():
        await core._request_lease("key", state)
        for _ in range(50):
            if failed:
                break
            await asyncio.sleep(0.05)

    asyncio.run(drive())
    assert failed == ["stop"]  # the second request reached the local raylet
    assert state.requests_in_flight == 0 and not state.queue
