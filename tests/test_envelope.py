"""Scalability-envelope regression floors (scaled-down port of the
reference's release/benchmarks/README.md:9-31 suite: many tasks, many
actors, many placement groups, object broadcast, many args). Runs against a
real 4-raylet cluster on one machine and asserts coarse floors: the goal is
catching regressions in completion and fan-out behavior, not absolute rates
(nothing here is a measurement of the system; those are in PERF.md)."""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util.broadcast import broadcast_object
from ray_tpu.util.placement_group import (
    placement_group,
    remove_placement_group,
)

N_TASKS = 5_000
N_ACTORS = 200
N_PGS = 50
BCAST_MB = 64
N_ARGS = 1_000


def run() -> dict:
    results = {}

    cluster = Cluster(
        initialize_head=True, head_node_args={"resources": {"CPU": 4}}
    )
    for _ in range(3):
        cluster.add_node(resources={"CPU": 2})
    cluster.wait_for_nodes()
    ray_tpu.init(address=cluster.address)
    try:
        # ---- queued-task drain (reference: 1M+ queued tasks) ----
        @ray_tpu.remote
        def tiny():
            return 1

        ray_tpu.get(tiny.remote())
        t0 = time.perf_counter()
        refs = [tiny.remote() for _ in range(N_TASKS)]
        t_submit = time.perf_counter() - t0
        ray_tpu.get(refs)
        t_total = time.perf_counter() - t0
        results["queued_tasks"] = {
            "n": N_TASKS,
            "submit_per_s": round(N_TASKS / t_submit, 1),
            "drain_per_s": round(N_TASKS / t_total, 1),
        }
        print(f"queued_tasks: {results['queued_tasks']}")
        del refs

        # ---- many actors (reference: 40k+ across a cluster) ----
        @ray_tpu.remote(num_cpus=0.001)
        class A:
            def ping(self):
                return 1

        t0 = time.perf_counter()
        actors = [A.remote() for _ in range(N_ACTORS)]
        ray_tpu.get([a.ping.remote() for a in actors])
        dt = time.perf_counter() - t0
        results["many_actors"] = {
            "n": N_ACTORS, "create_and_ping_per_s": round(N_ACTORS / dt, 1),
        }
        print(f"many_actors: {results['many_actors']}")
        for a in actors:
            ray_tpu.kill(a)
        del actors

        # ---- many placement groups (reference: 1k+ simultaneous) ----
        t0 = time.perf_counter()
        pgs = [
            placement_group([{"CPU": 0.001}]) for _ in range(N_PGS)
        ]
        for pg in pgs:
            pg.ready()
        dt = time.perf_counter() - t0
        results["many_pgs"] = {
            "n": N_PGS, "create_per_s": round(N_PGS / dt, 1),
        }
        t0 = time.perf_counter()
        for pg in pgs:
            remove_placement_group(pg)
        results["many_pgs"]["remove_per_s"] = round(
            N_PGS / (time.perf_counter() - t0), 1
        )
        print(f"many_pgs: {results['many_pgs']}")

        # ---- object broadcast (reference: 1 GiB to 50+ nodes) ----
        data = np.zeros(BCAST_MB * 1024 * 1024 // 8, dtype=np.float64)
        ref = ray_tpu.put(data)
        t0 = time.perf_counter()
        stats = broadcast_object(ref)
        dt = time.perf_counter() - t0
        srcs = {s for s, _ in stats["transfers"]}
        results["broadcast"] = {
            "mb": BCAST_MB,
            "nodes": len(stats["nodes"]),
            "seconds": round(dt, 2),
            "mb_per_s": round(BCAST_MB * len(stats["transfers"]) / dt, 1),
            "rounds": stats["rounds"],
            "distinct_sources": len(srcs),
        }
        print(f"broadcast: {results['broadcast']}")
        assert len(srcs) >= 2, "broadcast must fan out from >=2 sources"
        del ref, data

        # ---- many args to one task (reference: 10k+ args) ----
        @ray_tpu.remote
        def consume(*args):
            return len(args)

        t0 = time.perf_counter()
        assert ray_tpu.get(consume.remote(*range(N_ARGS))) == N_ARGS
        results["many_args"] = {
            "n": N_ARGS,
            "seconds": round(time.perf_counter() - t0, 3),
        }
        print(f"many_args: {results['many_args']}")
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
    return results


def test_envelope_quick_floors():
    r = run()

    # queued-task drain completes and sustains a sane rate
    assert r["queued_tasks"]["n"] == 5_000
    assert r["queued_tasks"]["drain_per_s"] > 300

    # hundreds of actors all come up and answer
    assert r["many_actors"]["n"] == 200
    assert r["many_actors"]["create_and_ping_per_s"] > 2

    # PG churn
    assert r["many_pgs"]["create_per_s"] > 30
    assert r["many_pgs"]["remove_per_s"] > 30

    # broadcast reaches every node via tree fan-out (>=2 sources, <=N-1
    # transfers, log rounds) — the push path, not N serial pulls
    b = r["broadcast"]
    assert b["nodes"] == 4
    assert b["distinct_sources"] >= 2
    assert b["rounds"] <= 2

    # thousands of args to one task in bounded time
    assert r["many_args"]["n"] == 1_000
    assert r["many_args"]["seconds"] < 10
