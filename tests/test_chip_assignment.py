"""Per-instance TPU chip assignment (reference: resource instance IDs
scheduling_ids.h:162 / GPU_0-style; TPU manager TPU_VISIBLE_CHIPS
_private/accelerators/tpu.py). Two concurrent TPU workers must never see the
same chip; chips must return to the pool when a lease ends. One process per
chip: on a node with chips a zero-TPU lease cannot reach them."""

import os
import shutil
import subprocess
import sys

import pytest

import ray_tpu
from ray_tpu._private import accelerators

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def tpu_cluster(monkeypatch):
    # what a host with chips has: the raylet, not the ambient env, must be
    # what keeps a zero-TPU worker off them
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    ray_tpu.init(num_cpus=4, num_tpus=4)
    yield
    ray_tpu.shutdown()


def test_concurrent_actors_get_disjoint_chips(tpu_cluster):
    @ray_tpu.remote(num_tpus=2)
    class Holder:
        def chips(self):
            return os.environ.get("TPU_VISIBLE_CHIPS", "")

    a = Holder.remote()
    b = Holder.remote()
    ca = ray_tpu.get(a.chips.remote(), timeout=60)
    cb = ray_tpu.get(b.chips.remote(), timeout=60)
    assert ca and cb
    sa, sb = set(ca.split(",")), set(cb.split(","))
    assert len(sa) == 2 and len(sb) == 2
    assert not (sa & sb), f"chip overlap: {ca} vs {cb}"
    assert sa | sb == {"0", "1", "2", "3"}
    ray_tpu.kill(a)
    ray_tpu.kill(b)


def test_chips_recycle_after_release(tpu_cluster):
    @ray_tpu.remote(num_tpus=4)
    def all_chips():
        return os.environ.get("TPU_VISIBLE_CHIPS", "")

    first = ray_tpu.get(all_chips.remote(), timeout=60)
    assert set(first.split(",")) == {"0", "1", "2", "3"}
    # lease released after the task; the full pool must be reusable
    second = ray_tpu.get(all_chips.remote(), timeout=60)
    assert set(second.split(",")) == {"0", "1", "2", "3"}


def test_fractional_tpu_shares_pool(tpu_cluster):
    @ray_tpu.remote(num_tpus=0.5)
    def frac():
        return os.environ.get("TPU_VISIBLE_CHIPS", "unset")

    # fractional demand gets no exclusive assignment (shares the node view)
    assert ray_tpu.get(frac.remote(), timeout=60) == "unset"


def test_runtime_context_accelerator_ids(tpu_cluster):
    @ray_tpu.remote(num_tpus=1)
    def ids():
        return ray_tpu.get_runtime_context().get_accelerator_ids()

    out = ray_tpu.get(ids.remote(), timeout=60)
    assert out.get("TPU") in (["0"], [0], ["1"], [1], ["2"], [2], ["3"], [3])


@pytest.mark.parametrize("chips, bounds", [
    ([0], "1,1,1"), ([2, 3], "1,2,1"), ([0, 1, 2, 3], "2,2,1"),
])
def test_visible_chip_env_describes_its_chips(chips, bounds):
    assert accelerators.visible_chip_env(chips) == {
        "TPU_VISIBLE_CHIPS": ",".join(map(str, chips)),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def test_visible_chip_env_unknown_grid_names_chips_only():
    assert accelerators.visible_chip_env([0, 1, 3]) == {"TPU_VISIBLE_CHIPS": "0,1,3"}


@pytest.mark.parametrize("accel, vfio, want", [
    (4, 0, 4),   # TPU VM: one /dev/accel* per chip
    (0, 1, 1),   # VFIO passthrough, one chip attached
    (0, 4, 4),
    (0, 0, 0),   # only the /dev/vfio/vfio control node, or nothing
])
def test_num_tpu_chips_from_device_nodes(monkeypatch, accel, vfio, want):
    listing = {
        "/dev/accel*": [f"/dev/accel{i}" for i in range(accel)],
        "/dev/vfio/[0-9]*": [f"/dev/vfio/{i}" for i in range(vfio)],
    }
    monkeypatch.delenv("RTPU_num_tpu_chips", raising=False)
    monkeypatch.setattr(accelerators.glob, "glob", lambda pat: listing[pat])
    assert accelerators.num_tpu_chips() == want


def test_zero_tpu_task_cannot_pick_the_tpu(tpu_cluster):
    @ray_tpu.remote(num_cpus=1)
    def view():
        import jax

        return (os.environ.get("JAX_PLATFORMS"),
                os.environ.get("TPU_VISIBLE_CHIPS", "unset"),
                jax.config.jax_platforms, jax.devices()[0].platform)

    assert ray_tpu.get(view.remote(), timeout=120) == ("cpu", "unset", "cpu", "cpu")


def test_four_chip_lease_sees_four(tpu_cluster):
    @ray_tpu.remote(num_tpus=4)
    def view():
        return {k: os.environ.get(k) for k in (
            "TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
            "TPU_PROCESS_BOUNDS", "JAX_PLATFORMS")}

    assert ray_tpu.get(view.remote(), timeout=60) == {
        "TPU_VISIBLE_CHIPS": "0,1,2,3",
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "2,2,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "JAX_PLATFORMS": "tpu,cpu",  # the node's own: the lease may use the TPU
    }


def test_node_without_chips_leaves_worker_env_alone():
    ray_tpu.init(num_cpus=2, num_tpus=0)
    try:
        @ray_tpu.remote
        def view():
            return os.environ.get("JAX_PLATFORMS")

        assert ray_tpu.get(view.remote(), timeout=60) == os.environ["JAX_PLATFORMS"]
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in_checkout", "script_alone"])
def test_chip_smoke_fails_without_a_chip(tmp_path, alone):
    """The default invocation where jax finds no accelerator — and in a
    directory holding nothing else of the repo — exits non-zero and never
    prints the ok line."""
    script = os.path.join(REPO, "chip_smoke.py")
    cwd = REPO
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if alone:
        script = shutil.copy(script, tmp_path)
        cwd = str(tmp_path)
        env.pop("PYTHONPATH", None)
    env.pop("RTPU_num_tpu_chips", None)
    r = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=150)
    assert r.returncode != 0, r.stdout
    assert '"ok": true' not in r.stdout


def test_host_freeze_does_not_kill_the_node():
    """libtpu bringing up four chips stops every process on the host for up
    to 13 s at a time (PR 22). A health checker that was itself frozen has
    seen nothing: the node, and the actors on it, must outlive the freeze
    (threshold: 5 beats of 1 s)."""
    import signal
    import time

    from ray_tpu import api

    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        c = Counter.remote()
        assert ray_tpu.get(c.bump.remote(), timeout=60) == 1
        procs = list(api._local_node.processes.values())
        for p in procs:
            p.send_signal(signal.SIGSTOP)
        time.sleep(7)
        for p in procs:
            p.send_signal(signal.SIGCONT)
        time.sleep(2.5)  # two health checks after the thaw
        assert [n["Alive"] for n in ray_tpu.nodes()] == [True]
        assert ray_tpu.get(c.bump.remote(), timeout=60) == 2
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize("chips", [
    # 165 s: the one-chip loop runs every kernel's check against the plain
    # lines in interpret mode, each of which its own test file holds, fast
    pytest.param(1, marks=[pytest.mark.slow, pytest.mark.timeout(300)]), 4])
def test_chip_smoke_rehearsal_passes_and_never_says_ok(chips):
    """--rehearse drives the smoke's control flow on CPU devices (tiny model,
    pallas in interpret mode) through init -> TPU lease -> JaxTrainer ->
    TrainStep; it must pass here before chip time is spent (`-m "slow or not
    slow"` for the one-chip loop too), and must never end in the ok line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--rehearse",
         "--chips", str(chips)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=280)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert '"ok"' not in r.stdout
    last = r.stdout.strip().splitlines()[-1]
    assert last == ('{"rehearsal": "passed", "device": {"platform": "cpu", '
                    f'"kind": "cpu", "count": {chips}}}}}')
