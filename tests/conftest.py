"""Shared fixtures (modeled on reference python/ray/tests/conftest.py).

JAX-related tests run on a virtual 8-device CPU mesh: the env vars must be set
before jax is first imported anywhere in the process.
"""

import math
import os
import sys

# Force CPU: the ambient env may point JAX_PLATFORMS at real TPU hardware,
# but tests must run chip-free on the virtual 8-device mesh (SURVEY.md §4).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)
# Validate every RPC payload against the typed wire contracts
# (_private/schema.py) in all cluster tests — contract drift fails loudly.
os.environ.setdefault("RTPU_VALIDATE_RPC", "1")
# One dashboard-agent process per raylet is pure boot cost on a 1-core CI
# box; tests that exercise the agent re-enable it explicitly (test_agent.py).
os.environ.setdefault("RTPU_dashboard_agent", "0")

# A pytest plugin may have imported jax before this file ran, baking the
# ambient JAX_PLATFORMS into its config; override it (backends are lazy, so
# this works as long as no array has touched a device yet).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

# ---------------------------------------------------------------- timeout
# The reference sets a 180 s default timeout in pytest.ini so one hung test
# cannot brick CI. pytest-timeout isn't available in this image, so use
# SIGALRM: it interrupts the main thread even when it is blocked in a
# syscall (socket recv, poll loop), raising in the test body.

_TEST_TIMEOUT_S = int(os.environ.get("RAY_TPU_TEST_TIMEOUT", "180"))


def _item_timeout(item):
    # @pytest.mark.timeout(N) overrides the default, mirroring pytest-timeout's
    # marker contract (which isn't installed in this image).
    mark = item.get_closest_marker("timeout")
    if mark:
        value = mark.args[0] if mark.args else mark.kwargs.get("timeout")
        if value is not None:
            # signal.alarm(0) would CANCEL the alarm; round fractions up.
            return max(1, math.ceil(value))
    return _TEST_TIMEOUT_S


def _install_alarm(phase, item):
    import faulthandler
    import signal

    deadline = _item_timeout(item)

    def _abort(signum, frame):
        faulthandler.dump_traceback()
        raise TimeoutError(
            f"{item.nodeid} {phase} exceeded {deadline}s timeout"
        )

    old = signal.signal(signal.SIGALRM, _abort)
    signal.alarm(deadline)
    return old


def _clear_alarm(old):
    import signal

    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    old = _install_alarm("setup", item)
    try:
        yield
    finally:
        _clear_alarm(old)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    old = _install_alarm("call", item)
    try:
        yield
    finally:
        _clear_alarm(old)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    old = _install_alarm("teardown", item)
    try:
        yield
    finally:
        _clear_alarm(old)


@pytest.fixture
def ray_start_regular():
    """Boot a real single-node cluster for the duration of one test
    (reference: conftest.py ray_start_regular :419)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_2_cpus():
    import ray_tpu

    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def shutdown_only():
    yield
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-raylet-on-one-machine cluster (reference: cluster_utils.Cluster)."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=False)
    yield cluster
    cluster.shutdown()


# ------------------------------------------- a described (not attached) TPU
# tests/test_tpu_compile*.py compile for it. Module-scoped: every file that
# asks keeps the compilation cache off round its own tests.

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Such a compile is written to the persistent cache but cannot be read
    # back without a chip: keep the cache off round these tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
