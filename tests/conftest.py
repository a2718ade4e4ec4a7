"""Shared fixtures (modeled on reference python/ray/tests/conftest.py).

JAX-related tests run on a virtual 8-device CPU mesh: the env vars must be set
before jax is first imported anywhere in the process.
"""

import contextlib
import math
import os
import shutil
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

# XLA:CPU's loader prints two `cpu_aot_loader.cc` E lines for every program it
# reads from the compilation cache (pytest_configure below): they must not
# reach the log the driver counts dots in, where a line of dots with text
# after it is not counted. Set it to 0 by hand to see XLA's own complaints.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

# A pytest plugin may have imported jax before this file ran, baking the
# ambient JAX_PLATFORMS into its config; override it (backends are lazy, so
# this works as long as no array has touched a device yet).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# A test's programs run once or twice: XLA:CPU optimising them costs more
# than it saves. The `topo` fixture puts the optimiser back for its compiles.
jax.config.update("jax_disable_most_optimizations", True)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

# ------------------------------------------------------- a cache for run two
# A run's programs are kept under the user's cache directory, outside the
# tree, and the next run reads back those it still has (`PERF.md` section 7
# has a whole run's seconds, cold and warm). The key is jax's own (program,
# options, jaxlib), so a run's verdicts do not depend on the directory, only
# its seconds do.
# jax's own bound (`jax_compilation_cache_max_size`) is not used: with it
# every write lists the whole directory under a lock the six workers share.
# A directory over the bound is emptied instead, once, before the workers start.

_CACHE_BOUND = 2 << 30


def pytest_configure(config):
    cache = jax.config.jax_compilation_cache_dir  # JAX_COMPILATION_CACHE_DIR, where set
    if cache is None:
        cache = os.path.join(os.path.expanduser("~"), ".cache", "ray_tpu", "tier1-xla")
        jax.config.update("jax_compilation_cache_dir", cache)
        if not hasattr(config, "workerinput") and os.path.isdir(cache):
            with os.scandir(cache) as entries:
                if sum(e.stat().st_size for e in entries) > _CACHE_BOUND:
                    shutil.rmtree(cache, ignore_errors=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


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


# The one rule for `slow`: tier-1 (`-m 'not slow'`) is what fits the default
# deadline with room. A case that takes over half of it under the driver's
# command (/root/TESTS_LAST_RUN.json) is marked `slow` and may then ask for a
# longer deadline; a case that asks for one and is not `slow` is refused here,
# as a collection error of its file.

@pytest.hookimpl(hookwrapper=True)
def pytest_pycollect_makeitem(collector):
    made = (yield).get_result()
    for item in made if isinstance(made, list) else [made]:
        if not isinstance(item, pytest.Item) or item.get_closest_marker("slow"):
            continue
        asked = _item_timeout(item)
        if asked > _TEST_TIMEOUT_S:
            raise collector.CollectError(
                f"{item.nodeid} asks for a deadline of {asked} s, over the default "
                f"{_TEST_TIMEOUT_S}: only a case marked `slow` may (tests/conftest.py)")


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


# ------------------------------------------------------------------ order
# `--dist loadfile` hands out whole files in the order collected. The files
# that compile for the described TPU are a few long tests each, the worst
# thing to hand out last: they lead, every other file keeps the alphabet's
# order behind them, and nothing moves within a file. Every worker sorts
# alike, so xdist's check that all collected one list holds.

_DESCRIBED_TPU = {"topo", "one_chip", "mesh_2x2"}


def pytest_collection_modifyitems(items):
    leads = {item.path for item in items if _DESCRIBED_TPU & set(item.fixturenames)}
    items.sort(key=lambda item: item.path not in leads)


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


# ------------------------------------------------- compiles that must be real

@contextlib.contextmanager
def _jax_flags_off(*names):
    from jax.experimental.compilation_cache import compilation_cache

    was = {name: jax.config._read(name) for name in names}
    for name in names:
        jax.config.update(name, False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for name, value in was.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


@pytest.fixture
def compiled_afresh():
    """For a test that reads what only a compile leaves behind (the HLO a
    trace stores under a program's name): XLA:CPU keeps none for a program
    it read back from the compilation cache."""
    with _jax_flags_off("jax_enable_compilation_cache"):
        yield


# ------------------------------------------- a described (not attached) TPU
# tests/test_tpu_compile*.py compile for it. Module-scoped: every file that
# asks keeps the compilation cache off (such a compile is written to it but
# cannot be read back without a chip) and XLA's optimiser on (the bytes,
# tallies and memory these tests pin are the optimised program's) round its
# own tests.

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with _jax_flags_off("jax_enable_compilation_cache", "jax_disable_most_optimizations"):
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
