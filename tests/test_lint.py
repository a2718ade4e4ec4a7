"""The invariant lint plane (ray_tpu/_private/lint/).

Each rule is exercised against a SYNTHETIC mini-repo (its own contract
files + seeded violations) so the assertions pin exact rule ids and
file:line anchors, independent of the real package's contents; the tier-1
test at the bottom then runs the full linter over the real ray_tpu/ and
asserts zero non-baseline findings — the same gate CI runs. Below that, the
tests' own harness (tests/conftest.py) is held to what it says of itself.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap
from concurrent.futures import ThreadPoolExecutor

import pytest

from ray_tpu._private.lint import (
    find_repo_root,
    load_baseline,
    render_report,
    run_lint,
    save_baseline,
)
from ray_tpu._private.lint.core import apply_baseline

REPO_ROOT = find_repo_root(os.path.dirname(os.path.dirname(__file__)))


def _write(root, rel, text):
    path = os.path.join(root, *rel.split("/"))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(text))
    return path


def make_mini_repo(tmp_path):
    """A synthetic repo with one declared flag/metric/event/site each."""
    root = str(tmp_path / "repo")
    _write(root, "ray_tpu/_private/config.py", '''\
        _FLAGS = {
            "declared_flag": 1,
        }
        ''')
    # a reader for every declared flag, so the default mini repo is clean
    _write(root, "ray_tpu/_read_flags.py", '''\
        from ray_tpu._private.config import RTPU_CONFIG

        DECLARED = RTPU_CONFIG.declared_flag
        ''')
    _write(root, "ray_tpu/util/metrics.py", '''\
        """Contract:
            ray_tpu_registered_total   counter
        """
        ''')
    _write(root, "ray_tpu/_private/flight_recorder.py", '''\
        """Recorder.

        EVENT-NAME STABILITY CONTRACT
        -----------------------------
          good.event   a fine event
        """
        def record(event, a=b"", b=""):
            pass
        ''')
    _write(root, "ray_tpu/_private/chaos.py", '''\
        """Chaos.

        SITE-NAME STABILITY CONTRACT
        ----------------------------
          good.site   a fine site

        THE PLAN
        --------
        (rules...)
        """
        ARMED = False
        def hit(site, **attrs):
            return None
        ''')
    return root


def _rules_at(result, rel):
    return [(f.rule, f.line) for f in result.new if f.path == rel]


# ------------------------------------------------------ contract cross-check


@pytest.mark.fast
def test_flag_undeclared_and_dead(tmp_path):
    root = make_mini_repo(tmp_path)
    _write(root, "ray_tpu/_private/config.py", '''\
        _FLAGS = {
            "declared_flag": 1,
            "dead_flag": 2,
        }
        ''')
    _write(root, "ray_tpu/app.py", '''\
        import os
        from ray_tpu._private.config import RTPU_CONFIG

        def f():
            a = RTPU_CONFIG.declared_flag          # ok: declared
            b = RTPU_CONFIG.bogus_flag             # line 6: undeclared
            c = os.environ.get("RTPU_bogus_two")   # line 7: undeclared
            d = os.environ.get("RTPU_ADDRESS")     # ok: infra env (caps)
            return a, b, c, d
        ''')
    r = run_lint(root=root)
    assert _rules_at(r, "ray_tpu/app.py") == [
        ("flag-undeclared", 6),
        ("flag-undeclared", 7),
    ]
    # dead_flag is declared but never read -> anchored at its config line
    dead = [f for f in r.new if f.rule == "flag-dead"]
    assert [f.path for f in dead] == ["ray_tpu/_private/config.py"]
    assert "dead_flag" in dead[0].message
    assert dead[0].line == 3


@pytest.mark.fast
def test_metric_unregistered(tmp_path):
    root = make_mini_repo(tmp_path)
    _write(root, "ray_tpu/emit.py", '''\
        from ray_tpu.util.metrics import Counter

        good = Counter("ray_tpu_registered_total")
        bad = Counter("ray_tpu_bogus_total")
        samples = []
        samples.append(("ray_tpu_tuple_metric", {"node": "n"}, 1.0))
        samples.append(("ray_tpu_results", "not-a-labels-dict"))
        other = Counter(some_dynamic_name)
        ''')
    r = run_lint(root=root)
    assert _rules_at(r, "ray_tpu/emit.py") == [
        ("metric-unregistered", 4),
        ("metric-unregistered", 6),
    ]
    assert "ray_tpu_bogus_total" in r.new[0].message or \
        "ray_tpu_bogus_total" in " ".join(f.message for f in r.new)


@pytest.mark.fast
def test_event_and_chaos_site_unregistered(tmp_path):
    root = make_mini_repo(tmp_path)
    _write(root, "ray_tpu/events.py", '''\
        from ray_tpu._private import flight_recorder as _fr
        from ray_tpu._private import chaos as _chaos

        def f(name):
            _fr.record("good.event", b"", "fine")
            _fr.record("bogus.event", b"", "nope")
            _fr.record(name)              # dynamic: out of scope
            _chaos.hit("good.site")
            _chaos.hit("bogus.site", x=1)
        ''')
    r = run_lint(root=root)
    assert _rules_at(r, "ray_tpu/events.py") == [
        ("event-unregistered", 6),
        ("chaos-site-unregistered", 9),
    ]


# ---------------------------------------------------------- shard safety


@pytest.mark.fast
def test_shard_safety_rules(tmp_path):
    root = make_mini_repo(tmp_path)
    _write(root, "ray_tpu/server_mod.py", '''\
        _SHARD_SAFE_FIELDS = {"stats"}

        class Node:
            def start(self, server):
                server.register_all(self)
                server.set_shard_safe({"Good", "Bad", "Typo"})

            async def handle_Good(self, req):
                with self._lock:
                    self.counter += 1        # locked: fine
                self.stats.append(1)         # allowlisted field: fine
                local = {}
                local["x"] = 1               # not self state: fine
                return {"ok": True}

            async def handle_Bad(self, req):
                self.counter += 1            # line 17: unlocked mutation
                self.pending.append(req)     # line 18: unlocked mutator call
                return {"ok": True}
        ''')
    r = run_lint(root=root)
    got = _rules_at(r, "ray_tpu/server_mod.py")
    assert ("shard-safe-unresolved", 6) in got      # "Typo" never resolves
    assert ("shard-unsafe-mutation", 17) in got
    assert ("shard-unsafe-mutation", 18) in got
    assert len(got) == 3
    unresolved = [f for f in r.new if f.rule == "shard-safe-unresolved"]
    assert "handle_Typo" in unresolved[0].message


@pytest.mark.fast
def test_rpc_choke_point_bypass(tmp_path):
    root = make_mini_repo(tmp_path)
    _write(root, "ray_tpu/_private/rpc.py", '''\
        class RpcServer:
            async def _run_handler(self, method, handler, payload):
                return await handler(payload)    # the one legal call site

            async def _dispatch_ok(self, method, payload):
                handler = self._handlers.get(method)
                return await self._run_handler(method, handler, payload)

            async def _dispatch_bad(self, method, payload):
                handler = self._handlers.get(method)
                return await handler(payload)    # line 11: bypasses the hop

            async def _notify_bad(self, method, payload):
                return self._handlers[method](payload)   # line 14: same
        ''')
    r = run_lint(root=root)
    got = _rules_at(r, "ray_tpu/_private/rpc.py")
    assert ("shard-home-loop-bypass", 11) in got
    assert ("shard-home-loop-bypass", 14) in got
    assert len(got) == 2


# ------------------------------------------------------- blocking detector


@pytest.mark.fast
def test_blocking_calls_in_coroutines(tmp_path):
    root = make_mini_repo(tmp_path)
    # inside the package: only control-plane modules are in scope
    _write(root, "ray_tpu/serve/loopmod.py", '''\
        import asyncio
        import subprocess
        import time

        async def bad():
            time.sleep(1)                     # line 6
            subprocess.run(["true"])          # line 7
            open("/tmp/x")                    # line 8
            with lock_thing:                  # line 9: sync lock
                pass

        async def good(sem, loop):
            await asyncio.sleep(0)
            await sem.acquire()               # awaited: fine

            def helper():
                time.sleep(1)                 # sync def: fine (executor)
            await loop.run_in_executor(None, helper)
        ''')
    # same violations OUTSIDE the control-plane scope: ignored
    _write(root, "ray_tpu/train/offloop.py", '''\
        import time

        async def also_sleeps():
            time.sleep(1)
        ''')
    r = run_lint(root=root)
    assert _rules_at(r, "ray_tpu/serve/loopmod.py") == [
        ("blocking-call-in-async", 6),
        ("blocking-call-in-async", 7),
        ("blocking-io-in-async", 8),
        ("sync-lock-in-async", 9),
    ]
    assert _rules_at(r, "ray_tpu/train/offloop.py") == []


@pytest.mark.fast
def test_unawaited_lock_acquire_in_coroutine(tmp_path):
    root = make_mini_repo(tmp_path)
    _write(root, "ray_tpu/serve/lockmod.py", '''\
        async def f(self):
            self._lock.acquire()              # line 2: un-awaited
            ok = await self._alock.acquire()  # awaited: fine
            self.queue.get()                  # not lock-ish: fine
            return ok
        ''')
    r = run_lint(root=root)
    assert _rules_at(r, "ray_tpu/serve/lockmod.py") == [
        ("sync-lock-in-async", 2),
    ]


# ------------------------------------------------- pragma + baseline round-trip


@pytest.mark.fast
def test_allow_pragma_suppression(tmp_path):
    root = make_mini_repo(tmp_path)
    _write(root, "ray_tpu/serve/pragmod.py", '''\
        import time

        async def f():
            time.sleep(1)  # lint: allow(blocking-call-in-async) -- why
            # lint: allow(blocking-call-in-async) -- pragma on prior line
            time.sleep(2)
            time.sleep(3)  # lint: allow(some-other-rule)
            time.sleep(4)  # lint: allow(*)
        ''')
    r = run_lint(root=root)
    got = _rules_at(r, "ray_tpu/serve/pragmod.py")
    assert got == [("blocking-call-in-async", 7)]  # wrong-rule pragma: kept
    assert r.suppressed == 3


@pytest.mark.fast
def test_baseline_round_trip(tmp_path):
    root = make_mini_repo(tmp_path)
    mod = _write(root, "ray_tpu/serve/basemod.py", '''\
        import time

        async def f():
            time.sleep(1)
        ''')
    r1 = run_lint(root=root)
    assert [f.rule for f in r1.new] == ["blocking-call-in-async"]

    # accept the current findings; a re-run is clean
    bl_path = os.path.join(root, ".lint-baseline.json")
    save_baseline(bl_path, r1.new)
    bl = load_baseline(bl_path)
    r2 = run_lint(root=root, baseline=bl)
    assert r2.ok and len(r2.accepted) == 1

    # a NEW violation fails while the accepted one stays accepted
    with open(mod, "a") as f:
        f.write("\nasync def g():\n    time.sleep(2)\n")
    r3 = run_lint(root=root, baseline=bl)
    assert [f.rule for f in r3.new] == ["blocking-call-in-async"]
    assert "time.sleep(2)" in r3.new[0].snippet
    assert len(r3.accepted) == 1

    # editing the ACCEPTED line re-surfaces its finding for review
    with open(mod, "w") as f:
        f.write("import time\n\nasync def f():\n    time.sleep(1 + 0)\n")
    r4 = run_lint(root=root, baseline=bl)
    assert [f.snippet for f in r4.new] == ["time.sleep(1 + 0)"]
    assert not r4.accepted


@pytest.mark.fast
def test_report_rendering_and_json(tmp_path):
    root = make_mini_repo(tmp_path)
    _write(root, "ray_tpu/serve/rmod.py", '''\
        import time

        async def f():
            time.sleep(1)
        ''')
    r = run_lint(root=root)
    text = render_report(r)
    assert "ray_tpu/serve/rmod.py:4: blocking-call-in-async" in text
    assert text.strip().endswith(")") and "FAIL" in text
    doc = r.to_json()
    assert doc["schema"] == "ray_tpu.lint.v1"
    assert doc["ok"] is False
    assert doc["findings"][0]["rule"] == "blocking-call-in-async"
    json.dumps(doc)  # artifact mode must be serializable


# ------------------------------------------------------------- tier-1 gate


def test_full_package_lint_is_clean():
    """The same gate CI runs: the real linter over the real package with
    the committed baseline must produce zero new findings. If this fails,
    either fix the new violation or (for an accepted design) add an
    inline `# lint: allow(<rule>)` / regenerate the baseline — see the
    rule reference in ray_tpu/_private/lint/__init__.py."""
    bl = load_baseline(os.path.join(REPO_ROOT, ".lint-baseline.json"))
    result = run_lint(root=REPO_ROOT, baseline=bl)
    assert result.files > 100  # sanity: the real package was scanned
    assert result.ok, "new lint findings:\n" + render_report(result)


def test_seeded_violations_all_fire_on_real_contracts(tmp_path):
    """Acceptance sweep: one seeded violation per analyzer, checked
    against the REAL repo contracts (not the mini fixtures), each caught
    with the right rule id and line."""
    fixture = _write(str(tmp_path), "seeded.py", '''\
        import time
        from ray_tpu._private import flight_recorder as _fr
        from ray_tpu._private.config import RTPU_CONFIG
        from ray_tpu.util.metrics import Counter

        flag = RTPU_CONFIG.definitely_not_a_flag          # line 6
        metric = Counter("ray_tpu_never_registered_total")  # line 7

        def emit():
            _fr.record("never.registered")                # line 10

        class Srv:
            def start(self, server):
                server.set_shard_safe({"Mut"})            # line 14

            async def handle_Mut(self, req):
                self.state = req                          # line 17

        async def pump():
            time.sleep(0.1)                               # line 20
        ''')
    r = run_lint(paths=[fixture], root=REPO_ROOT)
    got = {(f.rule, f.line) for f in r.new}
    assert ("flag-undeclared", 6) in got
    assert ("metric-unregistered", 7) in got
    assert ("event-unregistered", 10) in got
    assert ("shard-unsafe-mutation", 17) in got
    assert ("blocking-call-in-async", 20) in got


def test_cli_json_and_exit_codes(tmp_path, capsys):
    """`ray-tpu lint` over the real repo: exit 0 + machine-readable
    report with the committed baseline; exit 1 with --no-baseline (the
    accepted findings exist)."""
    from ray_tpu import scripts

    scripts.main(["lint", "--root", REPO_ROOT, "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == "ray_tpu.lint.v1" and doc["ok"] is True
    assert doc["accepted_by_baseline"]  # the committed accepted findings

    with pytest.raises(SystemExit) as ei:
        scripts.main(["lint", "--root", REPO_ROOT, "--no-baseline"])
    assert ei.value.code == 1


# ------------------------------------------------- the tests' own harness


def _pytest(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", "-p", "no:randomly",
         "-p", "no:xdist", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=170)


def _scratch_suite(tmp_path, **files):
    """A directory under tests/conftest.py's rules, outside the tree."""
    shutil.copy(os.path.join(REPO_ROOT, "tests", "conftest.py"), tmp_path)
    for name, text in files.items():
        _write(str(tmp_path), name + ".py", text)
    return str(tmp_path)


def test_collection_is_one_list_and_the_described_tpu_files_lead():
    """Every xdist worker must collect the same list, and `--dist loadfile`
    hands files out in its order: the files that compile for the described
    TPU come first, the rest in the alphabet's order, twice alike."""
    with ThreadPoolExecutor(2) as pool:
        runs = list(pool.map(lambda _: _pytest(REPO_ROOT, "--collect-only", "tests/"), range(2)))
    ids = [[line for line in r.stdout.splitlines() if "::" in line] for r in runs]
    assert ids[0] == ids[1] and len(ids[0]) > 1900, runs[0].stdout[-2000:] + runs[0].stderr[-2000:]
    files = list(dict.fromkeys(line.split("::")[0] for line in ids[0]))
    assert files.index("tests/test_tpu_compile.py") < files.index("tests/test_actors.py")
    lead = [f for f in files if f.startswith("tests/test_tpu_compile")]
    assert files[:len(lead)] == lead == sorted(lead)
    assert files[len(lead):] == sorted(files[len(lead):])


def test_a_long_deadline_without_the_slow_mark_is_refused_at_collection(tmp_path):
    suite = _scratch_suite(tmp_path, test_scratch='''\
        import pytest

        @pytest.mark.timeout(300)
        def test_asks_for_more_than_the_default():
            pass

        @pytest.mark.slow
        @pytest.mark.timeout(300)
        def test_may_ask_being_slow():
            pass
        ''', test_other='''\
        import pytest

        @pytest.mark.timeout(170)
        def test_within_the_default():
            pass
        ''')
    r = _pytest(suite, "--collect-only", "--continue-on-collection-errors", ".")
    assert r.returncode != 0
    assert "ERROR collecting test_scratch.py" in r.stdout, r.stdout[-2000:]
    assert ("test_scratch.py::test_asks_for_more_than_the_default asks for a deadline of 300 s"
            in r.stdout), r.stdout[-2000:]
    assert "test_may_ask_being_slow asks" not in r.stdout
    assert "test_other.py::test_within_the_default" in r.stdout  # the other file is collected


def test_the_topo_fixture_puts_xla_s_optimiser_back_and_takes_it_away_after(tmp_path):
    """tests/conftest.py turns most of XLA's optimisations off for programs
    a test runs once; the bytes and tallies pinned for the described TPU are
    the optimised program's, so `topo` turns them on round its module."""
    suite = _scratch_suite(tmp_path, test_a_described='''\
        import jax

        def test_before():
            assert jax.config._read("jax_disable_most_optimizations") is True

        def test_inside(topo):
            assert jax.config._read("jax_disable_most_optimizations") is False
            assert jax.config._read("jax_enable_compilation_cache") is False
        ''', test_b_after='''\
        import jax

        def test_after():
            assert jax.config._read("jax_disable_most_optimizations") is True
        ''')
    r = _pytest(suite, "test_a_described.py", "test_b_after.py")
    if "no v5e:2x2 topology can be described here" in r.stdout + r.stderr or " skipped" in r.stdout:
        pytest.skip("no v5e:2x2 topology can be described here")
    assert r.returncode == 0 and "3 passed" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
