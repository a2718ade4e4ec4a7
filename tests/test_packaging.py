"""Installability (reference: python/setup.py): the package builds a
wheel, installs into a clean target, and the runtime works from the
installed copy outside the checkout (plasma .so builds into the
per-version user cache)."""

import os
import subprocess
import sys

import pytest


def test_wheel_install_and_smoke(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    wheel_dir = tmp_path / "wheels"
    target = tmp_path / "site"
    r = subprocess.run(
        [sys.executable, "-m", "pip", "wheel", "--no-deps",
         "--no-build-isolation", "-w", str(wheel_dir), repo],
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    wheels = list(wheel_dir.glob("ray_tpu-*.whl"))
    assert wheels, list(wheel_dir.iterdir())
    r = subprocess.run(
        [sys.executable, "-m", "pip", "install", "--no-deps", "--no-index",
         "--target", str(target), str(wheels[0])],
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert (target / "ray_tpu" / "_native" / "plasma_store.cc").exists()

    # run the smoke test from OUTSIDE the checkout with only the installed
    # copy importable
    smoke = tmp_path / "smoke.py"
    smoke.write_text(
        "import ray_tpu\n"
        "import ray_tpu.data as rd\n"
        "ray_tpu.init(num_cpus=2)\n"
        "@ray_tpu.remote\n"
        "def f(x):\n"
        "    return x + 1\n"
        "assert ray_tpu.get(f.remote(41)) == 42\n"
        "assert rd.range(10).map(lambda r: {'v': r['id'] * 2}).count() == 10\n"
        "ray_tpu.shutdown()\n"
        "print('SMOKE-OK')\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = str(target)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, str(smoke)], capture_output=True, text=True,
        timeout=240, cwd=str(tmp_path), env=env,
    )
    assert "SMOKE-OK" in r.stdout, (r.stdout[-2000:], r.stderr[-2000:])

    # console script installed
    assert (target / "bin" / "ray-tpu").exists()
