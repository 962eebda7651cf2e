"""Run a snippet in a fresh interpreter with forced host devices.

conftest.py forbids setting ``--xla_force_host_platform_device_count``
in-process (smoke tests and benches must see 1 device; jax locks the device
count at first init), so every multi-device test re-execs its body here:
a subprocess gets ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
plus ``PYTHONPATH`` covering ``src/`` and ``tests/`` (so bodies can import
repro and test fixtures like ``test_fl_api._GOLDEN``).

Usage::

    from _subproc import run_forced

    @pytest.mark.multidevice
    def test_something():
        out = run_forced("...python code that prints OK...", n_devices=4)
        assert "OK" in out

The helper raises AssertionError with the child's stdout/stderr attached on
nonzero exit, so failures read like ordinary test failures.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

_TESTS = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.abspath(os.path.join(_TESTS, os.pardir, "src"))


def forced_env(n_devices: int, extra: dict | None = None) -> dict:
    """A copy of os.environ with N forced host devices + repo PYTHONPATH."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={int(n_devices)}".strip()
    )
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (_SRC, _TESTS, env.get("PYTHONPATH")) if p
    )
    if extra:
        env.update(extra)
    return env


def run_py(code: str, n_devices: int, timeout: int = 900) -> subprocess.CompletedProcess:
    """Exec ``code`` under ``python -c`` with ``n_devices`` forced host
    devices; returns the CompletedProcess (no exit-status check)."""
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=forced_env(n_devices),
        cwd=_TESTS,
    )


def run_forced(code: str, n_devices: int, timeout: int = 900) -> str:
    """Like run_py but asserts exit 0; returns the child's stdout."""
    r = run_py(code, n_devices, timeout=timeout)
    if r.returncode != 0:
        raise AssertionError(
            f"subprocess (forced {n_devices} host devices) failed "
            f"(exit {r.returncode}):\n--- stdout ---\n{r.stdout}\n"
            f"--- stderr ---\n{r.stderr}"
        )
    return r.stdout
