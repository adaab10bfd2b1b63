"""Shared launcher for accelerator subprocess workers (the tests that
must run WITHOUT the conftest CPU pin so the real device is visible)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


_PROBE_CACHE = []  # session-wide: the environment can't gain a chip mid-run


def _probe_accelerator(env, timeout=None):
    """Ask a throwaway child which platform bare discovery finds.

    Run before the real worker spawn: a chip belongs to one process at
    a time, and discovery of a chip that another process holds blocks
    ``jax.devices()`` inside a GIL-holding C call (in-process thread
    timeouts cannot interrupt it), so the only reliable bound is a
    subprocess kill.  Returns the platform string, or None when
    discovery gave no answer within ``timeout``
    (``TEST_ACCEL_PROBE_TIMEOUT_S``, default 45 s — on a host with no
    chip discovery answers ``cpu`` in about two seconds, and a probe
    that hangs burns its FULL bound of tier-1 wall clock, so the
    default must stay well inside the suite's timeout budget).  The
    verdict is cached for the session so a hung discovery costs the
    suite one probe, not one per test."""
    if _PROBE_CACHE:
        return _PROBE_CACHE[0]
    if timeout is None:
        timeout = float(os.environ.get("TEST_ACCEL_PROBE_TIMEOUT_S", "45"))
    try:
        res = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.devices()[0].platform)"],
            capture_output=True, text=True, env=env, cwd=REPO,
            timeout=timeout)
    except subprocess.TimeoutExpired:
        _PROBE_CACHE.append(None)
        return None
    out = res.stdout.strip().splitlines()
    _PROBE_CACHE.append(out[-1] if res.returncode == 0 and out else None)
    return _PROBE_CACHE[0]


def run_accel_worker(argv, timeout=560):
    """Run a worker script in a clean env (no JAX_PLATFORMS pin) from
    the repo root; skip the calling test when the worker printed the
    no-accelerator sentinel; return the CompletedProcess."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS",)}
    platform = _probe_accelerator(env)
    if platform is None:
        pytest.skip("accelerator discovery gave no answer (bounded probe)")
    if platform == "cpu":
        # same verdict the worker's own sentinel would reach, without
        # a second discovery in the real spawn
        pytest.skip("no accelerator in this environment")
    try:
        res = subprocess.run([sys.executable] + list(argv),
                             capture_output=True, text=True, env=env,
                             cwd=REPO, timeout=timeout)
    except subprocess.TimeoutExpired:
        # environment failure, not a code failure: the accelerator
        # stopped answering mid-run (a hung discovery is answered by the
        # workers' own bounded probe well before this)
        pytest.skip("accelerator worker gave no answer in %ds"
                    % timeout)
    if "SKIP no accelerator" in res.stdout:
        pytest.skip("no accelerator in this environment")
    return res
