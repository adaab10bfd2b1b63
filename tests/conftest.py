"""Test configuration.

Mirrors the reference's test determinism fixture
(``tests/python/unittest/common.py`` seeds numpy+mx) and runs the suite on
a virtual 8-device CPU mesh so multi-chip sharding paths are exercised
without TPU hardware (the driver's dryrun_multichip contract).
"""
import os

# The suite runs on the CPU platform with 8 virtual devices whatever the
# host holds: set before jax is imported, which is when jax reads both
# variables (override, not setdefault — on a TPU host the tests must not
# take the chip).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# The persistent compilation cache follows the package's own rule
# (mxnet_tpu/compile_cache.py): JAX_COMPILATION_CACHE_DIR where set, else
# the fixed <checkout>/.cache/xla — one directory for every session and
# every xdist worker, so a second run hits.  Tests that count hits or
# misses name a directory of their own, per process.

import faulthandler
import signal
import sys

import jax
import numpy as np
import pytest

# tests compare against numpy float32 references, so use full-precision dots
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long multi-process tests excluded from the tier-1 run "
        "(pytest -m 'not slow')")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection matrix over the MXNET_FAULT_INJECT "
        "sites (runs in tier-1; select just the matrix with "
        "pytest -m chaos)")
    config.addinivalue_line(
        "markers",
        "mxlint: static-analysis self-tests and the lint-clean tree "
        "gate (tools/mxlint, docs/static_analysis.md)")


# The longest a single tier-1 test may run, in seconds.  The longest
# sound test takes 38 s with six workers sharing the machine and a warm
# compile cache, 61 s from a cold one (CHANGES.md, PR 24): twice that.
TEST_LIMIT_S = 120.0


class TestClockExpired(Exception):
    """Raised inside a test that outlived ``TEST_LIMIT_S``."""


@pytest.fixture(autouse=True)
def test_clock():
    """Every test has a clock of its own: past ``TEST_LIMIT_S`` every
    thread's stack is dumped to stderr and the test FAILS with a
    message naming the limit, and the run goes on to the next test
    (a busy loop with no way out once cost every PR the driver's whole
    1470 s).

    What it cannot do: SIGALRM's handler runs between two bytecodes of
    the main thread, so a test blocked inside native code (an XLA
    compile, a collective, ``Thread.join()`` without a timeout on some
    platforms) sees the exception only when that call returns; and a
    test that catches ``Exception`` around its whole body swallows it.
    The driver's own clock stays the backstop for those, and
    subprocess workers keep ``tests/worker_guard.py``."""
    def expired(signum, frame):
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        raise TestClockExpired(
            "test ran past the per-test limit of %.0f s "
            "(tests/conftest.py TEST_LIMIT_S)" % TEST_LIMIT_S)

    before = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.fixture(autouse=True)
def seed_rngs():
    import mxnet_tpu as mx

    np.random.seed(0)
    mx.random.seed(0)
    yield


@pytest.fixture(autouse=True)
def no_health_thread_leaks():
    """Every watchdog/heartbeat/gateway thread must be stopped by the
    code that started it (fit's finally block, kv.close, explicit
    stop()) — a leaked poller would keep firing into later tests."""
    yield
    import threading

    from mxnet_tpu.health import (HEARTBEAT_THREAD_PREFIX,
                                  WATCHDOG_THREAD_PREFIX)
    from mxnet_tpu.serve.gateway import GATEWAY_THREAD_PREFIX

    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith((WATCHDOG_THREAD_PREFIX,
                                    HEARTBEAT_THREAD_PREFIX,
                                    GATEWAY_THREAD_PREFIX))]
    assert not leaked, "leaked run-health threads: %s" % leaked


def _net_fds():
    """Snapshot the process's open sockets and event-loop epoll fds.

    /proc-based so it sees everything (asyncio transports, raw sockets,
    selectors) with no dependency beyond Linux; returns {} elsewhere so
    the guard degrades to a no-op."""
    fds = {}
    try:
        for name in os.listdir("/proc/self/fd"):
            try:
                target = os.readlink("/proc/self/fd/" + name)
            except OSError:
                continue  # raced a close
            if target.startswith("socket:") or \
                    target == "anon_inode:[eventpoll]":
                fds[int(name)] = target
    except OSError:
        pass
    return fds


@pytest.fixture(autouse=True)
def no_socket_leaks():
    """A test that opens sockets or event loops (the gateway tests)
    must close them: a leaked listener would collide with later binds
    and a leaked loop's epoll fd pins its callbacks alive.  fd numbers
    get recycled, so compare (fd, inode-target) pairs."""
    before = _net_fds()
    yield
    after = _net_fds()
    leaked = {fd: tgt for fd, tgt in after.items()
              if before.get(fd) != tgt}
    assert not leaked, (
        "leaked sockets/event loops (fd: kind): %s — close every "
        "socket and asyncio loop the test opens (Gateway.stop() does "
        "both for the gateway)" % leaked)
