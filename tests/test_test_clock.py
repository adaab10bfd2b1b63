"""The per-test clock of ``tests/conftest.py`` (``test_clock``): a test
body that never returns fails at the limit with the stacks dumped, and
the run goes on to the next test."""
import os
import subprocess
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))

TWO_TESTS = '''
import conftest

conftest.TEST_LIMIT_S = 1.0  # the fixture reads it when a test starts


def test_spins():
    while True:
        pass


def test_after_the_spinner():
    assert conftest.TEST_LIMIT_S == 1.0
'''


def test_spinning_test_fails_at_the_limit_and_the_run_goes_on(tmp_path):
    path = tmp_path / "test_two.py"
    path.write_text(TWO_TESTS)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [TESTS, os.path.dirname(TESTS),
                    os.environ.get("PYTHONPATH", "")]))
    # tests/conftest.py loaded as a plugin: the file under tmp_path has
    # no conftest of its own to find
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", str(path), "-q", "-p", "conftest",
         "-p", "no:cacheprovider", "-p", "no:xdist", "--rootdir",
         str(tmp_path)],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=100)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 1, out
    assert "1 failed, 1 passed" in proc.stdout, out
    assert "TestClockExpired" in proc.stdout, out
    assert "per-test limit of 1 s" in proc.stdout, out
    # faulthandler's dump names the spinning frame
    assert "most recent call first" in out and "test_spins" in out, out
