"""Run by hand: ``python -m pytest benchmark/tests -q`` (CPU, a minute
or two).  Four virtual CPU devices, so that a four-chip cell can be
rehearsed (``test_manifest.py``); the one-chip cells use the first."""
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
