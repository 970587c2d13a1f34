"""The benchmark's own tests run on the CPU, by hand:

    python -m pytest benchmark/tests

They are not part of the repository's tier-1 (``pytest.ini`` keeps that to
``tests/``). Four virtual CPU devices stand in for the training cell's
chips."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
