"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform so multi-chip sharding paths
can be exercised without TPU hardware (the driver's dryrun does the same via
xla_force_host_platform_device_count). The chip is reached only through
chip_smoke.py / the chip tool, never from the test suite.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent.parent))

# incidental engine loads must not each spawn the ~20-compile background
# warm-up ladder (tests that exercise warm-up pass warm="async" explicitly,
# which is never overridden)
os.environ.setdefault("CEDAR_TPU_WARM_DEFAULT", "off")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from the tier-1 -m 'not slow' run",
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection resilience tests (run via `make chaos`); "
        "always also marked slow so they stay out of the tier-1 time budget",
    )


def pytest_addoption(parser):
    parser.addoption(
        "--update-goldens",
        action="store_true",
        default=False,
        help="regenerate golden files instead of comparing",
    )
