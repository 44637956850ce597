import os
import sys

# Tests run on the CPU with four host devices, so that multi-device sessions
# (replicas, disaggregated pairs) bind their virtual devices to distinct
# JAX devices as they do on a four-chip host.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               "--xla_force_host_platform_device_count=4")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def np_rng():
    return np.random.default_rng(0)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")
    config.addinivalue_line(
        "markers",
        "timing: asserts on wall-clock behavior; false-fails under CPU "
        "contention — CI runs these serially in their own step (and local "
        "runs should too: pytest -m timing), with FLEX_TIMING_SLACK "
        "loosening the thresholds")


def timing_slack() -> float:
    """Multiplier (>= 1) that loosens wall-clock assertions on contended
    machines: FLEX_TIMING_SLACK=2 doubles every timing tolerance.  Tests
    marked ``timing`` must scale their thresholds by this."""
    try:
        return max(1.0, float(os.environ.get("FLEX_TIMING_SLACK", "1")))
    except ValueError:
        return 1.0


def drive_modes():
    """Daemon drive modes the dual-mode tests parameterize over.

    CI matrixes the tier-1 job over FLEX_DRIVE=threaded|stepped so each leg
    exercises one way of driving the daemons (real dispatch threads vs the
    discrete-event stepper); unset or unrecognized values run both."""
    want = os.environ.get("FLEX_DRIVE", "")
    modes = ["threaded", "stepped"]
    return [want] if want in modes else modes
