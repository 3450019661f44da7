import os
import sys

import pytest

# Tests run on the single real CPU device (the 512-device override belongs to
# the dry-run ONLY — launch/dryrun.py sets it before jax import).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# hypothesis is optional: the property-based tests (test_properties.py) skip
# themselves via pytest.importorskip, but the suite as a whole must collect
# and run on machines without it.
try:
    from hypothesis import HealthCheck, settings
except ImportError:
    settings = None


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (deselect with -m 'not slow')")


if settings is not None:
    settings.register_profile(
        "ci", max_examples=20, deadline=None, derandomize=True,
        suppress_health_check=[HealthCheck.too_slow])
    settings.load_profile("ci")
