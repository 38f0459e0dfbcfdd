import os
# Keep default device count = 1 for smoke tests/benches (dry-run overrides in
# its own subprocess; multi-device tests spawn subprocesses too).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
