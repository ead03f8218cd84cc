import numpy as np
import pytest

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device (the dry-run sets it in its own process).


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
