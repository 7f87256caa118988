import numpy as np
import pytest

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device (the 512-device override is exclusively the
# dry-run's, set inside repro.launch.dryrun before jax init).


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(0)
