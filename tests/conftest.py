import os

# The committed golden trajectories were recorded with the non-partitionable
# threefry PRNG stream; jax 0.9 defaults to the partitionable one, which
# draws different initial weights and batch orders. Pin the recorded stream
# here, and through the environment for the tests' subprocesses.
os.environ["JAX_THREEFRY_PARTITIONABLE"] = "0"

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_threefry_partitionable", False)

# NOTE: do NOT set --xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device; only repro.launch.dryrun uses 512.


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
