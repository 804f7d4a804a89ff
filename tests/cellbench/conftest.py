"""The benchmark's own tests run where the repo's do: XLA-CPU, the
checkout's root on the path (tests/conftest.py has set JAX_PLATFORMS
and the compile cache before this is read)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402


@pytest.fixture
def one_chip_plane():
    """Other tests of this worker may have tripped the breaker or capped
    the mesh on purpose: a closed breaker and one chip, as a cell has.
    The slot tracker is the process's too, and drops events of a
    (replica, sequence number) it has folded before — an earlier
    cluster's, in a test process; a benchmark run has one cluster."""
    from tpubft.ops.dispatch import crypto_mesh, device_breaker
    from tpubft.utils import flight
    flight.slot_tracker().reset()
    device_breaker().reset()
    crypto_mesh().reset()
    crypto_mesh().set_shard_count(1)
    yield
    crypto_mesh().set_shard_count(0)
