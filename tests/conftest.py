"""Test bootstrap: JAX on a virtual 8-device CPU mesh.

Runs before test modules import jax. The tests and every rehearsal of
the device path run on XLA-CPU (`JAX_PLATFORMS=cpu`); the multi-chip
sharding tests use the 8 virtual CPU devices. The chip itself is only
ever reached through chip_smoke.py.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

from tpubft.utils.jaxcache import setup_cache  # noqa: E402

# the crypto kernels are large programs (~1 min first compile): cache
# them across test runs
setup_cache()

import pytest  # noqa: E402


@pytest.fixture
def scalar_engine():
    """Pin the host crypto to the in-repo scalar engine. `cryptography`
    is installed on this installation, so crypto/cpu routes through
    OpenSSL unless told otherwise; tests of the scalar engine's own
    contracts (batched host counters, its pubkey admission checks) ask
    for it by name."""
    from tpubft.crypto import cpu
    os.environ["TPUBFT_NO_OPENSSL"] = "1"
    cpu._openssl.cache_clear()
    try:
        yield
    finally:
        del os.environ["TPUBFT_NO_OPENSSL"]
        cpu._openssl.cache_clear()


@pytest.fixture(autouse=True, scope="session")
def few_ecdsa_lanes():
    """ops/ecdsa pads every device launch to DEVICE_LANES = 128, one
    program on the chip; XLA-CPU takes 5 s to run that many lanes of
    the ladder. The tests' launches have 16 (larger batches split, as
    above 128 on the chip); test_ecdsa_batch pins the constant."""
    from tpubft.ops import ecdsa
    real, ecdsa.DEVICE_LANES = ecdsa.DEVICE_LANES, 16
    yield real
    ecdsa.DEVICE_LANES = real
