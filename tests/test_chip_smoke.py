"""chip_smoke.py's phases at a tiny size on XLA-CPU, so the script the
chip is proved with cannot rot between chip runs. The one thing steered
around is main()'s platform assertion: the phase functions are called
directly (conftest's JAX_PLATFORMS=cpu makes crypto_backend="tpu" the
XLA-CPU rehearsal of the device path)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def log():
    return chip_smoke.CompileLog()


@pytest.fixture
def clean_device_plane():
    """Other tests of this worker may have tripped the breaker or
    evicted chips on purpose: start from a closed breaker and a full
    mesh. Yields the CryptoMesh so a test can cap it."""
    from tpubft.ops.dispatch import crypto_mesh, device_breaker
    device_breaker().reset()
    crypto_mesh().reset()
    yield crypto_mesh()
    crypto_mesh().set_shard_count(0)


def test_served_matches_cpu_reference(log, clean_device_plane, capsys):
    """n=4 cluster on the device backend at production
    device_min_verify_batch, merkle SKVBC over the native kvlog engine,
    then the same traffic on the cpu backend: identical ledgers and
    reads, and the device path demonstrably engaged."""
    clean_device_plane.set_shard_count(1)     # one chip, as main() has
    chip_smoke.warm(chip_smoke.single_device_programs(
        ed25519_batches=[32, 256], sha_uniform=[(192, 2)]), log)
    tpu, cpu = chip_smoke.served_vs_reference(
        seed=7, clients=2, msgs_per_client=1, batch=32, bulk_writes=1,
        bulk_keys=192, cfg_overrides={"view_change_timer_ms": 60000})
    assert tpu["writes_acked"] == cpu["writes_acked"] == 64
    assert tpu["keys_read"] == 192 + 63
    assert tpu["sigs_device_dispatched"] > 0
    assert cpu["sigs_device_dispatched"] == 0
    rows = capsys.readouterr().out.strip().splitlines()
    assert all(line.startswith("{") for line in rows), rows


def test_crypto_plane_matches_host(log, clean_device_plane):
    """Every kernel kind through its public entry, spoiled corpora,
    verdicts equal to the host's — and the warm-up table really covers
    the shapes the phase forms (no cold compile inside it)."""
    clean_device_plane.set_shard_count(1)     # one chip, as main() has
    chip_smoke.warm(chip_smoke.single_device_programs(
        ed25519_batches=[40], rlc=[("secp256k1", 4)], msm_points=[5],
        sha_masked=[(16, 4)]), log)
    chip_smoke.crypto_plane(seed=7, ed25519_sizes=[(40, 8)], ecdsa_n=4,
                            sha_n=16, msm_k=5, msm_n=8, log=log,
                            curves=["secp256k1"])


# slow: the smallest batches the mesh tier accepts on the 8 virtual
# devices (32 RLC lanes per shard) are minutes of XLA-CPU ladder work.
# Run it before a four-chip call — it is the guide's second rehearsal.
@pytest.mark.slow
def test_mesh_plane_matches_single_device(log, clean_device_plane):
    """--mesh's phase on the virtual CPU devices: every kind at one
    chip and at full width, identical answers, healthy mesh."""
    import jax
    chip_smoke.mesh_plane(seed=7, chips=len(jax.devices()), ed25519_n=64,
                          flood_n=64, sha_n=256, rlc_n=320, msm_k=16,
                          msm_n=24, log=log)


def test_main_refuses_without_a_tpu():
    """No accelerator: non-zero exit before any work, no result line."""
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       cwd=ROOT)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "needs a TPU" in r.stderr
