"""The kernels of the chip's main path, compiled for a TPU v5e that is
described, not attached (on-chip-measurement guide, section 2): what the
chip's compiler would refuse — a Mosaic kernel it cannot partition, a
tile it cannot lay out, a program that does not fit — fails here, on the
CPU, at no chip time. The shapes are the ones chip_smoke.py forms.

Nothing touches the TPU library until a test of this file has started:
the topology is described inside a module-scoped fixture (which skips
where it cannot be), shardings and shapes are built from it in fixtures
and tests, and everything compiles in the test's own process. Keep these
tests in this one file — under xdist a second file would land on another
worker, whose fixture cannot load the library a second time.

Tier-1 keeps what the chip's main path selects; `slow` carries the wider
ed25519 batches, the second curve, the other sharded kernels and the XLA
ed25519 kernel (on a TPU `_single_device_verify` never selects it).
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

slow = pytest.mark.slow


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever the reason, skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def on_tpu():
    """Code that asks JAX for its platform sees the CPU here; the kernel
    selector is steered to the branch it takes on the chip."""
    from tpubft.ops import ed25519
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ed25519, "_use_pallas", lambda: True)
        yield


@pytest.fixture(scope="module")
def programs(on_tpu):
    """chip_smoke.py's own table at the sizes its main() runs."""
    import chip_smoke
    return {label: (kernel, shapes) for label, kernel, shapes in
            chip_smoke.single_device_programs(**chip_smoke.ONE_CHIP_SHAPES)}


def _placed(shapes, sharding):
    import jax
    if not isinstance(sharding, (list, tuple)):
        sharding = [sharding] * len(shapes)
    return [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
            for s, sh in zip(shapes, sharding)]


@pytest.mark.parametrize("label", [
    "ed25519@1024", "bls_msm@1024", "ecdsa_rlc.secp256k1@256",
    "sha256@256x2", "sha256.masked@1024x4",
    pytest.param("ed25519@4096", marks=slow),
    pytest.param("ed25519@16384", marks=slow),
    pytest.param("ecdsa_rlc.secp256r1@256", marks=slow),
])
def test_single_chip_program_compiles(topo, programs, label):
    from jax.sharding import SingleDeviceSharding
    kernel, shapes = programs[label]
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = kernel.lower(*_placed(shapes, one_chip)).compile()
    if label.startswith(("ed25519", "bls_msm")):
        # Mosaic compiled the fused kernel; nothing was interpreted
        assert compiled.as_text().count("tpu_custom_call") >= 1
    # 16 GB of HBM on a v5e chip; these programs are nowhere near it
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 1 << 30


@slow
@pytest.mark.parametrize("batch", [1024, 16384])
def test_xla_ed25519_kernel_compiles(topo, batch):
    import chip_smoke
    from jax.sharding import SingleDeviceSharding
    from tpubft.ops import ed25519
    one_chip = SingleDeviceSharding(topo.devices[0])
    ed25519.verify_kernel.lower(
        *_placed(chip_smoke._ed25519_args(batch), one_chip)).compile()


@pytest.fixture(scope="module")
def mesh(topo):
    from jax.sharding import Mesh
    from tpubft.parallel import sharding
    return Mesh(np.array(topo.devices), (sharding.AXIS,))


def _ed25519_shardings(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tpubft.parallel import sharding
    last = NamedSharding(mesh, P(None, sharding.AXIS))
    only = NamedSharding(mesh, P(sharding.AXIS))
    return [last, last, last, only, last, only]


@pytest.mark.parametrize("batch", [4096, pytest.param(16384, marks=slow)])
def test_pallas_ed25519_compiles_under_the_mesh(topo, mesh, on_tpu, batch):
    """The multi-chip ed25519 tier on a four-chip host: the partitioner
    cannot split a Mosaic kernel, so each device must run the fused
    kernel on its own shard — one custom call, a whole TILE (or more)
    of lanes per device."""
    import chip_smoke
    from tpubft.ops import ed25519_pallas
    from tpubft.parallel import sharding
    shardings = _ed25519_shardings(mesh)
    args = _placed(chip_smoke._ed25519_args(batch), shardings)
    compiled = sharding.sharded_verify_ed25519(mesh).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "num_partitions=4" in text
    lanes = shardings[0].shard_shape(args[0].shape)[1]
    assert lanes == batch // 4 and lanes % ed25519_pallas.TILE == 0
    # what dispatch forms for this batch is exactly this program's shape
    assert max(sharding.shard_rows(batch, 4, ed25519_pallas.TILE), 8) * 4 \
        == batch


def test_sha256_compiles_under_the_mesh(topo, mesh):
    import chip_smoke
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tpubft.parallel import sharding
    batch = NamedSharding(mesh, P(sharding.AXIS))
    for kernel, masked in ((sharding.sharded_sha256_kernel(mesh), False),
                           (sharding.sharded_sha256_masked_kernel(mesh),
                            True)):
        args = _placed(chip_smoke._sha_args(4096, 4, masked), batch)
        assert "num_partitions=4" in kernel.lower(*args).compile().as_text()


@slow
def test_msm_and_rlc_compile_under_the_mesh(topo, mesh):
    """The shard_map kernels of chip_smoke.py --mesh: the MSM combines
    its per-chip partial sums with all-gathers, the RLC aggregate gathers
    one verdict bit per chip."""
    import chip_smoke
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tpubft.parallel import sharding
    col = NamedSharding(mesh, P(None, sharding.AXIS))
    row = NamedSharding(mesh, P(sharding.AXIS))
    msm = sharding.sharded_msm_kernel(mesh).lower(*_placed(
        chip_smoke._msm_args(1024), [col, col, col, row])).compile()
    assert "all-gather" in msm.as_text()
    sharding.sharded_rlc_kernel("secp256k1", mesh).lower(*_placed(
        chip_smoke._rlc_args("secp256k1", 1024),
        [col, col, col, col, col, col, row, row, col])).compile()
