"""ops/g1_pallas — the G1 MSM's ladder and tree reduce as one Pallas
kernel — against ops/weierstrass's XLA ladder and msm_reduce, bit for
bit, on XLA-CPU in Pallas's interpret mode. This file holds the
flood's width (1,024 lanes: one whole block), which kernel each shape
takes, and the ECDSA kernel's functions the G1 kernel imports;
test_g1_pallas_narrow.py holds 128 lanes and the ladder alone.

Tier-1 runs a 16-bit scalar matrix (the loop's body is what a longer
one repeats; the CPU compiles the unrolled body for some 45 s); the
full 255 bits and a 667-share combine are `slow`."""
import ctypes
import functools
import gc
import hashlib
import inspect
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SHORT = 16


def _points(n, seed):
    """n affine points of G1, a few distinct ones repeated."""
    from tpubft.crypto import bls12381 as ref
    rng = np.random.default_rng(seed)
    base = [ref.g1_mul(ref.G1_GEN, int(rng.integers(1, 1 << 62)))
            for _ in range(16)]
    return [base[i % len(base)] for i in range(n)]


def case(kind, lanes, nbits, seed=3):
    """-> (bits (nbits, B), px, py, infinity (B,)) for `msm_kernel`.
    `mixed`: random bits; padding lanes; scalars 0, 1 and all ones (the
    largest the width holds: r - 1 where nbits is 255); the same point
    and bits in lanes t and t + B/2, so the reduce's first level meets
    a doubling. `identity`: every lane padding."""
    from tpubft.ops import bls12_381
    from tpubft.crypto import bls12381 as ref
    rng = np.random.default_rng(seed)
    pts = _points(lanes, seed)
    bits = rng.integers(0, 2, (nbits, lanes)).astype(np.int32)
    infinity = np.zeros(lanes, bool)
    if kind == "identity":
        infinity[:] = True
    else:
        infinity[[5, lanes // 2 + 9, lanes - 1]] = True
        bits[:, 1] = 0                                  # k = 0
        bits[:, 2] = 0
        bits[-1, 2] = 1                                 # k = 1
        if nbits == bls12_381.SCALAR_BITS:
            bits[:, 3] = bls12_381._bits_msb_batch([ref.R - 1])[:, 0]
        else:
            bits[:, 3] = 1
        half = lanes // 2
        pts[half + 4] = pts[4]
        bits[:, half + 4] = bits[:, 4]
    cv = bls12_381.g1_curve()
    px, py = cv.affine_to_device(pts)
    return bits, px, py, infinity


def _lift(cv, px, py, infinity):
    import jax.numpy as jnp
    pts = cv.from_affine(jnp.asarray(px), jnp.asarray(py))
    return cv.select(jnp.asarray(infinity), cv.identity(px.shape[1:]), pts)


@functools.lru_cache(maxsize=None)
def _programs():
    """(the XLA ladder + msm_reduce, the kernel in interpret mode), each
    jitted once for the module: a shape compiles once."""
    import jax
    from tpubft.ops import bls12_381, g1_pallas
    cv = bls12_381.g1_curve()

    def xla(bits, px, py, infinity):
        p = _lift(cv, px, py, infinity)
        return tuple(cv.msm_reduce(cv.scalar_mul_bits(bits, p)))

    def kernel(bits, px, py, infinity):
        return g1_pallas.msm_bits(cv, bits, _lift(cv, px, py, infinity),
                                  interpret=True)

    return jax.jit(xla), jax.jit(kernel)


@pytest.fixture(scope="module", autouse=True)
def release_programs():
    """The interpret-mode programs hold some 7 GB of the worker's memory,
    and the worker runs other files after this one: drop them and hand
    the freed memory back to the system when the module is done."""
    yield
    _programs.cache_clear()
    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def assert_msm_identical(args):
    xla, kernel = _programs()
    want, got = xla(*args), kernel(*args)
    for w, g in zip(want, got):
        assert g.shape == w.shape
        assert np.array_equal(np.asarray(w), np.asarray(g))
    return want


@pytest.mark.parametrize("kind", ["mixed", "identity"])
def test_the_msm_kernel_is_the_ladder_then_msm_reduce(kind):
    """1,024 lanes, the flood's launch: one block of eight sublanes,
    every limb of (X : Y : Z) as XLA's, the padding lanes the identity."""
    want = assert_msm_identical(case(kind, 1024, SHORT))
    if kind == "identity":                    # (0 : y : 0), y != 0
        assert not np.asarray(want[2]).any() and np.asarray(want[1]).any()


def test_only_flat_whole_register_batches_on_a_tpu_take_the_g1_kernel(
        monkeypatch):
    import jax
    import jax.numpy as jnp
    from tpubft.ops import ecdsa, ed25519, g1_pallas
    from tpubft.ops.bls12_381 import g1_curve
    from tpubft.ops.weierstrass import WPoint

    def shape(*s):
        return jnp.zeros(s, jnp.int32)

    assert not g1_pallas.usable(shape(36, 1024), reduce=True)     # CPU
    monkeypatch.setattr(ed25519, "_use_pallas", lambda: True)
    for lanes in (128, 256, 512, 1024, 2048, 4096):
        assert g1_pallas.usable(shape(36, lanes))
        assert g1_pallas.usable(shape(36, lanes), reduce=True)
    assert g1_pallas.usable(shape(36, 384))            # one block
    assert g1_pallas.usable(shape(36, 3072))           # whole blocks
    assert not g1_pallas.usable(shape(36, 384), reduce=True)
    assert not g1_pallas.usable(shape(36, 3072), reduce=True)
    assert not g1_pallas.usable(shape(36, 1536))       # neither
    assert not g1_pallas.usable(shape(36, 64))
    assert not g1_pallas.usable(shape(36, 2, 128))

    taken = []
    monkeypatch.setattr(g1_pallas, "scalar_mul_bits",
                        lambda cv, b, p: taken.append("ladder") or p)
    monkeypatch.setattr(g1_pallas, "msm_bits",
                        lambda cv, b, p: taken.append("msm") or
                        tuple(c[:, :1] for c in p))

    def trace(cv, fn, nl, batch):
        pt = WPoint(*(jax.ShapeDtypeStruct((nl,) + batch, jnp.int32),) * 3)
        bits = jax.ShapeDtypeStruct((8,) + batch, jnp.int32)
        jax.eval_shape(fn, bits, pt)

    g1 = g1_curve()
    trace(g1, g1.msm_bits, 36, (1024,))
    trace(g1, g1.scalar_mul_bits, 36, (256,))
    assert taken == ["msm", "ladder"]
    # msm_batch_kernel's segmented (S, K) batch, a batch of no whole
    # blocks and a = -3 (P-256) keep XLA's ladder
    trace(g1, g1.scalar_mul_bits, 36, (2, 128))
    trace(g1, g1.msm_bits, 36, (1536,))
    p256 = ecdsa.get_curve("secp256r1")
    assert not p256.fused
    trace(p256, p256.scalar_mul_bits, p256.f.nl, (256,))
    trace(p256, p256.msm_bits, p256.f.nl, (256,))
    assert taken == ["msm", "ladder"]


@pytest.mark.parametrize("on_tpu", [False, True])
def test_a_launch_counts_as_fused_where_its_program_takes_the_kernel(
        monkeypatch, on_tpu):
    from tpubft.crypto import systems
    from tpubft.ops import bls12_381, ed25519
    monkeypatch.setattr(ed25519, "_use_pallas", lambda: on_tpu)
    before = systems.METRICS.snapshot()["counters"]["bls_msm_fused_launches"]
    bls12_381._count_launch(1024, reduce=True)          # one chip
    bls12_381._count_launch(256, reduce=False)          # a mesh's shard
    bls12_381._count_launch(96, reduce=False)           # XLA's
    after = systems.FUSED_MSM_LAUNCHES.value
    assert after - before == (2 if on_tpu else 0)


# The ECDSA kernel's functions as the ECDSA path runs them: the G1
# kernel imports them and must not edit them (sha256 of the source).
ECDSA_KERNEL = {
    "_carry_chain":
        "daeadfd5836609813e0dc60f330b30a132a59118528fbac4f9729146534aae4d",
    "_Engine":
        "9d48bc5f9055e899da70ae69f6459c68891f03c4d9d2fd394244acda51598acf",
    "_add_a0":
        "f87a53085325f62b39c5f58c729a15671015c7d5f9fcb13a42a9d8ef7c92d90c",
    "_ladder_kernel":
        "0af825edea40837d2720f7b93951ad58b0c48c82c0b09ede6372e3144d6e8ebf",
    "double_scalar_mul_bits":
        "b233ffb6e98741a9b5d9d8c58dd71a2035cbc3dc62687845af36018b4f1373e4",
}


def test_the_g1_kernel_imports_the_ecdsa_kernel_s_arithmetic_unedited():
    from tpubft.ops import field_pallas, g1_pallas
    assert g1_pallas._Engine is field_pallas._Engine
    assert g1_pallas._add_a0 is field_pallas._add_a0
    assert g1_pallas.LANES == field_pallas.LANES
    got = {name: hashlib.sha256(inspect.getsource(
        getattr(field_pallas, name)).encode()).hexdigest()
        for name in ECDSA_KERNEL}
    assert got == ECDSA_KERNEL


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["mixed", "identity"])
def test_the_msm_kernel_over_the_whole_scalar(kind):
    """255 bits: the scalar r - 1 whole, every bit of the ladder."""
    from tpubft.ops import bls12_381
    assert_msm_identical(case(kind, 1024, bls12_381.SCALAR_BITS))


@pytest.mark.slow
def test_a_667_share_combine_through_the_kernel_is_the_host_s():
    """The flood's combine: 667 shares padded to 1,024 lanes, Lagrange
    weights at zero, against crypto/bls12381.combine_shares."""
    from tpubft.crypto import bls12381 as ref
    from tpubft.ops import bls12_381
    ids = list(range(3, 3 + 667))
    shares = _points(667, 11)
    bits, px, py, infinity = bls12_381._prep_msm(
        shares, ref.lagrange_coeffs_at_zero(ids), 1024)
    x, y, z = (np.asarray(c) for c in
               assert_msm_identical((bits, px, py, infinity)))
    got = bls12_381._to_affine_host(x[:, 0], y[:, 0], z[:, 0])
    assert got == ref.combine_shares(ids, shares)
