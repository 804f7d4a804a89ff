"""ops/field_pallas against ops/field and ops/weierstrass, bit for bit,
on XLA-CPU: the engine's arithmetic as plain array code (what the kernel
traces), and the whole ladder kernel in Pallas's interpret mode (slow:
the CPU compiles its unrolled body for a minute)."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

B = 128


@pytest.fixture(scope="module")
def cv():
    from tpubft.ops import ecdsa
    return ecdsa.get_curve("secp256k1")


@pytest.fixture(scope="module")
def engine(cv):
    import jax.numpy as jnp
    from tpubft.ops import field_pallas
    f = cv.f
    p = jnp.broadcast_to(jnp.asarray(f.p_limbs)[:, None, None],
                         (f.nl, 1, B))
    return field_pallas._Engine(f.nl, int(f.pinv), p)


def _elements(cv, seed, n):
    rng = np.random.default_rng(seed)
    return [cv.f.from_ints([int.from_bytes(rng.bytes(32), "big") % cv.f.p
                            for _ in range(B)]) for _ in range(n)]


@pytest.mark.parametrize("case", ["canonical", "sums", "negative"])
def test_engine_mul_is_field_mul(cv, engine, case):
    """Field.mul's contract: loose limbs, values that may be negative."""
    import jax
    import jax.numpy as jnp
    a, b, c = (jnp.asarray(x) for x in _elements(cv, 11, 3))
    x, y = {"canonical": (a, b), "sums": (a + b + c, b + b),
            "negative": (a - b - c, b - c)}[case]
    want = jax.jit(cv.f.mul)(x, y)
    got = jax.jit(engine.mul)(x[:, None, :], y[:, None, :])[:, 0, :]
    assert np.array_equal(np.asarray(want), np.asarray(got))
    assert np.array_equal(np.asarray(cv.f.norm(x)),
                          np.asarray(engine.norm(x[:, None, :])[:, 0, :]))


@pytest.mark.parametrize("case", ["distinct", "doubling", "identity",
                                  "inverse"])
def test_engine_add_is_curve_add(cv, engine, case):
    """The a = 0 closed form against Curve.add on every branch the
    complete formula folds into one: P + Q, P + P, P + O, P - P."""
    import jax
    import jax.numpy as jnp
    from tpubft.ops import field_pallas
    from tpubft.ops.weierstrass import WPoint
    g = cv.generator((B,))
    z = jnp.asarray(_elements(cv, 5, 1)[0])
    p = jax.jit(cv.add)(g, g)
    p = WPoint(cv.f.mul(p.x, z), cv.f.mul(p.y, z), cv.f.mul(p.z, z))
    q = {"distinct": g, "doubling": p, "identity": cv.identity((B,)),
         "inverse": cv.neg(p)}[case]
    want = jax.jit(cv.add)(p, q)
    b3 = jnp.broadcast_to(jnp.asarray(cv._b3_m)[:, None, None],
                          (cv.f.nl, 1, B))
    lift = lambda pt: tuple(c[:, None, :] for c in pt)     # noqa: E731
    got = jax.jit(lambda u, v: field_pallas._add_a0(engine, b3, u, v))(
        lift(p), lift(q))
    for w, x in zip(want, got):
        assert np.array_equal(np.asarray(w), np.asarray(x[:, 0, :]))


def test_only_whole_register_batches_on_a_tpu_take_the_kernel(monkeypatch):
    import jax.numpy as jnp
    from tpubft.ops import ed25519, field_pallas
    assert not field_pallas.usable(jnp.zeros((25, 128), jnp.int32))  # CPU
    monkeypatch.setattr(ed25519, "_use_pallas", lambda: True)
    assert field_pallas.usable(jnp.zeros((25, 128), jnp.int32))
    assert field_pallas.usable(jnp.zeros((25, 256), jnp.int32))
    assert not field_pallas.usable(jnp.zeros((25, 64), jnp.int32))
    assert not field_pallas.usable(jnp.zeros((25, 2, 128), jnp.int32))


@pytest.mark.slow
def test_the_ladder_kernel_is_the_curve_s_ladder(cv):
    import jax
    import jax.numpy as jnp
    from tpubft.ops import field_pallas
    rng = np.random.default_rng(7)
    bits1, bits2 = (jnp.asarray(rng.integers(0, 2, (12, B)), jnp.int32)
                    .at[:, 0].set(0) for _ in range(2))  # lane 0: O
    g = cv.generator((B,))
    q = jax.jit(cv.add)(g, g)
    cv.fused = False
    try:
        want = jax.jit(cv.double_scalar_mul_bits)(bits1, g, bits2, q)
    finally:
        cv.fused = True
    got = jax.jit(lambda *a: field_pallas.double_scalar_mul_bits(
        cv, *a, interpret=True))(bits1, g, bits2, q)
    for w, x in zip(want, got):
        assert np.array_equal(np.asarray(w), np.asarray(x))
