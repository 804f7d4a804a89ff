"""`bls.g1_decompress_many` against a loop of `bls.g1_decompress`.

The batch decode is ONE native call that also runs the order-R
membership test on every share (native/bls12381.cpp
`bls381_g1_decompress_batch`, through the curve parameter's sparse
form); the single-point `g1_decompress` keeps its own two-step path
(native square root, then `g1_in_subgroup`'s generic ladder), and
without the native library both are the pure-Python golden model. The
reference here is always the PURE-PYTHON single decode, so the native
batch is held to code it shares nothing with.
"""
import itertools

import pytest

from tpubft.crypto import bls12381 as bls
from tpubft.crypto import bls_native

H1 = 0x396C8C005555E1568C00AAAB0000AAAB      # the G1 cofactor


def _curve_point_from(x: int):
    while True:
        y = bls.fp_sqrt((x * x * x + bls.B1) % bls.P)
        if y is not None:
            return (x, y)
        x = (x + 1) % bls.P


def _compress_any(pt, greater=None) -> bytes:
    """g1_compress for any on-curve point (in the subgroup or not);
    `greater` overrides the sign flag."""
    x, y = pt
    b = bytearray(x.to_bytes(48, "big"))
    b[0] |= 0x80
    if (y > (bls.P - 1) // 2) if greater is None else greater:
        b[0] |= 0x20
    return bytes(b)


def _build_cases():
    s = bls.g1_mul_py(bls.G1_GEN, 0x1234567890ABCDEF)
    low = s if s[1] <= (bls.P - 1) // 2 else bls.g1_neg(s)
    high = bls.g1_neg(low)
    # cofactor component of a curve point, then its order-3 part (as
    # tests/test_bls12381.py builds it) and its order-11 part (the
    # cofactor holds 11^2 = (11 from x-1)^2, as Z_11 x Z_11)
    c = bls.g1_mul_nonorder_py(_curve_point_from(0xBE7A), bls.R)
    assert c is not None
    order3 = bls.g1_mul_nonorder_py(c, H1 // 3)
    order11 = bls.g1_mul_nonorder_py(c, H1 // 121)
    assert order3 is not None and order11 is not None
    assert bls.g1_mul_nonorder_py(order3, 3) is None
    assert bls.g1_mul_nonorder_py(order11, 11) is None
    off_curve_x = next(x for x in itertools.count(5)
                       if bls.fp_sqrt((x ** 3 + bls.B1) % bls.P) is None)
    x_ge_p = bytearray((bls.P + 3).to_bytes(48, "big"))
    x_ge_p[0] |= 0x80
    valid = bls.g1_compress(low)
    return {
        "valid_sign_low": bls.g1_compress(low),
        "valid_sign_high": bls.g1_compress(high),
        "valid_wrong_sign_flag_is_the_other_point":
            _compress_any(low, greater=True),
        "generator": bls.g1_compress(bls.G1_GEN),
        "infinity_canonical": bytes([0xC0]) + b"\x00" * 47,
        "infinity_with_sign_flag": bytes([0xE0]) + b"\x00" * 47,
        "infinity_with_payload": bytes([0xC0]) + b"\x01" + b"\x00" * 46,
        "x_equal_p": bytes([0x80 | bls.P.to_bytes(48, "big")[0]])
            + bls.P.to_bytes(48, "big")[1:],
        "x_above_p": bytes(x_ge_p),
        "not_on_curve": _compress_any((off_curve_x, 0), greater=False),
        "too_short": valid[:47],
        "too_long": valid + b"\x00",
        "empty": b"",
        "uncompressed_flag": bytes([valid[0] & 0x7F]) + valid[1:],
        "cofactor_component": _compress_any(c),
        "cofactor_order_3": _compress_any(order3),
        "cofactor_order_11": _compress_any(order11),
        "subgroup_plus_order_3": _compress_any(bls.g1_add(s, order3)),
        "subgroup_plus_order_11": _compress_any(bls.g1_add(s, order11)),
    }


CASES = _build_cases()
POINTS = {"valid_sign_low", "valid_sign_high", "generator",
          "valid_wrong_sign_flag_is_the_other_point"}


@pytest.fixture(scope="module")
def reference():
    """case -> what the pure-Python `g1_decompress` gives: the point,
    None, or ValueError (the class; messages differ between engines)."""
    lib, tried = bls_native._lib, bls_native._tried
    bls_native._lib, bls_native._tried = None, True
    try:
        out = {}
        for name, enc in CASES.items():
            try:
                out[name] = bls.g1_decompress(enc)
            except ValueError:
                out[name] = ValueError
    finally:
        bls_native._lib, bls_native._tried = lib, tried
    return out


def _use(engine_name, monkeypatch):
    if engine_name == "native":
        if not bls_native.available():
            pytest.skip("native bls12381 library did not build")
    else:
        monkeypatch.setattr(bls_native, "_lib", None)
        monkeypatch.setattr(bls_native, "_tried", True)


@pytest.fixture(params=["native", "fallback"])
def engine(request, monkeypatch):
    _use(request.param, monkeypatch)
    return request.param


def _same(got, want) -> bool:
    if want is ValueError:
        return isinstance(got, ValueError)
    return not isinstance(got, ValueError) and got == want


def test_the_reference_is_what_the_cases_say(reference):
    for name, want in reference.items():
        if name in POINTS:
            assert isinstance(want, tuple), name
        elif name == "infinity_canonical":
            assert want is None
        else:
            assert want is ValueError, name


@pytest.mark.parametrize("name", sorted(CASES))
def test_each_case_alone_and_in_company(engine, reference, name):
    """A batch of one, and the same share between two valid ones: a
    verdict never leaks into a neighbour's."""
    enc = CASES[name]
    assert _same(bls.g1_decompress_many([enc])[0], reference[name])
    v = CASES["valid_sign_low"]
    got = bls.g1_decompress_many([v, enc, v])
    assert _same(got[0], reference["valid_sign_low"])
    assert _same(got[1], reference[name])
    assert _same(got[2], reference["valid_sign_low"])
    # and the single-point entry agrees with its batch form
    try:
        single = bls.g1_decompress(enc)
    except ValueError as e:
        single = e
    assert _same(single, reference[name])


@pytest.mark.parametrize("engine_name,size", [
    ("native", 1), ("native", 7), ("native", 670), ("native", 673),
    ("fallback", 1), ("fallback", 7), ("fallback", 67),
    # the fallback IS the loop over the pure-Python decode: 19 s at 670
    pytest.param("fallback", 670, marks=pytest.mark.slow)])
def test_a_batch_is_the_loop_element_for_element(
        engine_name, size, reference, monkeypatch):
    """Sizes of a lone share, an n=7 flush, a flood slot, and one that
    no even split divides; every kind of share in every batch past the
    first, at rotating positions."""
    _use(engine_name, monkeypatch)
    names = sorted(CASES)
    picks = [names[(5 * i + size) % len(names)] for i in range(size)]
    got = bls.g1_decompress_many([CASES[n] for n in picks])
    assert len(got) == size
    for n, g in zip(picks, got):
        assert _same(g, reference[n]), n


def test_an_empty_batch(engine):
    assert bls.g1_decompress_many([]) == []
