"""The ed25519 batch's host half (`ops/ed25519.prepare_batch`, one native
call over packed blobs, `native/ed25519prep.cpp`) against the numpy body
it replaced, kept here as the oracle: all seven arrays byte for byte."""
import hashlib
import random

import numpy as np
import pytest

from tpubft.crypto import cpu
from tpubft.ops import ed25519 as ops
from tpubft.ops import f25519 as F

L, P = ops.L, ops.P
# SHA-512 block edges with the 64-byte R || A prefix: 111 bytes fit one
# block, 112 need two; 239 two, 240 three
MSG_LENS = (0, 45, 47, 48, 175, 176)


# ---------------- the oracle: prepare_batch as numpy had it ----------------

def _lex_lt(rows_le, bound):
    b_be = np.frombuffer(bound.to_bytes(32, "big"), np.uint8)
    r_be = rows_le[:, ::-1]
    diff = r_be != b_be[None, :]
    has = diff.any(axis=1)
    first = diff.argmax(axis=1)
    rows_first = r_be[np.arange(len(r_be)), first]
    return np.where(has, rows_first < b_be[first], False)


def _windows_le(rows_le):
    bits = np.unpackbits(rows_le, axis=1, bitorder="little")
    nib = bits.reshape(bits.shape[0], ops.WINDOWS, 4).astype(np.int32)
    vals = nib @ np.array([1, 2, 4, 8], np.int32)
    return np.ascontiguousarray(vals.T)


def _bytes_le_to_limbs(arr_u8):
    bits = np.unpackbits(arr_u8, axis=1, bitorder="little")
    out = np.zeros((F.NL, bits.shape[0]), np.int32)
    for i in range(F.NL):
        seg = bits[:, F.W[i]:F.W[i + 1]].astype(np.int32)
        out[i] = seg @ (1 << np.arange(seg.shape[1], dtype=np.int64)).astype(
            np.int32)
    return out


def oracle(items):
    n = len(items)
    sig_raw = np.zeros((n, 64), np.uint8)
    pk_raw = np.zeros((n, 32), np.uint8)
    shaped = np.zeros(n, bool)
    h_raw = np.zeros((n, 32), np.uint8)
    for i, (msg, sig, pk) in enumerate(items):
        if len(sig) != 64 or len(pk) != 32:
            continue
        shaped[i] = True
        sig_raw[i] = np.frombuffer(sig, np.uint8)
        pk_raw[i] = np.frombuffer(pk, np.uint8)
        h = int.from_bytes(
            hashlib.sha512(sig[:32] + pk + msg).digest(), "little") % L
        h_raw[i] = np.frombuffer(h.to_bytes(32, "little"), np.uint8)
    r_bytes = sig_raw[:, :32].copy()
    s_bytes = sig_raw[:, 32:].copy()
    a_sign = (pk_raw[:, 31] >> 7).astype(np.int32)
    r_sign = (r_bytes[:, 31] >> 7).astype(np.int32)
    a_masked = pk_raw.copy()
    a_masked[:, 31] &= 0x7F
    r_masked = r_bytes.copy()
    r_masked[:, 31] &= 0x7F
    host_valid = (shaped & _lex_lt(s_bytes, L) & _lex_lt(a_masked, P)
                  & _lex_lt(r_masked, P))
    keep = host_valid[:, None]
    return ops.PreparedBatch(
        s_win=_windows_le(np.where(keep, s_bytes, 0)),
        h_win=_windows_le(np.where(keep, h_raw, 0)),
        a_y=_bytes_le_to_limbs(np.where(keep, a_masked, 0)),
        a_sign=np.where(host_valid, a_sign, 0),
        r_y=_bytes_le_to_limbs(np.where(keep, r_masked, 0)),
        r_sign=np.where(host_valid, r_sign, 0),
        host_valid=host_valid)


def assert_same(got, want):
    for name in ops.PreparedBatch._fields:
        g, w = getattr(got, name), getattr(want, name)
        assert g.dtype == w.dtype, name
        assert g.shape == w.shape, name
        assert g.tobytes() == w.tobytes(), name


# ---------------- items ----------------

_SIGNERS = [cpu.Ed25519Signer.generate(seed=f"prep{i}".encode())
            for i in range(8)]


def signed(i, msg):
    s = _SIGNERS[i % len(_SIGNERS)]
    return (msg, s.sign(msg), s.public_bytes())


def batch(n, seed=0):
    """n items: real signatures over messages of every edge length, with
    every seventh a random (mostly non-canonical) signature."""
    rng = random.Random(seed)
    out = []
    for i in range(n):
        msg = rng.randbytes(MSG_LENS[i % len(MSG_LENS)])
        if i % 7 == 3:
            out.append((msg, rng.randbytes(64), rng.randbytes(32)))
        else:
            out.append(signed(i, msg))
    return out


def with_bytes(b, at, value, width=32):
    return b[:at] + value.to_bytes(width, "little") + b[at + width:]


def spoil(kind):
    msg, sig, pk = signed(1, b"spoiled" * 7)
    if kind == "sig40":
        return (msg, sig[:40], pk), False
    if kind == "pk31":
        return (msg, sig, pk[:31]), False
    if kind == "s=L":
        return (msg, with_bytes(sig, 32, L), pk), False
    if kind == "s=L-1":
        return (msg, with_bytes(sig, 32, L - 1), pk), True
    if kind in ("A=p", "A=p-1", "A=2^255-1+sign"):
        y = {"A=p": P, "A=p-1": P - 1, "A=2^255-1+sign": 2**256 - 1}[kind]
        return (msg, sig, y.to_bytes(32, "little")), kind == "A=p-1"
    y = {"R=p": P, "R=p-1": P - 1, "R=2^255-1+sign": 2**256 - 1}[kind]
    return (msg, with_bytes(sig, 0, y), pk), kind == "R=p-1"


# ---------------- tests ----------------

@pytest.mark.parametrize("n", [0, 1, 31, 32, 1000, ops.PREP_CHUNK + 1])
def test_native_prep_equals_the_numpy_oracle(n):
    items = batch(n, seed=n)
    got = ops.prepare_batch(items)
    assert_same(got, oracle(items))
    if n >= 7:
        assert 0 < got.host_valid.sum() < n


@pytest.mark.parametrize("kind", [
    "sig40", "pk31", "s=L", "s=L-1", "A=p", "A=p-1", "R=p", "R=p-1",
    "A=2^255-1+sign", "R=2^255-1+sign"])
def test_a_spoiled_item_matches_the_oracle(kind):
    item, host_ok = spoil(kind)
    items = batch(5, seed=1) + [item] + batch(3, seed=2)
    got = ops.prepare_batch(items)
    assert_same(got, oracle(items))
    assert bool(got.host_valid[5]) is host_ok
    if not host_ok:
        for name in ("s_win", "h_win", "a_y", "r_y"):
            assert not getattr(got, name)[:, 5].any(), name
        assert got.a_sign[5] == 0 and got.r_sign[5] == 0


@pytest.mark.parametrize("length",
                         [0, 1, 45, 111, 112, 127, 128, 129, 175, 176, 239,
                          240, 1000])
def test_native_sha512_equals_hashlib(length):
    import ctypes
    msg = random.Random(length).randbytes(length)
    out = ctypes.create_string_buffer(64)
    ops._prep_native().ed25519prep_sha512(msg, length, out)
    assert out.raw == hashlib.sha512(msg).digest()


@pytest.mark.parametrize("x", [0, 1, L - 1, L, L + 1, 2**252, 2 * L - 1,
                               2**256 - 1, (2**512 - 1) // L * L,
                               (2**512 - 1) // L * L - 1, 2**512 - 1,
                               2**511 + 12345, 2**385 + 2**252 * 63])
def test_native_reduction_mod_l(x):
    import ctypes
    out = ctypes.create_string_buffer(32)
    ops._prep_native().ed25519prep_sc_reduce(x.to_bytes(64, "little"), out)
    assert int.from_bytes(out.raw, "little") == x % L


def test_native_reduction_mod_l_random():
    import ctypes
    rng = random.Random(7)
    out = ctypes.create_string_buffer(32)
    red = ops._prep_native().ed25519prep_sc_reduce
    for _ in range(2000):
        x = rng.getrandbits(rng.choice([64, 253, 260, 385, 512]))
        red(x.to_bytes(64, "little"), out)
        assert int.from_bytes(out.raw, "little") == x % L


def test_counter_counts_each_calls_items():
    from tpubft.crypto import systems
    c = systems.ED25519_PREP_NATIVE_ITEMS
    before = c.value
    ops.prepare_batch(batch(10))
    assert c.value - before == 10
    ops.prepare_batch(batch(3))
    assert c.value - before == 13
    assert (systems.METRICS.snapshot()["counters"]
            ["ed25519_prep_native_items"] == c.value)


def test_verdicts_through_verify_batch_on_xla_cpu():
    """Forged, truncated and duplicated items through the whole verify
    (native prep, XLA-CPU kernel) against `cryptography`'s verify."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import \
        Ed25519PublicKey
    items = [signed(i, f"verdict-{i}".encode() * (i % 4)) for i in range(12)]
    msg, sig, pk = items[2]
    items[2] = (msg, sig[:5] + bytes([sig[5] ^ 1]) + sig[6:], pk)  # forged
    msg, sig, pk = items[4]
    items[4] = (msg, sig[:63], pk)                                 # truncated
    items[7] = items[6]                                            # duplicate

    def reference(m, s, k):
        try:
            Ed25519PublicKey.from_public_bytes(k).verify(s, m)
            return True
        except (InvalidSignature, ValueError):
            return False

    want = [reference(*it) for it in items]
    assert want.count(False) == 2
    assert ops.verify_batch(items).tolist() == want
