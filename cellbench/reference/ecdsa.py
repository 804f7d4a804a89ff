"""ECDSA over secp256k1 in plain Python integers: the comparison's
reference for the `apollo_n31` cells. It imports nothing of the program.

Verification is SEC 1 v2 section 4.1.4 on y^2 = x^3 + 7 over F_p with
SHA-256, by affine addition and doubling and a double-and-add ladder
(u1*G + u2*Q on one ladder, Straus), s^-1 mod n by `pow`. The rules,
as the configuration's file states them:

  * a signature is 64 bytes, r || s big-endian; anything else is
    rejected (SEC 1 has no wire format: this is the deployment's);
  * 0 < r < n and 0 < s < n; low-s and high-s both verify (SEC 1 asks
    for no more; the low-s rule is Bitcoin's, not this deployment's);
  * a public key is SEC1 uncompressed: 0x04 || x || y, 65 bytes, with
    x, y < p and the point on the curve; compressed (0x02/0x03) and
    hybrid encodings are rejected — a departure from SEC 1 section
    2.3.4, which can decode them: the program's batched tiers take the
    uncompressed form only, and a verdict may not depend on the tier;
  * e is the leftmost 256 bits of SHA-256(message), which is all of
    them, reduced mod n; accept when x(u1*G + u2*Q) mod n == r.

`sign` is deterministic (RFC 6979, SHA-256) so that the tests' keys and
signatures are functions of a seed; the benchmark's clients sign with
the program's own signer and the reference only judges.
"""
from __future__ import annotations

import hashlib
import hmac

P = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEFFFFFC2F
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
B = 7
G = (0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798,
     0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8)
SIG_BYTES, KEY_BYTES = 64, 65


def on_curve(pt) -> bool:
    x, y = pt
    return 0 <= x < P and 0 <= y < P and (y * y - x * x * x - B) % P == 0


def add(a, b):
    """Affine a + b; None is the point at infinity."""
    if a is None:
        return b
    if b is None:
        return a
    (x1, y1), (x2, y2) = a, b
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        lam = 3 * x1 * x1 * pow(2 * y1, -1, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, -1, P) % P
    x3 = (lam * lam - x1 - x2) % P
    return x3, (lam * (x1 - x3) - y1) % P


def mul(k: int, pt):
    """k * pt by double-and-add from the top bit."""
    acc = None
    for bit in bin(k)[2:] if k > 0 else "":
        acc = add(acc, acc)
        if bit == "1":
            acc = add(acc, pt)
    return acc


def mul2(k1: int, p1, k2: int, p2):
    """k1 * p1 + k2 * p2 on one ladder (Straus): a doubling a bit, and
    an addition of p1, p2 or p1 + p2 where either scalar's bit is set."""
    both = add(p1, p2)
    acc = None
    for i in range(max(k1.bit_length(), k2.bit_length()) - 1, -1, -1):
        acc = add(acc, acc)
        b1, b2 = (k1 >> i) & 1, (k2 >> i) & 1
        if b1 or b2:
            acc = add(acc, both if b1 and b2 else p1 if b1 else p2)
    return acc


def decode_public(public: bytes):
    """The point of a SEC1 uncompressed key, or None."""
    public = bytes(public)
    if len(public) != KEY_BYTES or public[0] != 0x04:
        return None
    pt = (int.from_bytes(public[1:33], "big"),
          int.from_bytes(public[33:], "big"))
    return pt if on_curve(pt) else None


def verify(public: bytes, message: bytes, sig: bytes) -> bool:
    sig = bytes(sig)
    if len(sig) != SIG_BYTES:
        return False
    r = int.from_bytes(sig[:32], "big")
    s = int.from_bytes(sig[32:], "big")
    if not (0 < r < N and 0 < s < N):
        return False
    q = decode_public(public)
    if q is None:
        return False
    e = int.from_bytes(hashlib.sha256(bytes(message)).digest(), "big") % N
    w = pow(s, -1, N)
    pt = mul2(e * w % N, G, r * w % N, q)
    return pt is not None and pt[0] % N == r


def verify_many(items) -> list:
    """`verify` of each (public key, message, signature)."""
    return [verify(*it) for it in items]


# ---------------------------------------------------------------------
# keys and signatures for the tests (the benchmark's clients sign with
# the program's signer)
# ---------------------------------------------------------------------

def public_of(secret: int) -> bytes:
    x, y = mul(secret, G)
    return b"\x04" + x.to_bytes(32, "big") + y.to_bytes(32, "big")


def secret_of(seed: bytes) -> int:
    return int.from_bytes(hashlib.sha256(b"ecdsa-ref/" + seed).digest(),
                          "big") % (N - 1) + 1


def _nonces(secret: int, h1: bytes):
    """RFC 6979 section 3.2 with HMAC-SHA-256."""
    x = secret.to_bytes(32, "big")
    z = (int.from_bytes(h1, "big") % N).to_bytes(32, "big")
    v, k = b"\x01" * 32, b"\x00" * 32
    k = hmac.new(k, v + b"\x00" + x + z, hashlib.sha256).digest()
    v = hmac.new(k, v, hashlib.sha256).digest()
    k = hmac.new(k, v + b"\x01" + x + z, hashlib.sha256).digest()
    v = hmac.new(k, v, hashlib.sha256).digest()
    while True:
        v = hmac.new(k, v, hashlib.sha256).digest()
        cand = int.from_bytes(v, "big")
        if 0 < cand < N:
            yield cand
        k = hmac.new(k, v + b"\x00", hashlib.sha256).digest()
        v = hmac.new(k, v, hashlib.sha256).digest()


def sign(secret: int, message: bytes) -> bytes:
    h1 = hashlib.sha256(bytes(message)).digest()
    e = int.from_bytes(h1, "big") % N
    for k in _nonces(secret, h1):
        r = mul(k, G)[0] % N
        s = pow(k, -1, N) * (e + r * secret) % N
        if r and s:
            return r.to_bytes(32, "big") + s.to_bytes(32, "big")
    raise AssertionError("unreachable")


def high_s(sig: bytes) -> bytes:
    """The other s of the same signature: (r, n - s) verifies too."""
    s = int.from_bytes(sig[32:], "big")
    return sig[:32] + (N - s).to_bytes(32, "big")
