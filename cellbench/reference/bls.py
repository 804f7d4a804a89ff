"""Plain reference for the threshold-BLS plane of `flood_n1000`:
BLS12-381 G1 in Python integers, nothing of the program imported.

The scheme is the configuration's: shares are [f(i)]·H(m) of a Shamir
polynomial f of degree k-1 over the scalar field, and any k of them
combine, by Lagrange interpolation at zero, to [f(0)]·H(m). The
reference keeps the polynomial, so the certificate it expects is that
one scalar multiplication — no Lagrange coefficient and no MSM, which
are what the program computes. H is the configuration's internal
ciphersuite (try-and-increment on SHA-256, then the cofactor cleared by
1 - x), written down again here; the encoding is ZCash's 48 bytes.
"""
from __future__ import annotations

import hashlib

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
B = 4                                   # y^2 = x^3 + 4
H_EFF = 0xD201000000010001              # 1 - x, the effective cofactor
DST = b"TPUBFT-V01-CS01-with-BLS12381G1_XMD:SHA-256_TAI_"

# Jacobian points (X, Y, Z); Z == 0 is the point at infinity
INF = (1, 1, 0)


def double(p):
    x, y, z = p
    if z == 0 or y == 0:
        return INF
    a = x * x % P
    b = y * y % P
    c = b * b % P
    d = 2 * ((x + b) * (x + b) - a - c) % P
    e = 3 * a % P
    x3 = (e * e - 2 * d) % P
    return x3, (e * (d - x3) - 8 * c) % P, 2 * y * z % P


def add_affine(p, q):
    """p (Jacobian) + q (affine (x, y), or None for infinity)."""
    if q is None:
        return p
    x1, y1, z1 = p
    x2, y2 = q
    if z1 == 0:
        return x2, y2, 1
    zz = z1 * z1 % P
    u2 = x2 * zz % P
    s2 = y2 * z1 % P * zz % P
    h = (u2 - x1) % P
    r = (s2 - y1) % P
    if h == 0:
        return double(p) if r == 0 else INF
    hh = h * h % P
    hhh = h * hh % P
    v = x1 * hh % P
    x3 = (r * r - hhh - 2 * v) % P
    return x3, (r * (v - x3) - y1 * hhh) % P, z1 * h % P


def to_affine(p):
    x, y, z = p
    if z == 0:
        return None
    zi = pow(z, -1, P)
    zi2 = zi * zi % P
    return x * zi2 % P, y * zi2 % P * zi % P


def mul(q, k: int):
    """[k]q for affine q, by double-and-add from the top bit."""
    acc = INF
    for bit in bin(k)[2:]:
        acc = double(acc)
        if bit == "1":
            acc = add_affine(acc, q)
    return to_affine(acc)


def on_curve(q) -> bool:
    x, y = q
    return (y * y - x * x * x - B) % P == 0


def hash_to_g1(msg: bytes):
    ctr = 0
    while True:
        h = hashlib.sha256(DST + ctr.to_bytes(4, "big") + msg).digest()
        x = int.from_bytes(
            h + hashlib.sha256(b"x2" + h).digest()[:16], "big") % P
        rhs = (x * x % P * x + B) % P
        y = pow(rhs, (P + 1) // 4, P)
        if y * y % P == rhs:
            pt = mul((x, min(y, P - y)), H_EFF)
            if pt is not None:
                return pt
        ctr += 1


def compress(q) -> bytes:
    if q is None:
        return bytes([0xC0] + [0] * 47)
    x, y = q
    b = bytearray(x.to_bytes(48, "big"))
    b[0] |= 0x80 | (0x20 if y > (P - 1) // 2 else 0)
    return bytes(b)


class Polynomial:
    """The dealer's polynomial: f(0) is the master secret, f(i) signer
    i's share of it (i from 1)."""

    def __init__(self, coeffs) -> None:
        self.coeffs = [c % R for c in coeffs]

    def at(self, i: int) -> int:
        v = 0
        for c in reversed(self.coeffs):
            v = (v * i + c) % R
        return v

    @property
    def secret(self) -> int:
        return self.coeffs[0]


class WindowTable:
    """Fixed-base scalar multiplication of one point by many scalars:
    the multiples d·16^w·H for every 4-bit window w and digit d, so that
    one share costs 64 additions. Only the generator of inputs needs it
    (1,000 shares a digest); the expected certificate is one plain
    `mul`."""

    def __init__(self, base) -> None:
        self.rows = []
        row_base = base
        for _ in range(64):
            row, acc = [None], INF
            jac = []
            for _d in range(15):
                acc = add_affine(acc, row_base)
                jac.append(acc)
            row += _batch_affine(jac)
            self.rows.append(row)
            row_base = to_affine(double(jac[7]))      # 16 * row_base

    def mul(self, k: int):
        acc = INF
        for row in self.rows:
            acc = add_affine(acc, row[k & 15])
            k >>= 4
        return acc


def _batch_affine(points):
    """Jacobian -> affine with one inversion (Montgomery's trick)."""
    zs = [p[2] for p in points]
    if any(z == 0 for z in zs):
        return [to_affine(p) for p in points]
    prefix, acc = [], 1
    for z in zs:
        prefix.append(acc)
        acc = acc * z % P
    inv = pow(acc, -1, P)
    out = [None] * len(points)
    for i in range(len(points) - 1, -1, -1):
        zi = inv * prefix[i] % P
        inv = inv * zs[i] % P
        zi2 = zi * zi % P
        x, y, _ = points[i]
        out[i] = (x * zi2 % P, y * zi2 % P * zi % P)
    return out


def shares_of(poly: Polynomial, signers: int, digest: bytes) -> list:
    """Every signer's compressed share over `digest`, ids 1..signers."""
    table = WindowTable(hash_to_g1(digest))
    jac = [table.mul(poly.at(i)) for i in range(1, signers + 1)]
    return [compress(q) for q in _batch_affine(jac)]


def expected_certificate(poly: Polynomial, digest: bytes) -> bytes:
    return compress(mul(hash_to_g1(digest), poly.secret))
