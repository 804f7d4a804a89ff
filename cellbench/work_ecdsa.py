"""Operations and bytes of an ECDSA verify call, as a function of the
signature count and the curve only — the same work whatever implements
it, counted as cellbench/work.py counts (field multiplications as their
schoolbook byte products, one multiply and one add a product, against
the chip's int8 peak; padding lanes are not work).

ECDSA verify (SEC 1 section 4.1.4) on secp256k1, y^2 = x^3 + 7, per
signature, given u1 = e/s and u2 = r/s from the host (one inversion
mod n, not field work on the curve's prime):
  u1*G + u2*Q     Straus over 256 bits, Jacobian: 256 doublings
                  (2M + 5S, a = 0) and a mixed addition (7M + 4S) for
                  the 3 in 4 bit pairs that are not 00:
                  256*7 + 192*11                                  3904
  G + Q           once, a full addition (11M + 5S)                  16
  x(R) == r ?     projective: r*Z^2 (and (r+n)*Z^2 where r+n < p)
                  against X, no inversion                            2
SHA-256 of the message is not field work and is left out (it runs on
the host). 3,922 multiplications of 32 x 32 byte products: 20.4 ns a
signature against 393 TOP/s.
"""
from __future__ import annotations

ECDSA_FIELD_MULTS = 256 * 7 + 192 * 11 + 16 + 2                # 3922
ECDSA_BYTES_IN = 33 + 64 + 32   # compressed key, signature, hash scalar
ECDSA_BYTES_OUT = 1
SECP256K1_BYTE_PRODUCTS = 32 * 32
OPS_PER_PRODUCT = 2             # a multiply and an add


def ecdsa_verify(items: float, calls: int = 1) -> dict:
    return {"ops": items * ECDSA_FIELD_MULTS * SECP256K1_BYTE_PRODUCTS
            * OPS_PER_PRODUCT,
            "bytes": items * (ECDSA_BYTES_IN + ECDSA_BYTES_OUT)}
