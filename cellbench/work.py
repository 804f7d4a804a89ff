"""Operations and bytes of a kernel call, as a function of the item
count and the curve only — the same work whatever implements it. The
textbook algorithm is counted in field multiplications, each as its
schoolbook byte products (one multiply and one add a product), against
the chip's published int8 peak; padding lanes are not work, and no
int32 vector peak is published, so none is assumed.

ed25519 verify (RFC 8032 §5.1.7), per signature, field 2^255-19:
  decompress A      one exponentiation to (p-5)/8: 251 S + 11 M, and
                    8 M round it                                   270
  [S]B - [k]A       Straus over 253 bits: 253 doublings (4M+4S) and
                    an addition (8M + 1 by 2d) for the 3 in 4 bit
                    pairs that are not 00: 253*8 + 190*9          3734
  compress, compare one inversion (254 S + 11 M) and 2 M           267
SHA-512 of (R, A, M) is not field work and is left out (it runs on the
host today). 4,271 multiplications of 32 x 32 byte products.

BLS12-381 G1 multi-scalar multiplication, per point, field of 381 bits,
double-and-add over 255-bit scalars on y^2 = x^3 + 4, Jacobian:
  255 doublings (2M + 5S)                                         1785
  an addition (7M + 4S, mixed) for the bits that are set, half of
  them for a uniform scalar: 127.5 * 11                         1402.5
  the sum over points: one full addition (11M + 5S)                 16
and once a call the inversion to affine (380 S + ~190 M, and 3 M): 573.
3,203.5 multiplications a point, of 48 x 48 byte products.
"""
from __future__ import annotations

ED25519_FIELD_MULTS = 270 + (253 * 8 + 190 * 9) + 267          # 4271
ED25519_BYTES_IN = 32 + 64 + 32     # public key, signature, hash scalar
ED25519_BYTES_OUT = 1
F25519_BYTE_PRODUCTS = 32 * 32

MSM_FIELD_MULTS_PER_POINT = 255 * 7 + 127.5 * 11 + 16          # 3203.5
MSM_FIELD_MULTS_PER_CALL = 573
MSM_BYTES_IN_PER_POINT = 32 + 96    # scalar, affine point
MSM_BYTES_OUT_PER_CALL = 96
BLS381_BYTE_PRODUCTS = 48 * 48

OPS_PER_PRODUCT = 2                 # a multiply and an add


def ed25519_verify(items: int, calls: int = 1) -> dict:
    return {"ops": items * ED25519_FIELD_MULTS * F25519_BYTE_PRODUCTS
            * OPS_PER_PRODUCT,
            "bytes": items * (ED25519_BYTES_IN + ED25519_BYTES_OUT)}


def bls12_381_g1_msm(items: int, calls: int = 1) -> dict:
    mults = (items * MSM_FIELD_MULTS_PER_POINT
             + calls * MSM_FIELD_MULTS_PER_CALL)
    return {"ops": mults * BLS381_BYTE_PRODUCTS * OPS_PER_PRODUCT,
            "bytes": items * MSM_BYTES_IN_PER_POINT
            + calls * MSM_BYTES_OUT_PER_CALL}


def least_seconds(work: dict, peak: dict) -> dict:
    """The least time the chip could take for `work`: the larger of
    operations over the int8 peak and bytes over the memory bandwidth,
    and which of the two it is."""
    compute = work["ops"] / peak["int8_ops_per_s"]
    memory = work["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
