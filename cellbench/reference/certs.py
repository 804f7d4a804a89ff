"""Plain reference for a served cluster's threshold-BLS certificates
(`skvbc_n7_bls`): what a Prepare, Commit or full-commit-proof
certificate over a digest has to be, from the cluster's key shares
alone.

A k-of-n system deals shares f(1..n) of a polynomial f of degree k-1;
any k shares [f(i)]·H(m) combine to [f(0)]·H(m), so a certificate is
the same 48 bytes whichever shares made it. The reference interpolates
f(0) in plain integers from the first k key shares (any k give the same
value, which `consistent` holds) and multiplies once. No Lagrange
coefficient in the group, no MSM, no pairing: those are the program's.
"""
from __future__ import annotations

from cellbench.reference import bls as ref


def interpolate_at_zero(points) -> int:
    """f(0) of the one polynomial of degree len(points)-1 through
    `points`, [(i, f(i))], over the scalar field."""
    secret = 0
    for i, y in points:
        num = den = 1
        for j, _ in points:
            if j != i:
                num = num * j % ref.R
                den = den * (j - i) % ref.R
        secret = (secret + y * num * pow(den, -1, ref.R)) % ref.R
    return secret


class ThresholdSystem:
    """One k-of-n system of the cluster, from its key shares (signer i's
    is `shares[i-1]`, an integer)."""

    def __init__(self, threshold: int, shares) -> None:
        self.threshold = threshold
        self.points = [(i + 1, int(s) % ref.R) for i, s in enumerate(shares)]
        self.secret = interpolate_at_zero(self.points[:threshold])

    def consistent(self) -> bool:
        """The last k shares lie on the polynomial the first k define."""
        return interpolate_at_zero(
            self.points[-self.threshold:]) == self.secret

    def certificate(self, digest: bytes) -> bytes:
        return ref.compress(ref.mul(ref.hash_to_g1(digest), self.secret))
