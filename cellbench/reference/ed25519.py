"""Plain reference for the ed25519 plane of `flood_n1000`: RFC 8032
through OpenSSL (the `cryptography` package), nothing of the program
imported. Keys come from 32-byte seeds, so the reference holds the
principals it judges."""
from __future__ import annotations

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey, Ed25519PublicKey)
from cryptography.hazmat.primitives.serialization import (Encoding,
                                                          PublicFormat)


class Signer:
    def __init__(self, seed32: bytes) -> None:
        self._sk = Ed25519PrivateKey.from_private_bytes(seed32)
        self.public = self._sk.public_key().public_bytes(
            Encoding.Raw, PublicFormat.Raw)

    def sign(self, msg: bytes) -> bytes:
        return self._sk.sign(msg)


def verify(public: bytes, msg: bytes, sig: bytes) -> bool:
    try:
        Ed25519PublicKey.from_public_bytes(public).verify(sig, msg)
    except (InvalidSignature, ValueError):
        return False
    return True
