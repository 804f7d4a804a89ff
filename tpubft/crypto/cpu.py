"""Host signer/verifier backends — self-hosted engine, OpenSSL optional.

Rebuild of the reference's crypto_utils (Crypto++ RSA/ECDSA signers —
/root/reference/util/include/crypto_utils.hpp:41-100) plus the EdDSA
path, with one crucial delta: the implementation underneath is OURS.
The pure-python scalar engine (tpubft/crypto/scalar.py) provides
Ed25519 + ECDSA sign/verify/keygen from the stdlib alone; the
third-party `cryptography` package (OpenSSL) is a soft OPTIONAL
accelerator, probed at runtime and used only when importable. No module
under tpubft/ may hard-import it (tools/check_imports.py enforces
this) — the repo must work fully offline, because the batched device
kernels in tpubft/ops are the primary verification plane and the host
engine exists for signing, keygen, and small/cold verifies.

Backend order for a verify (see docs/OPERATIONS.md):
  1. batched device kernels — SigManager.verify_batch / BatchVerifier;
  2. OpenSSL via `cryptography`, when present (`have_openssl()`);
  3. the scalar engine — always available.

All signatures use fixed-length raw encodings so wire messages have
static layouts (TPU batches need fixed shapes).
"""
from __future__ import annotations

import functools
import os
from typing import Optional

from tpubft.crypto import scalar
from tpubft.crypto.interfaces import ISigner, IVerifier

ED25519_SIG_LEN = 64
ED25519_PK_LEN = 32
ECDSA_SIG_LEN = 64  # raw r||s, 32B each


@functools.lru_cache(maxsize=1)
def _openssl():
    """Feature probe for the optional OpenSSL stack: the needed
    `cryptography` submodules as a namespace, or None. Never raises.
    TPUBFT_NO_OPENSSL=1 forces the scalar engine (tests use it to pin
    down the pure path even where `cryptography` is installed)."""
    if os.environ.get("TPUBFT_NO_OPENSSL"):
        return None
    try:
        from cryptography.exceptions import InvalidSignature
        from cryptography.hazmat.primitives import hashes, serialization
        from cryptography.hazmat.primitives.asymmetric import ec, ed25519
        from cryptography.hazmat.primitives.asymmetric.utils import (
            decode_dss_signature, encode_dss_signature)
    except Exception:  # noqa: BLE001 — any import failure = not available
        return None
    import types
    return types.SimpleNamespace(
        InvalidSignature=InvalidSignature, hashes=hashes,
        serialization=serialization, ec=ec, ed25519=ed25519,
        decode_dss=decode_dss_signature, encode_dss=encode_dss_signature)


def have_openssl() -> bool:
    """True when the optional OpenSSL accelerator is importable."""
    return _openssl() is not None


# ---------------- Ed25519 ----------------

class Ed25519Signer(ISigner):
    def __init__(self, private_key_bytes: bytes):
        if len(private_key_bytes) != 32:
            raise ValueError("ed25519 private key must be 32 bytes")
        self.private_bytes = private_key_bytes
        ossl = _openssl()
        self._sk = (ossl.ed25519.Ed25519PrivateKey.from_private_bytes(
            private_key_bytes) if ossl is not None else None)
        self._pub: Optional[bytes] = None

    @classmethod
    def generate(cls, seed: Optional[bytes] = None) -> "Ed25519Signer":
        if seed is not None:
            return cls(scalar.ed25519_seed_to_private(seed))
        return cls(os.urandom(32))

    def sign(self, data: bytes) -> bytes:
        if self._sk is not None:
            return self._sk.sign(data)
        return scalar.ed25519_sign(self.private_bytes, data,
                                   pk=self.public_bytes())

    def sign_batch(self, datas) -> list:
        """Batch signing seam (SigManager.sign_batch): OpenSSL stays a
        per-item loop (its one-shot sign has no batch API), the
        self-hosted engine amortizes the per-signature field inversion
        across the batch (scalar.ed25519_sign_batch)."""
        if self._sk is not None:
            return [self._sk.sign(d) for d in datas]
        return scalar.ed25519_sign_batch(self.private_bytes, datas,
                                         pk=self.public_bytes())

    @property
    def signature_length(self) -> int:
        return ED25519_SIG_LEN

    def public_bytes(self) -> bytes:
        if self._pub is None:
            if self._sk is not None:
                ossl = _openssl()
                self._pub = self._sk.public_key().public_bytes(
                    ossl.serialization.Encoding.Raw,
                    ossl.serialization.PublicFormat.Raw)
            else:
                self._pub = scalar.ed25519_public_key(self.private_bytes)
        return self._pub


class Ed25519Verifier(IVerifier):
    def __init__(self, public_key_bytes: bytes):
        if len(public_key_bytes) != ED25519_PK_LEN:
            raise ValueError("ed25519 public key must be 32 bytes")
        self.public_key_bytes = public_key_bytes
        ossl = _openssl()
        self._pk = (ossl.ed25519.Ed25519PublicKey.from_public_bytes(
            public_key_bytes) if ossl is not None else None)

    def verify(self, data: bytes, sig: bytes) -> bool:
        if len(sig) != ED25519_SIG_LEN:
            return False
        if self._pk is not None:
            try:
                self._pk.verify(sig, data)
                return True
            except _openssl().InvalidSignature:
                return False
        return scalar.ed25519_verify(self.public_key_bytes, data, sig)

    @property
    def signature_length(self) -> int:
        return ED25519_SIG_LEN


# ---------------- ECDSA (secp256k1 / P-256), raw r||s signatures ----------------

def _ossl_curve(ossl, curve: str):
    return {"secp256k1": ossl.ec.SECP256K1,
            "secp256r1": ossl.ec.SECP256R1}[curve]()


class EcdsaSigner(ISigner):
    def __init__(self, private_value: int, curve: str = "secp256k1"):
        if curve not in scalar.CURVES:
            raise ValueError(f"unknown curve {curve}")
        if not 1 <= private_value < scalar.CURVES[curve]["n"]:
            # same construction-time validation as the OpenSSL path
            # (ec.derive_private_key) — invalid keys must not fail late
            # with backend-dependent errors
            raise ValueError("ECDSA private value out of range [1, n-1]")
        self.curve_name = curve
        self.private_value = private_value
        ossl = _openssl()
        self._sk = (ossl.ec.derive_private_key(
            private_value, _ossl_curve(ossl, curve))
            if ossl is not None else None)
        self._pub: Optional[bytes] = None

    @classmethod
    def generate(cls, curve: str = "secp256k1",
                 seed: Optional[bytes] = None) -> "EcdsaSigner":
        if seed is not None:
            return cls(scalar.ecdsa_seed_to_private(seed, curve), curve)
        return cls(scalar.ecdsa_random_private(curve), curve)

    def sign(self, data: bytes) -> bytes:
        if self._sk is not None:
            ossl = _openssl()
            der = self._sk.sign(data, ossl.ec.ECDSA(ossl.hashes.SHA256()))
            r, s = ossl.decode_dss(der)
            return r.to_bytes(32, "big") + s.to_bytes(32, "big")
        return scalar.ecdsa_sign(self.private_value, data, self.curve_name)

    @property
    def signature_length(self) -> int:
        return ECDSA_SIG_LEN

    def public_bytes(self) -> bytes:
        """Uncompressed SEC1 point (0x04 || x || y), 65 bytes."""
        if self._pub is None:
            if self._sk is not None:
                ossl = _openssl()
                self._pub = self._sk.public_key().public_bytes(
                    ossl.serialization.Encoding.X962,
                    ossl.serialization.PublicFormat.UncompressedPoint)
            else:
                self._pub = scalar.ecdsa_public_key(self.private_value,
                                                    self.curve_name)
        return self._pub


class EcdsaVerifier(IVerifier):
    def __init__(self, public_key_bytes: bytes, curve: str = "secp256k1"):
        if curve not in scalar.CURVES:
            raise ValueError(f"unknown curve {curve}")
        self.curve_name = curve
        self.public_key_bytes = public_key_bytes
        # SEC1 uncompressed only, whatever backs the verifier: OpenSSL
        # would decode a compressed point too, the batched host engine
        # and the device tier (crypto/scalar._pk_entry) would not, and a
        # verdict must not depend on the tier that gave it
        if len(public_key_bytes) != 65 or public_key_bytes[0] != 0x04:
            raise ValueError("invalid SEC1 uncompressed public key")
        ossl = _openssl()
        if ossl is not None:
            # raises ValueError on an off-curve point, matching the
            # scalar-path check below
            self._pk = ossl.ec.EllipticCurvePublicKey.from_encoded_point(
                _ossl_curve(ossl, curve), public_key_bytes)
        else:
            self._pk = None
            if not scalar.ecdsa_on_curve(
                    int.from_bytes(public_key_bytes[1:33], "big"),
                    int.from_bytes(public_key_bytes[33:], "big"), curve):
                raise ValueError("invalid SEC1 uncompressed public key")

    def verify(self, data: bytes, sig: bytes) -> bool:
        if len(sig) != ECDSA_SIG_LEN:
            return False
        if self._pk is not None:
            ossl = _openssl()
            r = int.from_bytes(sig[:32], "big")
            s = int.from_bytes(sig[32:], "big")
            try:
                self._pk.verify(ossl.encode_dss(r, s), data,
                                ossl.ec.ECDSA(ossl.hashes.SHA256()))
                return True
            except (ossl.InvalidSignature, ValueError):
                return False
        return scalar.ecdsa_verify(self.public_key_bytes, data, sig,
                                   self.curve_name)

    @property
    def uses_scalar_engine(self) -> bool:
        """True when verifies run on the in-repo scalar engine (no
        OpenSSL) — the shape whose batches ride ecdsa_verify_batch."""
        return self._pk is None

    def verify_batch(self, items) -> list:
        """Batch verification through the Montgomery/comb engine
        (scalar.ecdsa_verify_batch) when the scalar path would carry the
        items anyway: this is what keeps degraded mode (breaker OPEN, no
        device, no OpenSSL) at thousands of verifies/sec instead of the
        per-item ladder's tens. With OpenSSL present the per-item
        C-backed verify is already faster than the batched python walk."""
        if self.uses_scalar_engine and len(items) > 1:
            return scalar.ecdsa_verify_batch(
                [(self.public_key_bytes, d, s) for d, s in items],
                self.curve_name)
        return [self.verify(d, s) for d, s in items]

    @property
    def signature_length(self) -> int:
        return ECDSA_SIG_LEN


def make_signer(scheme: str, seed: Optional[bytes] = None) -> ISigner:
    if scheme == "ed25519":
        return Ed25519Signer.generate(seed=seed)
    if scheme in ("ecdsa-secp256k1", "secp256k1"):
        return EcdsaSigner.generate("secp256k1", seed=seed)
    if scheme in ("ecdsa-secp256r1", "secp256r1", "ecdsa-p256"):
        return EcdsaSigner.generate("secp256r1", seed=seed)
    raise ValueError(f"unknown signature scheme {scheme}")


def make_verifier(scheme: str, public_key_bytes: bytes) -> IVerifier:
    if scheme == "ed25519":
        return Ed25519Verifier(public_key_bytes)
    if scheme in ("ecdsa-secp256k1", "secp256k1"):
        return EcdsaVerifier(public_key_bytes, "secp256k1")
    if scheme in ("ecdsa-secp256r1", "secp256r1", "ecdsa-p256"):
        return EcdsaVerifier(public_key_bytes, "secp256r1")
    raise ValueError(f"unknown signature scheme {scheme}")
