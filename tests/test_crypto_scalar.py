"""Self-hosted scalar engine: RFC vectors, kernel cross-checks, keyfile
byte-compatibility.

The scalar engine (tpubft/crypto/scalar.py) is the repo-owned ground
truth the batched device kernels are validated against — and vice
versa: scalar signing must produce signatures the kernels accept for
Ed25519 and both ECDSA curves, making the stack self-validating with no
third-party reference implementation in the loop."""
import hashlib

import pytest

from tpubft.crypto import cpu, scalar

# ---------------- RFC 8032 §7.1 test vectors ----------------

RFC8032 = [
    # (secret key, public key, message, signature)
    ("9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60",
     "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a",
     "",
     "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
     "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"),
    ("4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb",
     "3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c",
     "72",
     "92a009a9f0d4cab8720e820b5f642540a2b27b5416503f8fb3762223ebdb69da"
     "085ac1e43e15996e458f3613d0f11d8c387b2eaeb4302aeeb00d291612bb0c00"),
    ("c5aa8df43f9f837bedb7442f31dcb7b166d38535076f094b85ce3a2e0b4458f7",
     "fc51cd8e6218a1a38da47ed00230f0580816ed13ba3303ac5deb911548908025",
     "af82",
     "6291d657deec24024827e69c3abe01a30ce548a284743a445e3680d7db5ac3ac"
     "18ff9b538d16f290ae67f760984dc6594a7c15e9716ed28dc027beceea1ec40a"),
]


@pytest.mark.parametrize("sk,pk,msg,sig", RFC8032)
def test_ed25519_rfc8032_vectors(sk, pk, msg, sig):
    sk, pk = bytes.fromhex(sk), bytes.fromhex(pk)
    msg, sig = bytes.fromhex(msg), bytes.fromhex(sig)
    assert scalar.ed25519_public_key(sk) == pk
    assert scalar.ed25519_sign(sk, msg) == sig
    assert scalar.ed25519_verify(pk, msg, sig)
    assert not scalar.ed25519_verify(pk, msg + b"x", sig)
    assert not scalar.ed25519_verify(pk, msg, sig[:-1] + b"\x01")


def test_ed25519_rejects_malleated_s():
    sk, pk, msg, sig = (bytes.fromhex(RFC8032[0][0]),
                        bytes.fromhex(RFC8032[0][1]), b"",
                        bytes.fromhex(RFC8032[0][3]))
    s = int.from_bytes(sig[32:], "little")
    high_s = (s + scalar.L).to_bytes(32, "little")
    assert not scalar.ed25519_verify(pk, msg, sig[:32] + high_s)


def test_rfc6979_p256_sample_vector():
    """RFC 6979 A.2.5 (P-256, SHA-256, message 'sample'): deterministic
    ECDSA must reproduce the spec's exact signature."""
    d = 0xC9AFA9D845BA75166B5C215767B1D6934E50C3DB36E89B127B8A622B120F6721
    sig = scalar.ecdsa_sign(d, b"sample", "secp256r1")
    assert sig.hex().upper() == (
        "EFD48B2AACB6A8FD1140DD9CD45E81D69D2C877B56AAF991C34D0EA84EAF3716"
        "F7CB1C942D657C41D436C7A1B6E29F65F3E900DBB9AFF4064DC4AB2F843ACDA8")
    assert scalar.ecdsa_verify(scalar.ecdsa_public_key(d, "secp256r1"),
                               b"sample", sig, "secp256r1")


def test_curve_parameters_mirror_device_kernels():
    """scalar.CURVES is a dependency-free duplicate of ops/ecdsa.CURVES
    — they must never drift."""
    from tpubft.ops.ecdsa import CURVES as DEVICE_CURVES
    assert scalar.CURVES == DEVICE_CURVES


def test_ed25519_sign_batch_byte_identical():
    """The batched signer (one Montgomery inversion for the whole
    batch) must land the exact bytes of the per-item RFC 8032 path —
    clients accept f+1 MATCHING replies, so replicas may never disagree
    on a signature's bytes."""
    sk = scalar.ed25519_seed_to_private(b"batch-sign-seed")
    pk = scalar.ed25519_public_key(sk)
    msgs = [b"reply-%d" % i for i in range(17)] + [b"", b"\x00" * 200]
    batch = scalar.ed25519_sign_batch(sk, msgs, pk=pk)
    assert batch == [scalar.ed25519_sign(sk, m, pk=pk) for m in msgs]
    for m, sig in zip(msgs, batch):
        assert scalar.ed25519_verify(pk, m, sig)
    assert scalar.ed25519_sign_batch(sk, []) == []
    # signer-level seam: the cpu signer's sign_batch agrees with sign
    s = cpu.Ed25519Signer.generate(seed=b"batch-sign-seed2")
    assert s.sign_batch(msgs[:5]) == [s.sign(m) for m in msgs[:5]]


# ---------------- scalar sign → device kernel verify ----------------

# ~22 s of kernel compiles; every tpu-backend cluster test exercises
# host-sign -> device-verify end to end in tier-1
@pytest.mark.slow
def test_scalar_ed25519_signs_for_the_kernel():
    from tpubft.ops import ed25519 as dev
    signers = [cpu.Ed25519Signer.generate(seed=b"xk%d" % i)
               for i in range(4)]
    items = [(b"msg-%d" % i, s.sign(b"msg-%d" % i), s.public_bytes())
             for i, s in enumerate(signers)]
    # tampered row: kernel must reject exactly it
    bad = (b"tampered", items[0][1], items[0][2])
    verdicts = dev.verify_batch(items + [bad])
    assert list(verdicts) == [True] * 4 + [False]
    # and the scalar verifier agrees with the kernel on every row
    for (m, sig, pk), v in zip(items + [bad], verdicts):
        assert scalar.ed25519_verify(pk, m, sig) == bool(v)


@pytest.mark.parametrize("curve", ["secp256k1", "secp256r1"])
def test_scalar_ecdsa_signs_for_the_kernel(curve):
    from tpubft.ops import ecdsa as dev
    signers = [cpu.EcdsaSigner.generate(curve, seed=b"xc%d" % i)
               for i in range(3)]
    items = [(b"msg-%d" % i, s.sign(b"msg-%d" % i), s.public_bytes())
             for i, s in enumerate(signers)]
    bad = (b"tampered", items[0][1], items[0][2])
    verdicts = dev.verify_batch(curve, items + [bad])
    assert list(verdicts) == [True] * 3 + [False]
    for (m, sig, pk), v in zip(items + [bad], verdicts):
        assert scalar.ecdsa_verify(pk, m, sig, curve) == bool(v)


# ---------------- keyfile byte-compatibility ----------------

# Golden seed→pubkey derivations: these lock the historical keyfile
# formulas (sha256("ed25519-keygen"+seed), sha512("ecdsa-keygen"+seed)
# folded into [1, n-1]). If any of these change, existing on-disk
# keyfiles stop matching their principals.
GOLDEN_SEED = b"tpubft-golden"
GOLDEN = {
    "ed25519":
        "e57bf3c027d9dd4a8577fe9e75ee44af8b658a5b8d31e993b00a9b8fb119b89d",
    "secp256k1":
        "049e82b4cd5c3d6b2029f6c6dc5fc8b10f518b3a79447a0e9b773da500b26b85"
        "4df472dd9ffc79e527f8a8a8b2b883cbfd37e0d8241a4fcdd1e5c7822120f681c3",
    "secp256r1":
        "04267b88ebad9e76b4dc952023831e10568180afaff6af592afc4f761deeea27"
        "97b846e54a3127970993d9e69859ba0be5b0a36500b5ea605921814dbe2bda2f5a",
}


def test_seed_derivation_locked():
    assert cpu.Ed25519Signer.generate(seed=GOLDEN_SEED).public_bytes() \
        == bytes.fromhex(GOLDEN["ed25519"])
    for curve in ("secp256k1", "secp256r1"):
        assert cpu.EcdsaSigner.generate(curve, seed=GOLDEN_SEED) \
            .public_bytes() == bytes.fromhex(GOLDEN[curve])
    # derivation formulas, spelled out
    assert cpu.Ed25519Signer.generate(seed=b"s").private_bytes \
        == hashlib.sha256(b"ed25519-keygen" + b"s").digest()
    n = scalar.CURVES["secp256k1"]["n"]
    assert cpu.EcdsaSigner.generate("secp256k1", seed=b"s").private_value \
        == int.from_bytes(hashlib.sha512(b"ecdsa-keygen" + b"s").digest(),
                          "big") % (n - 1) + 1


def test_keygen_keyfiles_roundtrip(tmp_path):
    """tpubft.tools.keygen generate → load_keyfile → self-verify, on the
    self-hosted engine (no OpenSSL required anywhere in the path)."""
    import argparse

    from tpubft.tools import keygen

    args = argparse.Namespace(f=1, c=0, ro=0, clients=2,
                              out=str(tmp_path), seed="compat-cluster",
                              password=None, tls_certs=False)
    assert keygen.generate(args) == 0
    for name in ("replica-0.keys", "replica-3.keys", "client-4.keys",
                 "operator.keys"):
        keys = keygen.load_keyfile(str(tmp_path / name))
        v = argparse.Namespace(keyfile=str(tmp_path / name), password=None)
        assert keygen.verify(v) == 0, name
        signer = keys.my_signer()
        expect = (keys.replica_pubkeys.get(keys.my_id)
                  or keys.client_pubkeys.get(keys.my_id))
        assert signer.public_bytes() == expect


def test_random_keygen_roundtrips():
    s = cpu.Ed25519Signer.generate()
    assert cpu.Ed25519Verifier(s.public_bytes()).verify(b"m", s.sign(b"m"))
    e = cpu.EcdsaSigner.generate("secp256r1")
    assert cpu.EcdsaVerifier(e.public_bytes(), "secp256r1").verify(
        b"m", e.sign(b"m"))


def test_ecdsa_verifier_rejects_bad_pubkey(scalar_engine):
    """The scalar engine admits exactly the wire format: an uncompressed
    on-curve SEC1 point."""
    with pytest.raises(ValueError):
        cpu.EcdsaVerifier(b"\x04" + b"\x01" * 64, "secp256k1")
    with pytest.raises(ValueError):
        cpu.EcdsaVerifier(b"\x02" + b"\x01" * 32, "secp256k1")


def test_ecdsa_verifier_rejects_off_curve_pubkey_openssl():
    """With `cryptography` installed the constructor goes through
    OpenSSL, which refuses an off-curve point the same way."""
    if not cpu.have_openssl():
        pytest.skip("cryptography not installed")
    with pytest.raises(ValueError):
        cpu.EcdsaVerifier(b"\x04" + b"\x01" * 64, "secp256k1")
