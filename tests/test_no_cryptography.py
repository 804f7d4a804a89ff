"""Guard: the whole tpubft tree imports (and the host crypto works)
without the optional `cryptography` package.

The seed regression this pins down: a module-level OpenSSL import in
crypto/cpu.py broke *collection* of 32/51 test modules on hosts without
the package. The subprocess test installs a meta-path blocker that makes
any `cryptography` import raise (simulating absence even where it is
installed), then imports every module under tpubft/."""
import os
import subprocess
import sys

import pytest

_BLOCK_AND_WALK = r"""
import importlib, pkgutil, sys

class _Block:
    PREFIX = "cryptography"
    def find_module(self, name, path=None):
        if name == self.PREFIX or name.startswith(self.PREFIX + "."):
            return self
    def find_spec(self, name, path=None, target=None):
        if name == self.PREFIX or name.startswith(self.PREFIX + "."):
            raise ModuleNotFoundError(f"blocked for test: {name}")
    def load_module(self, name):
        raise ModuleNotFoundError(f"blocked for test: {name}")

sys.meta_path.insert(0, _Block())
# simulate a host that never had it installed
for k in [k for k in sys.modules if k.split(".")[0] == "cryptography"]:
    del sys.modules[k]

import tpubft
failed = []
for info in pkgutil.walk_packages(tpubft.__path__, prefix="tpubft."):
    try:
        importlib.import_module(info.name)
    except Exception as e:  # the tree contains ctypes .so artifacts that
        # walk_packages surfaces as "modules" — only a cryptography
        # dependency is a failure here
        if "cryptography" in str(e) or "blocked for test" in str(e):
            failed.append(f"{info.name}: {e}")
if failed:
    print("HARD-IMPORTS-CRYPTOGRAPHY:\n" + "\n".join(failed))
    sys.exit(1)

# the host crypto engine must actually WORK, not merely import
from tpubft.crypto import cpu
assert not cpu.have_openssl()
s = cpu.make_signer("ed25519", seed=b"no-ossl")
assert cpu.make_verifier("ed25519", s.public_bytes()).verify(
    b"m", s.sign(b"m"))
e = cpu.make_signer("ecdsa-p256", seed=b"no-ossl")
assert cpu.make_verifier("ecdsa-p256", e.public_bytes()).verify(
    b"m", e.sign(b"m"))
print("NO-CRYPTOGRAPHY-OK")
"""


@pytest.mark.slow
def test_import_tree_without_cryptography():
    """Every tpubft module imports with `cryptography` unavailable, and
    sign/verify round-trips on the pure engine. Slow: walking the tree
    imports jax/numpy-heavy modules in a fresh interpreter."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _BLOCK_AND_WALK],
                       capture_output=True, text=True, timeout=600,
                       env=env, cwd=os.path.join(os.path.dirname(__file__),
                                                 ".."))
    assert r.returncode == 0, (r.stdout + r.stderr)[-4000:]
    assert "NO-CRYPTOGRAPHY-OK" in r.stdout


def test_crypto_cpu_scalar_path_direct(scalar_engine):
    """In-process variant (fast): force the feature probe off and check
    the scalar path end to end, including cross-checking that the scalar
    engine's answer agrees with whatever backend is active."""
    from tpubft.crypto import cpu, scalar
    assert not cpu.have_openssl()
    s = cpu.Ed25519Signer.generate(seed=b"probe-off")
    sig = s.sign(b"payload")
    assert cpu.Ed25519Verifier(s.public_bytes()).verify(b"payload", sig)
    assert scalar.ed25519_verify(s.public_bytes(), b"payload", sig)
    assert not cpu.Ed25519Verifier(s.public_bytes()).verify(b"x", sig)
    for curve in ("secp256k1", "secp256r1"):
        e = cpu.EcdsaSigner.generate(curve, seed=b"probe-off")
        esig = e.sign(b"payload")
        v = cpu.EcdsaVerifier(e.public_bytes(), curve)
        assert v.verify(b"payload", esig)
        assert not v.verify(b"payload!", esig)


def test_collection_has_no_errors_without_cryptography():
    """`pytest --collect-only` must report zero collection errors in an
    environment without `cryptography` (the acceptance criterion). Cheap
    proxy when the package is genuinely absent; with it installed the
    subprocess import-walk above is the authoritative check."""
    try:
        import cryptography  # noqa: F401
        pytest.skip("cryptography installed; covered by the import walk")
    except ImportError:
        pass
    # the conftest already imported every test module's dependency chain
    # if we got here via full-suite collection; spot-check the heaviest
    # previously-broken imports directly
    import tpubft.consensus.keys        # noqa: F401
    import tpubft.consensus.sig_manager  # noqa: F401
    import tpubft.crypto.systems        # noqa: F401
    import tpubft.tools.keygen          # noqa: F401
