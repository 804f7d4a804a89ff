"""The late-decoding threshold-BLS accumulator against the eager one.

`BlsThresholdAccumulator.add` keeps a share's bytes and decodes the
whole set in one `decode_shares` call when the points are first needed.
The plain reference below is the accumulator as it stood before that:
`g1_decompress` inside `add`, one share at a time. Same `has_threshold`
after every `add`, same certificate bytes, same `identify_bad_shares`.
"""
import time
from typing import Dict, List, Optional

import pytest

from tpubft.crypto import bls12381 as bls
from tpubft.crypto import systems
from tpubft.crypto.interfaces import Cryptosystem

K, N = 5, 7
DIGEST = b"\x5a" * 32


class EagerAccumulator:
    """The per-share accumulator (PR 29's `BlsThresholdAccumulator`)."""

    def __init__(self, verifier, share_verification: bool):
        self._verifier = verifier
        self._share_verification = share_verification
        self._digest: Optional[bytes] = None
        self._shares: Dict[int, object] = {}

    def set_expected_digest(self, digest: bytes) -> None:
        self._digest = digest

    def add(self, share_id: int, share: bytes) -> int:
        if not 1 <= share_id <= self._verifier.total_signers:
            return len(self._shares)
        try:
            pt = bls.g1_decompress(share)
        except ValueError:
            return len(self._shares)
        if pt is None:
            return len(self._shares)
        if self._share_verification and self._digest is not None:
            if not self._verifier.verify_share(share_id, self._digest, share):
                return len(self._shares)
        self._shares[share_id] = pt
        return len(self._shares)

    def has_threshold(self) -> bool:
        return len(self._shares) >= self._verifier.threshold

    def get_full_signed_data(self) -> bytes:
        ids = sorted(self._shares)[: self._verifier.threshold]
        return bls.g1_compress(
            bls.combine_shares(ids, [self._shares[i] for i in ids]))

    def identify_bad_shares(self) -> List[int]:
        return self._verifier._identify_bad(self._digest, self._shares)


@pytest.fixture(scope="module")
def keys():
    return Cryptosystem("threshold-bls", K, N, seed=b"acc-batch")


@pytest.fixture(scope="module")
def shares(keys):
    """id -> its honest share over DIGEST."""
    return {i: keys.create_threshold_signer(i).sign_share(DIGEST)
            for i in range(1, N + 1)}


def _verifier(keys, backend: str):
    if backend == "host":
        return keys.create_threshold_verifier()
    from tpubft.crypto.tpu import make_threshold_verifier
    return make_threshold_verifier("threshold-bls", K, N, keys.public_key,
                                   keys.share_public_keys)


JUNK = b"\xff" * 48                                  # x out of range
OFF_CURVE = bytes([0x80]) + b"\x00" * 46 + b"\x05"   # no square root
INFINITY = bytes([0xC0]) + b"\x00" * 47
SHORT = b"\x80" * 47


def _h(i):              # "honest share of signer i", resolved in the test
    return ("honest", i)


def _as(i, j):          # signer j's share offered under id i: decodes, wrong
    return ("as", i, j)


# name -> the arrival sequence of (id, share or marker)
SCENARIOS = {
    "junk_before_honest": [(1, JUNK), (2, OFF_CURVE)]
        + [(i, _h(i)) for i in range(1, 6)],
    "junk_between_honest": [(1, _h(1)), (2, JUNK), (2, _h(2)), (3, SHORT),
                            (3, _h(3)), (4, INFINITY), (4, _h(4)),
                            (5, _h(5))],
    "junk_after_honest": [(i, _h(i)) for i in range(1, 6)]
        + [(6, JUNK), (7, SHORT), (1, INFINITY)],
    "duplicate_valid_then_invalid": [(1, _h(1)), (1, JUNK), (2, _h(2)),
                                     (3, _h(3)), (4, _h(4)), (5, _h(5))],
    "duplicate_invalid_then_valid": [(1, JUNK), (1, _h(1)), (2, _h(2)),
                                     (3, _h(3)), (4, _h(4)), (5, _h(5))],
    "duplicate_valid_then_other_valid": [(1, _as(1, 6)), (1, _h(1)),
                                         (2, _h(2)), (2, _as(2, 7)),
                                         (3, _h(3)), (4, _h(4)), (5, _h(5))],
    "id_out_of_range": [(0, _h(1)), (N + 1, _h(2)), (-3, _h(3)),
                        (9999, _h(4))] + [(i, _h(i)) for i in range(1, 6)],
    "exactly_threshold": [(i, _h(i)) for i in (7, 3, 1, 6, 2)],
    "threshold_minus_one": [(i, _h(i)) for i in (7, 3, 1, 6)]
        + [(2, JUNK)],
    "all_of_n": [(i, _h(i)) for i in range(1, N + 1)],
    "a_wrong_signers_share": [(1, _h(1)), (2, _as(2, 3)), (3, _h(3)),
                              (4, _h(4)), (5, _h(5)), (6, _h(6))],
    "nothing_valid": [(1, JUNK), (2, SHORT), (3, INFINITY), (4, OFF_CURVE)],
    "nothing_at_all": [],
}


def _resolve(seq, shares):
    out = []
    for sid, what in seq:
        if isinstance(what, tuple):
            what = shares[what[1]] if what[0] == "honest" \
                else shares[what[2]]
        out.append((sid, what))
    return out


@pytest.mark.parametrize("share_verification", [False, True],
                         ids=["deferred", "share_verification"])
@pytest.mark.parametrize("backend", ["host", "tpu"])
@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_the_accumulator_matches_the_eager_one(
        keys, shares, name, backend, share_verification):
    verifier = _verifier(keys, backend)
    seq = _resolve(SCENARIOS[name], shares)
    eager = EagerAccumulator(verifier, share_verification)
    probed = verifier.new_accumulator(share_verification)  # asked each add
    late = verifier.new_accumulator(share_verification)    # asked at the end
    assert isinstance(late, systems.BlsThresholdAccumulator)
    for acc in (eager, probed, late):
        acc.set_expected_digest(DIGEST)
    for held, (sid, share) in enumerate(seq, 1):
        eager.add(sid, share)
        probed.add(sid, share)
        assert late.add(sid, share) <= held     # held so far, not decoded
        assert probed.has_threshold() == eager.has_threshold(), held
    assert late.has_threshold() == eager.has_threshold()
    assert dict(late._shares) == dict(probed._shares) == eager._shares
    # what the outcome has to be, from the shares alone
    wrong = sorted(i for i, pt in eager._shares.items()
                   if pt != bls.g1_decompress(shares[i]))
    chosen = sorted(eager._shares)[:K]
    if eager._shares:
        want = eager.get_full_signed_data()
        assert probed.get_full_signed_data() == want
        assert late.get_full_signed_data() == want
        assert verifier.verify(DIGEST, want) == (
            len(chosen) == K and not set(chosen) & set(wrong))
    assert eager.identify_bad_shares() == wrong
    assert probed.identify_bad_shares() == wrong
    assert late.identify_bad_shares() == wrong
    if share_verification:
        assert wrong == []


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_the_fused_decode_matches_the_eager_one(keys, shares, name):
    """`_decode_job_shares` takes a slot's share dict (a later share for
    an id has already replaced the earlier one there) and keeps what the
    eager accumulator keeps of it."""
    verifier = keys.create_threshold_verifier()
    job = dict(_resolve(SCENARIOS[name], shares))
    eager = EagerAccumulator(verifier, False)
    for sid, share in job.items():
        eager.add(sid, share)
    got = verifier._decode_job_shares(job)
    assert got == eager._shares
    assert list(got) == list(eager._shares)         # dict order too


def test_a_digest_set_after_an_add_does_not_reach_back(keys, shares):
    """Share verification applies from the digest's setting on, as when
    `add` decoded on the spot: a share added before it is kept unseen."""
    verifier = keys.create_threshold_verifier()
    eager = EagerAccumulator(verifier, True)
    late = verifier.new_accumulator(True)
    for acc in (eager, late):
        acc.add(1, shares[2])           # wrong signer, no digest yet: kept
        acc.set_expected_digest(DIGEST)
        acc.add(2, shares[3])           # wrong signer, digest set: dropped
        acc.add(3, shares[3])
    assert dict(late._shares) == eager._shares
    assert sorted(eager._shares) == [1, 3]


def test_one_combine_is_one_decode_batch_and_one_span(keys, shares):
    from tpubft.utils import flight
    verifier = keys.create_threshold_verifier()
    acc = verifier.new_accumulator(False)
    acc.set_expected_digest(DIGEST)
    counters = systems.METRICS.counters
    before = {k: c.value for k, c in counters.items()}
    t0 = time.monotonic_ns()
    for sid in range(1, N + 1):
        acc.add(sid, shares[sid])
    acc.add(1, JUNK)
    assert counters["bls_decode_batches"].value == before[
        "bls_decode_batches"]                   # nothing decoded yet
    cert = acc.get_full_signed_data()
    assert verifier.verify(DIGEST, cert)
    assert counters["bls_decode_batches"].value \
        - before["bls_decode_batches"] == 1
    assert counters["bls_shares_batch_decoded"].value \
        - before["bls_shares_batch_decoded"] == N + 1
    if flight.enabled():
        spans = flight.span_events("bls_share_decompress", t0)
        assert spans is not None and len(spans) == 1
