"""Verified crypto-offload tier (ISSUE 20): helper fault matrix.

Pins the tier's three contracts:

  * byte-identity — every verdict-producing path (threshold combine,
    multisig sum, ECDSA RLC fold) returns output byte-identical to the
    offload-off local path, for honest helpers AND every lying shape
    (the soundness check catches the lie before it can touch a
    verdict);
  * bounded blast radius — each fault shape costs exactly one local
    re-run and fails only its own lease: Byzantine shapes (wrong point,
    wrong-but-on-curve, garbage bytes, stale lease replay, flipped
    verdict bits) are evicted into quarantine with NO cooldown
    re-admission (operator reset is the one way back); transport
    shapes (slow-loris past the lease deadline, crash) are merely SICK
    — breaker cooldown + probe re-admission, PR 16 discipline;
  * liveness — with the pool down to zero usable helpers every call
    degrades to the local path; nothing waits, nothing wedges.
"""
import time

import pytest

from tpubft.crypto import bls12381 as bls
from tpubft.crypto import cpu
from tpubft.crypto.interfaces import Cryptosystem
from tpubft.offload.helper import HelperServer
from tpubft.offload.pool import (InprocHelper, combine_via_offload,
                                 ecdsa_via_offload, get_offload_pool,
                                 reset_offload_pool, sum_via_offload)
from tpubft.utils.breaker import CLOSED, OPEN, BreakerOpen, get_breaker


@pytest.fixture(autouse=True)
def _clean_pool():
    reset_offload_pool()
    yield
    reset_offload_pool()


def _pool_with(*servers, timeout_ms=30000):
    pool = get_offload_pool()
    pool.configure(enabled=True, lease_timeout_ms=timeout_ms,
                   max_inflight=4)
    for s in servers:
        pool.add_helper(InprocHelper(s.helper_id, s))
    return pool


# ---------------------------------------------------------------------
# shared BLS threshold fixture material (3-of-4)
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def thr():
    return Cryptosystem("threshold-bls", 3, 4, seed=b"offload-fault")


def _combine_job(thr, digest, sids=(1, 2, 3)):
    """(segments, digests, local_fn-args) for one combine lease."""
    pts = {sid: bls.g1_decompress(
        thr.create_threshold_signer(sid).sign_share(digest))
        for sid in sids}
    ids = sorted(pts)
    return [(ids, [pts[i] for i in ids])], [digest]


def _counted_local(segments):
    calls = []

    def local_fn():
        calls.append(1)
        return [bls.combine_shares(ids, pts) if ids else None
                for ids, pts in segments]
    return local_fn, calls


# ---------------------------------------------------------------------
# threshold combine: honest + Byzantine shapes
# ---------------------------------------------------------------------

def test_honest_combine_verified_and_identical(thr):
    pool = _pool_with(HelperServer("h0"))
    segments, digests = _combine_job(thr, b"d" * 32)
    local_fn, calls = _counted_local(segments)
    out = combine_via_offload(segments, digests, thr.public_key, local_fn)
    assert out is not None
    want = [bls.combine_shares(ids, pts) for ids, pts in segments]
    assert [bls.g1_compress(p) for p in out] \
        == [bls.g1_compress(p) for p in want]
    assert calls == [], "honest lease must not pay a local re-run"
    snap = pool.snapshot()
    assert snap["counters"]["lease_verified"] == 1
    assert snap["counters"]["lease_rejected"] == 0
    assert snap["quarantined"] == []
    assert get_breaker("helper.h0").state == CLOSED


@pytest.mark.parametrize("strategy", ["wrong-point", "wrong-on-curve",
                                      "garbage"])
def test_lying_combine_costs_one_rerun_and_quarantine(thr, strategy):
    """Each content-level lie: caught by the soundness check, exactly
    one local re-run, byte-identical output, liar quarantined."""
    pool = _pool_with(HelperServer("liar", strategy=strategy))
    segments, digests = _combine_job(thr, b"e" * 32)
    local_fn, calls = _counted_local(segments)
    out = combine_via_offload(segments, digests, thr.public_key, local_fn)
    want = [bls.combine_shares(ids, pts) for ids, pts in segments]
    assert out is not None and [bls.g1_compress(p) for p in out] \
        == [bls.g1_compress(p) for p in want], \
        f"{strategy}: lie reached the caller"
    assert calls == [1], f"{strategy}: expected exactly one local re-run"
    snap = pool.snapshot()
    assert snap["quarantined"] == ["liar"], snap
    assert snap["counters"]["lease_rejected"] == 1
    assert snap["counters"]["helper_evicted"] == 1
    assert get_breaker("helper.liar").state == OPEN


def test_stale_replay_fails_only_its_own_lease(thr):
    """Replay shape: the first lease is genuine (cached + verified);
    the second gets the stale envelope — lease-id binding catches it,
    the liar is quarantined, and the caller simply falls local."""
    pool = _pool_with(HelperServer("replayer", strategy="stale-replay"))
    seg1, dig1 = _combine_job(thr, b"f" * 32)
    local1, calls1 = _counted_local(seg1)
    out1 = combine_via_offload(seg1, dig1, thr.public_key, local1)
    assert out1 is not None and calls1 == []   # first lease untouched
    assert pool.snapshot()["counters"]["lease_verified"] == 1
    seg2, dig2 = _combine_job(thr, b"g" * 32, sids=(2, 3, 4))
    local2, calls2 = _counted_local(seg2)
    out2 = combine_via_offload(seg2, dig2, thr.public_key, local2)
    # the stale envelope never reaches the soundness layer: the pool
    # rejects it, evicts, and reports "no lease" — caller runs local
    assert out2 is None
    assert calls2 == []
    snap = pool.snapshot()
    assert snap["quarantined"] == ["replayer"], snap
    assert get_breaker("helper.replayer").state == OPEN


def test_no_cooldown_readmission_for_byzantine_only_operator_reset(thr):
    """Quarantine is not a cooldown: even with the breaker's clock run
    far past any cooldown a Byzantine helper stays out; operator_reset
    is the single path back, after which leases flow again."""
    pool = _pool_with(HelperServer("liar", strategy="wrong-on-curve"))
    segments, digests = _combine_job(thr, b"h" * 32)
    local_fn, _ = _counted_local(segments)
    combine_via_offload(segments, digests, thr.public_key, local_fn)
    assert pool.snapshot()["quarantined"] == ["liar"]
    br = get_breaker("helper.liar")
    assert not br.allow()
    # even if an operator fat-fingers the BREAKER cooldown down to
    # nothing, the pool-level quarantine set still refuses the helper:
    # quarantine is a set, not a cooldown
    br.configure(cooldown_s=0.01)
    time.sleep(0.05)
    assert pool._pick(set()) is None
    local2, calls2 = _counted_local(segments)
    assert combine_via_offload(segments, digests, thr.public_key,
                               local2) is None
    assert calls2 == []              # caller falls local on its own
    # operator reset: helper re-admitted, next lease verified — the
    # server object itself now behaves (strategy swapped to honest)
    pool._helpers["liar"].server.set_strategy("honest")
    pool.operator_reset("liar")
    assert get_breaker("helper.liar").state == CLOSED
    local3, calls3 = _counted_local(segments)
    out = combine_via_offload(segments, digests, thr.public_key, local3)
    assert out is not None and calls3 == []


# ---------------------------------------------------------------------
# transport shapes: sick, not Byzantine
# ---------------------------------------------------------------------

def test_slow_loris_is_sick_not_byzantine(thr):
    """A helper that answers late misses the lease deadline: breaker
    failure (cooldown + probe re-admission), never quarantine."""
    slow = HelperServer("slow", strategy="slow-loris", slow_s=0.05)
    pool = _pool_with(slow, timeout_ms=1)
    segments, digests = _combine_job(thr, b"i" * 32)
    local_fn, calls = _counted_local(segments)
    out = combine_via_offload(segments, digests, thr.public_key, local_fn)
    assert out is None and calls == []       # caller falls local
    snap = pool.snapshot()
    assert snap["quarantined"] == [], "slow helper must NOT be Byzantine"
    assert snap["counters"]["lease_timeouts"] >= 1
    br = get_breaker("helper.slow")
    assert br.failures >= 1
    # heal: helper turns honest, deadline widened; after the breaker's
    # cooldown the probe re-admits it — PR 16 discipline
    slow.set_strategy("honest")
    pool.configure(lease_timeout_ms=30000)
    br.configure(cooldown_s=0.01)
    while br.state != OPEN:                  # drive it OPEN first
        try:
            with br.attempt("lease"):
                raise OSError("still sick")
        except (OSError, BreakerOpen):
            pass
    time.sleep(0.3)
    out2 = combine_via_offload(segments, digests, thr.public_key,
                               _counted_local(segments)[0])
    assert out2 is not None, "healed helper not re-admitted after probe"
    assert br.state == CLOSED


def test_crash_is_sick_and_pool_degrades_to_local(thr):
    pool = _pool_with(HelperServer("flaky", strategy="crash"))
    segments, digests = _combine_job(thr, b"j" * 32)
    local_fn, calls = _counted_local(segments)
    out = combine_via_offload(segments, digests, thr.public_key, local_fn)
    assert out is None and calls == []
    assert pool.snapshot()["quarantined"] == []
    assert get_breaker("helper.flaky").failures >= 1


def test_retry_lands_on_second_helper_in_same_flush(thr):
    """Deadline-miss then retry: the lease re-runs on the OTHER helper
    inside the same call; the flush never sees the failure."""
    slow = HelperServer("slow", strategy="slow-loris", slow_s=0.2)
    good = HelperServer("good")
    pool = _pool_with(slow, good, timeout_ms=50)
    segments, digests = _combine_job(thr, b"k" * 32)
    # try until round-robin starts the lease on the slow helper (the
    # retry path is the one under test)
    for _ in range(4):
        local_fn, calls = _counted_local(segments)
        out = combine_via_offload(segments, digests, thr.public_key,
                                  local_fn)
        assert out is not None and calls == []
    snap = pool.snapshot()
    assert snap["counters"]["lease_timeouts"] >= 1, \
        "slow helper never hit its deadline"
    assert snap["counters"]["lease_verified"] == 4
    assert snap["quarantined"] == []


# ---------------------------------------------------------------------
# multisig sum plane
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def ms():
    return Cryptosystem("multisig-bls", 3, 4, seed=b"offload-ms")


def _sum_job(ms, digest, sids=(1, 2, 4)):
    from tpubft.crypto.tpu import make_threshold_verifier
    v = make_threshold_verifier("multisig-bls", 3, 4, ms.public_key,
                                ms.share_public_keys)
    pts = [bls.g1_decompress(
        ms.create_threshold_signer(sid).sign_share(digest)[:48])
        for sid in sids]
    return v, [pts], [(digest, tuple(sids))]


def _counted_sum_local(segments):
    calls = []

    def local_fn():
        calls.append(1)
        out = []
        for pts in segments:
            acc = pts[0]
            for p in pts[1:]:
                acc = bls.g1_add(acc, p)
            out.append(acc)
        return out
    return local_fn, calls


def test_honest_sum_verified_and_identical(ms):
    pool = _pool_with(HelperServer("h0"))
    v, segments, meta = _sum_job(ms, b"m" * 32)
    local_fn, calls = _counted_sum_local(segments)
    out = sum_via_offload(segments, meta, v, local_fn)
    assert out is not None and calls == []
    want = _counted_sum_local(segments)[0]()
    assert [bls.g1_compress(p) for p in out] \
        == [bls.g1_compress(p) for p in want]
    assert pool.snapshot()["counters"]["lease_verified"] == 1


def test_lying_sum_caught_and_quarantined(ms):
    pool = _pool_with(HelperServer("liar", strategy="wrong-on-curve"))
    v, segments, meta = _sum_job(ms, b"n" * 32)
    local_fn, calls = _counted_sum_local(segments)
    out = sum_via_offload(segments, meta, v, local_fn)
    want = _counted_sum_local(segments)[0]()
    assert out is not None and [bls.g1_compress(p) for p in out] \
        == [bls.g1_compress(p) for p in want]
    assert calls == [1]
    assert pool.snapshot()["quarantined"] == ["liar"]


# ---------------------------------------------------------------------
# ECDSA verdict plane
# ---------------------------------------------------------------------

def _ecdsa_corpus(curve="secp256k1"):
    s1 = cpu.EcdsaSigner.generate(curve, seed=b"off-1")
    s2 = cpu.EcdsaSigner.generate(curve, seed=b"off-2")
    items = []
    for i in range(4):
        signer = s1 if i % 2 else s2
        m = b"off-msg-%d" % i
        items.append((m, signer.sign(m), signer.public_bytes()))
    # one forgery so the verdict vector is mixed
    items.append((b"forged", items[0][1], items[0][2]))
    want = [True, True, True, True, False]
    return items, want


def _counted_ecdsa_local(curve, items):
    calls = []

    def local_fn():
        calls.append(1)
        from tpubft.ops import ecdsa as ops_ecdsa
        return [bool(x) for x in ops_ecdsa.rlc_verify_batch(curve, items)]
    return local_fn, calls


def test_honest_ecdsa_verdicts_identical():
    pool = _pool_with(HelperServer("h0"))
    items, want = _ecdsa_corpus()
    local_fn, calls = _counted_ecdsa_local("secp256k1", items)
    out = ecdsa_via_offload("secp256k1", items, local_fn)
    assert out == want and calls == []
    assert pool.snapshot()["counters"]["lease_verified"] == 1


# wrong-point flips EVERY verdict bit, so the soundness layer pays the
# full host re-check of all plausible rejects (~17s warm on the 1-core
# host) — slow-marked; the cheap lying shapes keep the path in tier-1
@pytest.mark.parametrize("strategy", [
    pytest.param("wrong-point", marks=pytest.mark.slow),
    "wrong-on-curve", "garbage"])
def test_lying_ecdsa_verdicts_caught(strategy):
    """Flipped bits (either direction) and malformed payloads: the
    re-fold check refuses them, the liar is evicted, the caller gets
    the local verdict vector — byte-identical to offload-off."""
    pool = _pool_with(HelperServer("liar", strategy=strategy))
    items, want = _ecdsa_corpus()
    local_fn, calls = _counted_ecdsa_local("secp256k1", items)
    out = ecdsa_via_offload("secp256k1", items, local_fn)
    assert out == want, f"{strategy}: lie reached the caller"
    assert calls == [1], f"{strategy}: expected exactly one local re-run"
    assert pool.snapshot()["quarantined"] == ["liar"]


# ---------------------------------------------------------------------
# pool mechanics
# ---------------------------------------------------------------------

def test_inflight_cap_degrades_to_local(thr):
    pool = _pool_with(HelperServer("h0"))
    pool.configure(max_inflight=1)
    with pool._mu:
        pool._inflight = 1          # simulate a saturated tier
    segments, digests = _combine_job(thr, b"p" * 32)
    local_fn, calls = _counted_local(segments)
    assert combine_via_offload(segments, digests, thr.public_key,
                               local_fn) is None
    assert pool.snapshot()["counters"]["local_fallbacks"] == 1
    with pool._mu:
        pool._inflight = 0


def test_disabled_pool_never_leases(thr):
    pool = get_offload_pool()
    pool.add_helper(InprocHelper("h0", HelperServer("h0")))
    # enabled stays False
    segments, digests = _combine_job(thr, b"q" * 32)
    local_fn, calls = _counted_local(segments)
    assert combine_via_offload(segments, digests, thr.public_key,
                               local_fn) is None
    assert pool.snapshot()["counters"]["lease_issued"] == 0


# ---------------------------------------------------------------------
# verifier-level byte-identity: combine_batch offload on/off
# ---------------------------------------------------------------------

def _thr_jobs(thr, n_jobs=2, bad_job=None):
    jobs = []
    for j in range(n_jobs):
        digest = bytes([0x30 + j]) * 32
        shares = {sid: thr.create_threshold_signer(sid).sign_share(digest)
                  for sid in (1, 2, 3)}
        if bad_job == j:
            s = shares[2]
            shares[2] = s[:5] + bytes([s[5] ^ 0xFF]) + s[6:]
        jobs.append((digest, shares))
    return jobs


@pytest.mark.parametrize("strategy,bad_job", [
    ("honest", None), ("wrong-on-curve", None), ("honest", 1),
])
def test_combine_batch_byte_identical_with_offload(thr, strategy,
                                                   bad_job):
    """The full fused-combine entry point: offload on (honest or lying
    helper; clean or poisoned shares) returns byte-identical
    (ok, cert, bad_shares) tuples to offload off — including bad-share
    identification through the helper-honest/shares-bad path."""
    from tpubft.crypto.tpu import make_threshold_verifier
    v = make_threshold_verifier("threshold-bls", 3, 4, thr.public_key,
                                thr.share_public_keys)
    jobs = _thr_jobs(thr, bad_job=bad_job)
    want = v.combine_batch(jobs)             # pool inactive: local path
    _pool_with(HelperServer("h", strategy=strategy))
    got = v.combine_batch(jobs)
    assert got == want
    if strategy != "honest":
        assert get_offload_pool().snapshot()["quarantined"] == ["h"]


def test_helper_daemon_never_starts_jax():
    """A chip serves one process: the helper daemon, which runs beside
    device-backed replicas, computes on the host engines and must not
    even import JAX (a fresh interpreter, every lease kind served)."""
    import subprocess
    import sys
    code = (
        "import sys\n"
        "from tpubft.offload import helper, protocol as proto\n"
        "from tpubft.crypto import bls12381 as bls, cpu\n"
        "pt = bls.g1_compress(bls.G1_GEN)\n"
        "seg = proto.encode_bls_segments([([1, 2], [pt, pt])])\n"
        "helper.compute(proto.KIND_BLS_COMBINE, seg)\n"
        "helper.compute(proto.KIND_BLS_SUM, seg)\n"
        "s = cpu.EcdsaSigner.generate('secp256k1', seed=b'h')\n"
        "items = [(b'm', s.sign(b'm'), s.public_bytes())]\n"
        "helper.compute(proto.KIND_ECDSA_RLC,\n"
        "               proto.encode_ecdsa_items('secp256k1', items))\n"
        "assert 'jax' not in sys.modules, 'helper imported jax'\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
