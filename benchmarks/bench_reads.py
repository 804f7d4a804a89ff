"""Mixed read/write serving-plane benchmark (the read-scaling story).

Writes order through full consensus; reads are served either

  * ``thin``      — off the consensus path by the thin-replica tier:
    single-server digest-authenticated reads, each one verified against
    the f+1-signed checkpoint anchor (sparse-merkle audit path against
    the anchored root, value bound to the proven hash); or
  * ``consensus`` — the control: the same reads ride ClientRequest
    admission + the read-only quorum path on the replicas.

The A/B pairing discipline (same writers/readers/duration, one knob
flipped) shows whether read traffic scales independently of the write
pipeline: the thin rows must hold write goodput while adding read
throughput the consensus rows can't.

Every thin read in the bench is proof-verified; a row records
``reads_verified`` == ``read_ops``. A corrupted-server drill (a server
that bit-flips served values) runs alongside: the row reports
``corrupt_server_detected`` — a forged read must raise, never serve.

Usage: python -m benchmarks.bench_reads [--secs 10] [--writers 2]
       [--readers 4] [--modes thin,consensus] [--preexec]
Prints one JSON line per (mode,) row.
"""
from __future__ import annotations

import argparse
import json
import statistics
import threading
import time
from typing import List

from tpubft.apps import skvbc
from tpubft.kvbc import KeyValueBlockchain
from tpubft.storage import MemoryDB
from tpubft.testing.cluster import InProcessCluster
from tpubft.thinreplica import ThinReplicaClient, keys_cert_verifier

KEYS = 32                      # hot working set the writers churn
COLD_KEYS = 64                 # read-mostly set seeded once at warmup
HOT_READ_EVERY = 8             # 1-in-8 reads hit the hot (churning) set
ANCHOR_REFRESH_EVERY = 16      # reads between anchor roll-forwards

_OVERRIDES = dict(
    thin_replica_enabled=True,
    # small checkpoint window so the signed anchor rolls forward at
    # bench timescales (the anchor is the read tier's staleness bound)
    checkpoint_window_size=16, work_window_size=32)


def _handler_factory(_r=None):
    return skvbc.SkvbcHandler(
        KeyValueBlockchain(MemoryDB(), use_device_hashing=False),
        merkle=True)


def _pct(vals: List[float], q: float) -> float:
    return round(vals[min(len(vals) - 1, int(len(vals) * q))] * 1e3, 2) \
        if vals else 0.0


def run_mixed(mode: str, secs: float, writers: int, readers: int,
              f: int = 1, preexec: bool = False,
              op_timeout_ms: int = 8000) -> dict:
    """One row: `writers` write threads through consensus + `readers`
    read threads via `mode` ('thin' | 'consensus'), concurrently."""
    assert mode in ("thin", "consensus"), mode
    overrides = dict(_OVERRIDES)
    if preexec:
        overrides["pre_execution_enabled"] = True
    stop_at = [0.0]
    w_counts = [0] * writers
    w_lats: List[List[float]] = [[] for _ in range(writers)]
    r_counts = [0] * max(1, readers)
    r_lats: List[List[float]] = [[] for _ in range(max(1, readers))]
    verified = [0] * max(1, readers)
    stale = [0] * max(1, readers)
    refreshes = [0] * max(1, readers)
    errors: List[str] = []

    with InProcessCluster(f=f, num_clients=writers + 1,
                          handler_factory=_handler_factory,
                          cfg_overrides=overrides) as cluster:
        n = cluster.n
        eps = [("127.0.0.1", cluster.replicas[r].thin_replica.port)
               for r in range(n)]
        verifier = keys_cert_verifier(cluster.keys)
        kv0 = skvbc.SkvbcClient(cluster.client(0))

        # warmup: seed the read-mostly COLD set (batched — few slots)
        # and cross the first checkpoint window so the f+1-signed
        # anchor exists before the clock starts. The cold/hot split is
        # the serving-tier shape: most reads hit keys nobody is
        # actively overwriting; 1-in-HOT_READ_EVERY hits the churning
        # set and exercises the staleness-bound retry path.
        for base in range(0, COLD_KEYS, 8):
            rs = kv0.write_batch(
                [[(b"cold-%02d" % k, b"c%d" % k)]
                 for k in range(base, min(base + 8, COLD_KEYS))],
                timeout_ms=30000)
            assert all(r.success for r in rs), "cold seed failed"
        for i in range(_OVERRIDES["checkpoint_window_size"] + 2):
            assert kv0.write([(b"key-%02d" % (i % KEYS), b"w%d" % i)],
                             pre_process=preexec,
                             timeout_ms=30000).success, "warmup failed"
        probe = ThinReplicaClient(eps, f_val=f, cert_verifier=verifier)
        deadline = time.monotonic() + 20
        anchor = None
        while time.monotonic() < deadline and not anchor:
            anchor = probe.fetch_anchor()
            if not anchor:
                time.sleep(0.25)
        if not anchor:
            # PR 4's degraded-artifact convention: a row that could not
            # exercise the plane says WHY instead of posing as a number
            return {"bench": "reads", "read_mode": mode,
                    "degraded": True,
                    "probe_error": "checkpoint anchor never formed"}

        def writer(idx: int) -> None:
            kv = skvbc.SkvbcClient(cluster.client(idx))
            i = 0
            while time.monotonic() < stop_at[0]:
                t0 = time.monotonic()
                try:
                    r = kv.write([(b"key-%02d" % (i % KEYS),
                                   b"v-%d-%d" % (idx, i))],
                                 pre_process=preexec,
                                 timeout_ms=op_timeout_ms)
                except Exception:  # noqa: BLE001 — timeout under load
                    i += 1
                    continue
                if r.success:
                    w_counts[idx] += 1
                    w_lats[idx].append(time.monotonic() - t0)
                i += 1

        def thin_reader(idx: int) -> None:
            trc = ThinReplicaClient(eps[idx % n:] + eps[:idx % n],
                                    f_val=f, cert_verifier=verifier)
            try:
                trc.fetch_anchor()
            except ValueError as e:
                errors.append(f"anchor: {e}")
                return
            i = 0
            while time.monotonic() < stop_at[0]:
                key = (b"key-%02d" % (i % KEYS)
                       if i % HOT_READ_EVERY == 0
                       else b"cold-%02d" % (i % COLD_KEYS))
                t0 = time.monotonic()
                try:
                    if i % ANCHOR_REFRESH_EVERY == 0:
                        trc.fetch_anchor()
                        refreshes[idx] += 1
                    trc.verified_read("kv", key)
                    verified[idx] += 1
                    r_counts[idx] += 1
                    r_lats[idx].append(time.monotonic() - t0)
                except LookupError:
                    # key overwritten since the anchored block: roll the
                    # anchor forward and retry on the next loop — the
                    # read tier's staleness bound at work
                    stale[idx] += 1
                    try:
                        trc.fetch_anchor()
                        refreshes[idx] += 1
                    except ValueError as e:
                        errors.append(f"refresh: {e}")
                        return
                except ValueError as e:
                    errors.append(f"verify: {e}")
                    return
                except OSError:
                    pass             # server churn; retry next loop
                i += 1

        def consensus_reader(idx: int) -> None:
            kv = skvbc.SkvbcClient(cluster.client(writers))
            i = 0
            while time.monotonic() < stop_at[0]:
                key = (b"key-%02d" % (i % KEYS)
                       if i % HOT_READ_EVERY == 0
                       else b"cold-%02d" % (i % COLD_KEYS))
                t0 = time.monotonic()
                try:
                    kv.read([key], timeout_ms=op_timeout_ms)
                except Exception:  # noqa: BLE001 — timeout under load
                    i += 1
                    continue
                r_counts[idx] += 1
                r_lats[idx].append(time.monotonic() - t0)
                i += 1

        # clients pre-created on THIS thread: cluster.client() mutates
        # shared dicts and must not race the worker threads
        for i in range(writers + 1):
            cluster.client(i).start()
        reader = thin_reader if mode == "thin" else consensus_reader
        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(writers)]
        threads += [threading.Thread(target=reader, args=(i,))
                    for i in range(readers)]
        stop_at[0] = time.monotonic() + secs
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
        trs_proofs = sum(
            cluster.aggregators[r].get("thinreplica", "counters",
                                       "trs_proofs") or 0
            for r in range(n))
        trs_runs = sum(
            cluster.aggregators[r].get("thinreplica", "counters",
                                       "trs_pushed_runs") or 0
            for r in range(n))

    w_all = sorted(x for ls in w_lats for x in ls)
    r_all = sorted(x for ls in r_lats for x in ls)
    row = {
        "bench": "reads", "read_mode": mode, "n": 3 * f + 1, "f": f,
        "writers": writers, "readers": readers,
        "preexec": preexec, "secs": round(wall, 2),
        "write_ops": sum(w_counts),
        "write_ops_per_sec": round(sum(w_counts) / wall, 1),
        "read_ops": sum(r_counts),
        "read_ops_per_sec": round(sum(r_counts) / wall, 1),
        "write_p50_ms": _pct(w_all, 0.5), "write_p90_ms": _pct(w_all, 0.9),
        "read_p50_ms": _pct(r_all, 0.5), "read_p90_ms": _pct(r_all, 0.9),
        "read_mean_ms": round(statistics.mean(r_all) * 1e3, 2)
        if r_all else None,
    }
    if mode == "thin":
        row.update({
            "reads_verified": sum(verified),
            "stale_retries": sum(stale),
            "anchor_refreshes": sum(refreshes),
            "trs_proofs_served": trs_proofs,
            "trs_pushed_runs": trs_runs,
        })
        if errors:
            row["degraded"] = True
            row["probe_error"] = "; ".join(errors[:3])
    return row


# ----------------------------------------------------------------------
# corrupted-server drill: a forged value must be DETECTED, not served
# ----------------------------------------------------------------------

def corrupt_server_drill() -> dict:
    """Standalone (no cluster): an honest and a corrupting thin-replica
    server over identical chains, a hand-signed f+1 cert anchor. The
    corrupting server bit-flips every served value; the client's hash
    binding must reject it while the honest server's reads verify."""
    from tpubft.consensus import messages as cm
    from tpubft.crypto.cpu import Ed25519Signer, Ed25519Verifier
    from tpubft.kvbc import BLOCK_MERKLE, BlockUpdates
    from tpubft.thinreplica import messages as tm
    from tpubft.thinreplica.server import ThinReplicaServer

    def chain():
        bc = KeyValueBlockchain(MemoryDB(), use_device_hashing=False)
        for i in range(4):
            bc.add_block(BlockUpdates().put(
                "kv", b"k%d" % i, b"v%d" % i, cat_type=BLOCK_MERKLE))
        return bc

    honest_bc, corrupt_bc = chain(), chain()
    signers = {i: Ed25519Signer.generate(seed=bytes([i]) * 32)
               for i in (0, 1)}
    head = honest_bc.last_block_id
    digest = honest_bc.block_digest(head)
    certs = []
    for i, s in signers.items():
        ck = cm.CheckpointMsg(sender_id=i, seq_num=16,
                              state_digest=digest, is_stable=False,
                              res_pages_digest=b"", signature=b"")
        ck.signature = s.sign(ck.signed_payload())
        certs.append(ck.pack())
    anchor = (16, head, tuple(certs))

    class _CorruptingServer(ThinReplicaServer):
        def _serve_proof(self, conn, req):
            class _Tap:
                def __init__(self, inner):
                    self.inner = inner

                def sendall(self, data):
                    msg = tm.unpack_body(data[4:])
                    if isinstance(msg, tm.ProofReply) and msg.value:
                        msg.value = bytes([msg.value[0] ^ 1]) \
                            + msg.value[1:]
                    self.inner.sendall(tm.pack(msg))
            super()._serve_proof(_Tap(conn), req)

    honest = ThinReplicaServer(honest_bc, anchor_fn=lambda: anchor)
    corrupt = _CorruptingServer(corrupt_bc, anchor_fn=lambda: anchor)
    honest.start()
    corrupt.start()
    verifiers = {i: Ed25519Verifier(s.public_bytes())
                 for i, s in signers.items()}
    try:
        def cert_verifier(rid, payload, sig):
            v = verifiers.get(rid)
            return v is not None and v.verify(payload, sig)

        ok = ThinReplicaClient(
            [("127.0.0.1", honest.port), ("127.0.0.1", corrupt.port)],
            f_val=1, cert_verifier=cert_verifier)
        assert ok.fetch_anchor() == head
        assert ok.verified_read("kv", b"k0") == b"v0"
        bad = ThinReplicaClient(
            [("127.0.0.1", corrupt.port), ("127.0.0.1", honest.port)],
            f_val=1, cert_verifier=cert_verifier)
        assert bad.fetch_anchor() == head
        detected = False
        try:
            bad.verified_read("kv", b"k0")
        except ValueError:
            detected = True
        return {"corrupt_server_detected": detected,
                "honest_read_ok": True}
    finally:
        honest.stop()
        corrupt.stop()


def smoke(secs: float = 2.0) -> dict:
    """Tier-1 shape: a thin row and a consensus control row (1 writer +
    1 reader each), writes through the PRE-EXECUTION plane on the thin
    row (the serving plane's both halves under THREADCHECK), plus the
    corrupted-server drill. Every thin read must have verified."""
    from tpubft.utils.racecheck import get_watchdog
    out = {}
    for mode, preexec in (("thin", True), ("consensus", False)):
        row = run_mixed(mode, secs, writers=1, readers=1,
                        preexec=preexec)
        entry = {"ok": not row.get("degraded")
                 and row.get("read_ops", 0) > 0
                 and row.get("write_ops", 0) > 0,
                 "read_ops": row.get("read_ops", 0),
                 "write_ops": row.get("write_ops", 0)}
        if row.get("degraded"):
            entry["probe_error"] = row.get("probe_error", "")
        if mode == "thin":
            entry["all_verified"] = (row.get("reads_verified", -1)
                                     == row.get("read_ops", 0))
        out[mode] = entry
    out.update(corrupt_server_drill())
    out["stall_reports"] = get_watchdog().stall_reports
    return out


def main(argv=None) -> None:
    from tpubft.utils.jaxcache import setup_cache
    setup_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--secs", type=float, default=10.0)
    ap.add_argument("--writers", type=int, default=2)
    ap.add_argument("--readers", type=int, default=4)
    ap.add_argument("--modes", default="thin,consensus")
    ap.add_argument("--preexec", action="store_true",
                    help="route the writes through the pre-execution "
                         "plane (PRE_PROCESS flag)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fixed shape for CI")
    args = ap.parse_args(argv)
    if args.smoke:
        print(json.dumps(smoke()), flush=True)
        return
    for mode in args.modes.split(","):
        row = run_mixed(mode, args.secs, args.writers, args.readers,
                        preexec=args.preexec)
        print(json.dumps(row), flush=True)
    print(json.dumps(corrupt_server_drill()), flush=True)


if __name__ == "__main__":
    main()
