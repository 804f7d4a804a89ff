"""Driver `served_bls`: the `served` driver on a cluster whose
Prepare/Commit certificates are threshold-BLS signatures
(`skvbc_n7_bls`), with the certificates held to the plain reference.

The cluster, the clients, the window and every comparison of `served`
are that driver's, unchanged: it builds its cluster from the
configuration's `cluster` and `replica_config`. This one adds, after the
run has drained: the certificates the replicas still hold — in their
windows (`SeqNumInfo`) and as persisted (`consensus/persistent.py`, read
back from each replica's own store) — of slots drawn from the seed,
against `cellbench/reference/certs.py` byte for byte and under the
program's pairing check; and which commit path each of the window's
slots took, from the flight recorder's rows.
"""
from __future__ import annotations

import random

from cellbench.drivers.served import Driver as Served
from cellbench.harness import say
from cellbench.reference.certs import ThresholdSystem

# (field of SeqNumInfo and of PersistedSeqState, the certificate's kind)
CERT_FIELDS = (("prepare_full", "prepare"), ("commit_full", "commit"),
               ("full_commit_proof", "fast"))
PATH_COUNTERS = ("fast_path_commits", "slow_path_commits",
                 "slow_path_starts")
# the certificate path's flight spans, read when the window closes
CERT_SPANS = ("share_sign", "bls_share_decompress", "bls_combine",
              "bls_pairing_verify")


class _AtClose:
    """The run's tracer with one thing more to do when `served.measure`
    stops it, which is when the window closes."""

    def __init__(self, tracer, hook) -> None:
        self._tracer, self._hook = tracer, hook

    def __getattr__(self, name):
        return getattr(self._tracer, name)

    def stop(self) -> None:
        self._hook()
        self._tracer.stop()


def _quiet(read, tries: int = 20):
    """`read()` of a replica's window from outside its dispatcher: the
    cluster has drained, but a timer may still touch the dictionary."""
    for _ in range(tries - 1):
        try:
            return read()
        except RuntimeError:
            continue
    return read()


def reference_systems(keys) -> dict:
    """{(kind, first path): (reference system, program's verifier, the
    digest's domain tag)} of the cluster's three threshold systems: the
    slow path's signs Prepare and Commit whatever path the slot started
    on."""
    from tpubft.consensus import messages as m

    def pair(system):
        return (ThresholdSystem(system.threshold_, system.secret_shares),
                system.create_threshold_verifier())
    slow = pair(keys.slow_path_system)
    out = {}
    for path in m.CommitPath:
        out["prepare", int(path)] = slow + ("prepare",)
        out["commit", int(path)] = slow + ("commit",)
    out["fast", int(m.CommitPath.OPTIMISTIC_FAST)] = \
        pair(keys.optimistic_system) + ("fast0",)
    out["fast", int(m.CommitPath.FAST_WITH_THRESHOLD)] = \
        pair(keys.commit_path_system) + ("fast1",)
    return out


def held_certificates(cluster, dbs) -> dict:
    """{seq: [(replica, where, kind, the slot's PrePrepare or None,
    certificate message)]} of everything the replicas hold now: in
    their windows, and in their stores (`dbs[r]`), read back."""
    from tpubft.consensus import messages as m
    from tpubft.storage.metadata import DBPersistentStorage
    held = {}
    for r, rep in cluster.replicas.items():
        for seq, info in _quiet(lambda rep=rep: list(rep.window.items())):
            for field, kind in CERT_FIELDS:
                msg = getattr(info, field)
                if msg is not None:
                    held.setdefault(seq, []).append(
                        (r, "window", kind, info.pre_prepare, msg))
        stored = DBPersistentStorage(dbs[r]).load().seq_states
        for seq, st in stored.items():
            pp = m.unpack(st.pre_prepare) if st.pre_prepare else None
            for field, kind in CERT_FIELDS:
                raw = getattr(st, field)
                if raw is not None:
                    held.setdefault(seq, []).append(
                        (r, "persisted", kind, pp, m.unpack(raw)))
    return held


def compare_certificates(cluster, held: dict, seqs) -> dict:
    """The certificates of `seqs`, from every replica that holds them,
    against the reference's bytes and under the program's pairing
    check. A certificate also has to be over the digest its own
    PrePrepare binds it to."""
    from tpubft.consensus.replica import share_digest
    systems = reference_systems(cluster.keys)
    out = dict(compared=0, mismatches=0, unverified=0, by_kind={},
               unsound_systems=sum(not ref.consistent()
                                   for ref, _v, _t in systems.values()))
    judged = {}          # (tag, digest, sig) -> (matches, verifies)
    for seq in seqs:
        for r, _where, kind, pp, cert in held[seq]:
            out["compared"] += 1
            out["by_kind"][kind] = out["by_kind"].get(kind, 0) + 1
            if pp is None:              # a certificate and no PrePrepare
                out["mismatches"] += 1
                out["unverified"] += 1
                continue
            ref, verifier, tag = systems[kind, int(pp.first_path)]
            key = (tag, cert.digest, cert.sig)
            if key not in judged:
                judged[key] = (
                    cert.sig == ref.certificate(cert.digest),
                    bool(verifier.verify(cert.digest, cert.sig)))
            matches, verifies = judged[key]
            bound = share_digest(tag, cluster.replicas[r].epoch, cert.view,
                                 cert.seq_num, pp.digest())
            out["mismatches"] += (not matches or cert.digest != bound
                                  or cert.seq_num != seq)
            out["unverified"] += not verifies
    out["distinct"] = len(judged)
    return out


class Driver(Served):
    cert_spans = None

    def measure(self, seconds: float, tracer) -> None:
        super().measure(seconds, _AtClose(tracer, self._read_cert_spans))

    def _read_cert_spans(self) -> None:
        """The certificate path's ring spans since the window opened,
        read as it closes: a dispatcher's ring takes an event a message
        and holds the last seconds only, so what it holds of the window
        is gone once the drain and the check have passed through it.
        {name: (spans, from where on every ring that holds them is
        whole)}; nothing on a program without such a reader."""
        from tpubft.utils import flight
        if hasattr(flight, "span_events_tail"):
            since = int(self.t_open * 1e9)
            self.cert_spans = {name: flight.span_events_tail(name, since)
                               for name in CERT_SPANS}

    def layer_context(self) -> dict:
        return dict(super().layer_context(), cert_spans=self.cert_spans,
                    t_close=self.t_close)

    def finish(self) -> None:
        super().finish()
        cl = self.cluster
        rows = [s.get("path") for s in self.slots]
        say(phase="paths", slot_rows=len(rows),
            fast=rows.count("fast"), slow=rows.count("slow"),
            cert_spans={name: dict(
                spans=len(spans),
                from_s=round(from_ns / 1e9 - self.t_open, 3))
                for name, (spans, from_ns)
                in (self.cert_spans or {}).items()},
            replicas={name: [cl.metric(r, "counters", name)
                             for r in range(cl.n)]
                      for name in PATH_COUNTERS},
            # where the autotuner has the device's floor, and what the
            # seam was given in the window
            device_min_batch=[cl.replicas[r].sig.device_min_batch
                              for r in range(cl.n)],
            ed25519_calls=(self.after["kernels"].get("ed25519", (0, 0))[0]
                           - self.before["kernels"].get("ed25519",
                                                        (0, 0))[0]))

    def check(self, cmp) -> None:
        super().check(cmp)
        held = held_certificates(self.cluster, self.dbs)
        rng = random.Random(f"{self.seed}/certificates")
        drawn = sorted(rng.sample(sorted(held), min(
            self.params.get("check_slots", 24), len(held))))
        got = compare_certificates(self.cluster, held, drawn)
        cmp.add("certificate_mismatches",
                got["mismatches"] + got["unsound_systems"], 0)
        cmp.add("certificates_unverified", got["unverified"], 0)
        cmp.add("certificate_slots_missing", int(not drawn), 0)
        cmp.add("slots_on_no_path",
                sum(s.get("path") not in ("fast", "slow")
                    for s in self.slots), 0)
        say(phase="check_certificates", slots_held=len(held),
            slots_compared=len(drawn),
            certificates_compared=got["compared"],
            distinct=got["distinct"], by_kind=got["by_kind"],
            first_slot=drawn[0] if drawn else None,
            last_slot=drawn[-1] if drawn else None)
