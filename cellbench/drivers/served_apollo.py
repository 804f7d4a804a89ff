"""Driver `served_apollo`: the `served_bls` driver on a cluster whose
clients sign with ECDSA over secp256k1 (`apollo_n31`), with the client
signatures held to the plain reference.

The cluster, the clients, the window and every comparison of `served`
and `served_bls` are those drivers', unchanged. This one adds:

before anything is built, a check that the program can serve the
deployment — the `ReplicaConfig` and `ClientConfig` fields the
configuration's file names, the named ECDSA program, the call rows'
fields — and a refusal in one line if it cannot;

the ECDSA programs the workload's file names, run once at set-up (so
the first launch under traffic neither traces nor loads);

after the drain: every acknowledged message's client signatures, as
the client signed them, under `cellbench/reference/ecdsa.py`
(`requests_reference_rejects`); a seeded sample of batches of the
window's own requests, signed anew by their clients (so that no memo
answers for them) and a seeded few spoiled, through replica 0's own
`SigManager` at the floor the run left it with, against the reference
element by element (`verdict_mismatches`); and that the window drove
the ECDSA kernel at all (`ecdsa_device_calls_missing`).

A traced run traces the window's last `trace_window_s`, as the flood
does: the ECDSA launches that fall in it are in the trace whole.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import random
import statistics
import threading
import time
from concurrent.futures import ProcessPoolExecutor
from unittest import mock

from cellbench import harness
from cellbench.drivers.served_bls import Driver as ServedBls
from cellbench.harness import say
from cellbench.reference import ecdsa as ref

CURVE = "secp256k1"
SIG_ECDSA_COUNTERS = ("ecdsa_device_items", "ecdsa_host_items")
THRESHOLD_COUNTERS = ("bls_shares_batch_decoded", "bls_decode_batches")
CLIENT_COUNTERS = ("client_sends", "client_retransmissions",
                   "client_broadcasts")
# batches of the verdict sample, and how many of them carry one spoiled
# item each (in turn: forged, truncated, duplicated)
SAMPLE_BATCHES, SAMPLE_SPOILED = 8, 3
SAMPLE_MIN = 64     # the loader's batch: at or over every device floor


# ---------------------------------------------------------------------
# can the program serve this deployment?
# ---------------------------------------------------------------------

def missing_capabilities(cfg: dict, ecdsa_lanes=()) -> list:
    """What the configuration's file names and the program lacks, one
    string each; empty when it can serve the deployment. `ecdsa_lanes`
    are the lane counts the workload's file says set-up warms: the
    program has to form those and no others."""
    missing = []
    from tpubft.bftclient.client import ClientConfig
    from tpubft.utils.config import ReplicaConfig
    fields = {f.name for f in dataclasses.fields(ReplicaConfig)}
    missing += [f"ReplicaConfig.{k}" for k in cfg["replica_config"]
                if k not in fields]
    defaults = {f.name: f.default for f in dataclasses.fields(ClientConfig)}
    for k, v in cfg.get("client_config", {}).items():
        if k not in defaults:
            missing.append(f"ClientConfig.{k}")
        elif defaults[k] != v:
            # the served set-up builds its clients with the defaults
            missing.append(f"ClientConfig.{k} = {v!r} as the default "
                           f"(it is {defaults[k]!r})")
    kernel = harness.load_json("kernels", "ecdsa.json")["pattern"]
    from tpubft.ops import ecdsa
    jitted = getattr(ecdsa, "rlc_kernel", lambda _c: None)(CURVE)
    if getattr(jitted, "__name__", None) != kernel:
        missing.append(f"tpubft.ops.ecdsa: a jitted program named "
                       f"{kernel!r}")
    if ecdsa_lanes and [getattr(ecdsa, "DEVICE_LANES", None)] \
            != list(ecdsa_lanes):
        missing.append(f"tpubft.ops.ecdsa.DEVICE_LANES in {list(ecdsa_lanes)}"
                       " (one lane count for every launch)")
    from tpubft.utils import flight
    row = flight.KernelProfiler().record("ecdsa", 1, 1000, "closed") or {}
    missing += [f"ecdsa call rows' {k}" for k in ("prep_us", "device_us")
                if k not in row]
    return missing


# ---------------------------------------------------------------------
# what the clients signed
# ---------------------------------------------------------------------

class _RecordingSigner:
    """A client's signer, keeping (payload, signature) of everything it
    signs, in order: a request's signature as the client sent it."""

    def __init__(self, signer, log: list) -> None:
        self._signer, self._log = signer, log

    def __getattr__(self, name):
        return getattr(self._signer, name)

    def sign(self, data: bytes) -> bytes:
        sig = self._signer.sign(data)
        self._log.append((bytes(data), bytes(sig)))
        return sig


@contextlib.contextmanager
def recording_clients(signed: dict):
    """Every client the cluster makes signs through a recorder;
    `signed[client id]` is its list. (The served set-up builds cluster
    and clients in one go, so the seam is the cluster's own factory.)"""
    from tpubft.testing import InProcessCluster
    make = InProcessCluster.client

    def client(cluster, idx: int = 0, **kw):
        cl = make(cluster, idx, **kw)
        if not isinstance(cl._signer, _RecordingSigner):
            cl._signer = _RecordingSigner(
                cl._signer, signed.setdefault(cl.cfg.client_id, []))
        return cl

    with mock.patch.object(InProcessCluster, "client", client):
        yield


def reference_verdicts(items) -> list:
    """`ref.verify` of (public key, message, signature) triples; many
    of them on worker processes, which import the reference alone."""
    items = list(items)
    workers = min(8, os.cpu_count() or 1, len(items) // 64)
    if workers < 2:
        return [ref.verify(*it) for it in items]
    import multiprocessing
    from concurrent.futures.process import BrokenProcessPool
    chunks = [items[i::workers] for i in range(workers)]
    try:
        # spawned, not forked: the parent holds the chip and its threads
        with ProcessPoolExecutor(workers, mp_context=multiprocessing
                                 .get_context("spawn")) as pool:
            parts = list(pool.map(ref.verify_many, chunks))
    except (BrokenProcessPool, OSError):
        return [ref.verify(*it) for it in items]
    out = [False] * len(items)
    for i, part in enumerate(parts):
        out[i::workers] = part
    return out


# ---------------------------------------------------------------------

class Driver(ServedBls):
    def __init__(self, cell, seed: int, log) -> None:
        super().__init__(cell, seed, log)
        missing = missing_capabilities(self.cfg,
                                       self.params["ecdsa_lanes"])
        if missing:
            raise SystemExit(
                f"{cell.name}: this program cannot serve "
                f"{self.cfg['name']}; it lacks " + "; ".join(missing))
        self.signed = {}             # client id -> [(payload, signature)]

    # -----------------------------------------------------------------
    def setup(self) -> None:
        warmer = threading.Thread(target=self._warm_ecdsa,
                                  name="warm-ecdsa")
        warmer.start()               # traces and compiles beside ed25519
        with recording_clients(self.signed):
            super().setup()
        warmer.join()

    def _warm_ecdsa(self) -> None:
        """One launch at each lane count the workload's file names,
        through the program's own jitted kernel, so that no launch
        under traffic traces, compiles or loads."""
        from tpubft.crypto.cpu import make_signer
        from tpubft.ops import ecdsa
        signer = make_signer("ecdsa-" + CURVE, seed=b"cellbench-warm")
        for lanes in self.params["ecdsa_lanes"]:
            t0 = time.monotonic()
            items = [(b"warm %d" % i, signer.sign(b"warm %d" % i),
                      signer.public_bytes()) for i in range(lanes)]
            ok = bool(ecdsa.rlc_verify_batch(CURVE, items).all())
            say(phase="warm", kernel=f"ecdsa@{lanes}", verified=ok,
                wall_s=round(time.monotonic() - t0, 2))

    # -----------------------------------------------------------------
    def _apollo_counters(self) -> dict:
        from tpubft.crypto import systems
        cl = self.cluster
        out = {name: sum(cl.metric(r, "counters", name,
                                   component="signature_manager")
                         for r in range(cl.n))
               for name in SIG_ECDSA_COUNTERS}
        out.update({name: systems.METRICS.counters[name].value
                    for name in THRESHOLD_COUNTERS
                    if name in systems.METRICS.counters})
        for name in CLIENT_COUNTERS:
            out[name] = sum(c.metrics.counters[name].value
                            for c in cl.clients.values())
        return out

    def measure(self, seconds: float, tracer) -> None:
        self.apollo_before = self._apollo_counters()
        super().measure(seconds, tracer)

    def _read_cert_spans(self) -> None:
        super()._read_cert_spans()   # the window closes
        self.apollo_after = self._apollo_counters()

    def _ecdsa_rows(self) -> list:
        """The window's `ecdsa` call rows, cut by the calls the driver
        snapshots (no clock)."""
        from tpubft.utils import flight
        first = self.before["kernels"].get("ecdsa", (0, 0))[0]
        last = self.after["kernels"].get("ecdsa", (0, 0))[0]
        return [r for r in flight.kernel_profiler().call_rows("ecdsa")
                if first < r["ordinal"] <= last]

    def layer_context(self) -> dict:
        return dict(super().layer_context(), ecdsa_rows=self.ecdsa_rows,
                    apollo_before=self.apollo_before,
                    apollo_after=self.apollo_after)

    def finish(self) -> None:
        self.ecdsa_rows = self._ecdsa_rows()
        super().finish()
        rows = self.ecdsa_rows
        from tpubft.crypto import tpu
        from tpubft.ops import ecdsa
        say(phase="apollo", ecdsa_launches=len(rows),
            ecdsa_batches=sorted({r["batch"] for r in rows}),
            ecdsa_device_s=round(sum(r["device_us"] for r in rows) / 1e6, 3),
            gate_wait_ms_p50=(round(statistics.median(
                r["gate_wait_us"] for r in rows) / 1e3, 1) if rows else None),
            lanes=ecdsa.DEVICE_LANES, crossover=tpu.ecdsa_crossover(),
            window={k: self.apollo_after[k] - self.apollo_before[k]
                    for k in self.apollo_after})

    # -----------------------------------------------------------------
    def _acknowledged(self) -> list:
        """(principal, payload, signature) of every write of every
        acknowledged message, as its client signed it. A client signs
        its messages in order, one signature a write."""
        first = self.cluster.first_client_id
        out = []
        for r in self.records:
            if not r["ok"]:
                continue
            cid, per = first + r["client"], len(r["writes"])
            mine = self.signed.get(cid, [])[r["message"] * per:
                                            (r["message"] + 1) * per]
            out += [(cid,) + s for s in mine]
            out += [(cid, b"", b"")] * (per - len(mine))   # never signed?
        return out

    def check(self, cmp) -> None:
        super().check(cmp)
        t0 = time.monotonic()
        pubkey = self.cluster.keys.client_pubkeys
        acked = self._acknowledged()
        verdicts = reference_verdicts(
            (pubkey[c], data, sig) for c, data, sig in acked)
        cmp.add("requests_reference_rejects", verdicts.count(False), 0)
        t1 = time.monotonic()
        sample = self._verdict_sample(acked)
        cmp.add("verdict_mismatches", sample["mismatches"], 0)
        cmp.add("ecdsa_device_calls_missing",
                int(not self.ecdsa_rows), 0)
        say(phase="check_client_signatures", requests_checked=len(acked),
            reference_s=round(t1 - t0, 2), **sample,
            sample_s=round(time.monotonic() - t1, 2))

    def _verdict_sample(self, acked: list) -> dict:
        """Seeded batches of the window's own requests through the
        timed object: replica 0's own SigManager, at the device floor
        the run left it with, in batches no smaller than the loader's
        (so each rides the device tier as admission's did). Each
        request is signed anew by its client under a replay tag: the
        manager's memo holds every signature the window verified and
        would answer for them. A few are spoiled as
        `generate.Flood.slot` spoils. The program's verdicts against
        the reference's, element by element."""
        rng = random.Random(f"{self.seed}/verdicts")
        sizes = sorted({r["batch"] for r in self.ecdsa_rows}) or [SAMPLE_MIN]
        manager = self.cluster.replicas[0].sig
        signers = {cid: cl._signer
                   for cid, cl in self.cluster.clients.items()}
        launches0 = harness.kernel_profile().get("ecdsa", (0, 0))[0]
        pubkey = self.cluster.keys.client_pubkeys
        kinds = ["forged", "truncated", "duplicate"]
        out = dict(batches=0, items=0, spoiled=0, mismatches=0)
        if len(acked) < 2:
            return dict(out, mismatches=1)      # nothing to replay
        batches = []
        for b in range(SAMPLE_BATCHES):
            size = max(SAMPLE_MIN, rng.choice(sizes))
            items = []
            for c, data, _ in rng.choices(acked, k=size):
                data += b"/replay %d.%d" % (b, len(items))
                items.append((c, data, signers[c]._signer.sign(data)))
            if b < SAMPLE_SPOILED:
                i = rng.randrange(1, size)
                c, data, sig = items[i]
                kind = kinds[b % len(kinds)]
                items[i] = ((c, data + b"!", sig) if kind == "forged"
                            else (c, data, sig[:40]) if kind == "truncated"
                            else items[i - 1])
                out["spoiled"] += 1
            batches.append(items)
        want = reference_verdicts((pubkey[c], data, sig)
                                  for items in batches
                                  for c, data, sig in items)
        for items in batches:
            got = manager.verify_batch(items)
            out["batches"] += 1
            out["items"] += len(items)
            out["mismatches"] += sum(bool(g) != w for g, w in
                                     zip(got, want[:len(items)]))
            del want[:len(items)]
        out["launches"] = (harness.kernel_profile().get("ecdsa", (0, 0))[0]
                           - launches0)
        # a sample the device never saw compared nothing of its tier
        out["mismatches"] += int(out["launches"] < out["batches"])
        return out
