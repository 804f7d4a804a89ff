"""Driver `flood`: one replica's crypto plane under a signature flood.

A slot is the mix's messages through one `SigManager.verify_batch` call
(the cross-principal device batch, `crypto/tpu.verify_batch_mixed`),
then its shares through `new_accumulator()` -> add -> combine ->
`verify` of the certificate on the tpu backend's threshold verifier.
The construction is benchmarks/bench_flood.py's (SigManager and the
accumulator classes); its arithmetic is not: nothing is best-of,
`device_min_batch` and the memo stay at their defaults, and every slot
of the window counts. Inputs are signed ahead on a producer thread,
never inside a timed slot.
"""
from __future__ import annotations

import queue
import random
import statistics
import threading
import time

from cellbench import generate
from cellbench.harness import (breaker_events, breaker_snapshot,
                               kernel_profile, say, single_device_programs,
                               warm)


class Driver:
    def __init__(self, cell, seed: int, log) -> None:
        self.cell, self.seed, self.log = cell, seed, log
        self.cfg, self.params, self.mix = (cell.config, cell.workload,
                                           cell.traffic)
        self.slots = []              # one row per finished slot
        self._stop = threading.Event()
        self._producer = None
        self._q = queue.Queue(maxsize=self.params["slots_ahead"])
        self.input_waits = 0

    # -----------------------------------------------------------------
    def setup(self) -> None:
        from tpubft.consensus.keys import ClusterKeys
        from tpubft.consensus.sig_manager import SigManager
        from tpubft.crypto import bls12381 as bls
        from tpubft.crypto.tpu import (make_threshold_verifier,
                                       verify_batch_mixed)
        from tpubft.utils.config import ReplicaConfig

        self.flood = generate.Flood(self.mix, self.seed)
        self._producer = threading.Thread(target=self._produce,
                                          name="sign-ahead")
        self._producer.start()          # signs while the kernels lower
        warm(single_device_programs(**self.params["programs"]), self.log)
        cluster = self.cfg["cluster"]
        defaults = ReplicaConfig()
        keys = ClusterKeys(n=cluster["n"], f=cluster["f"], c=cluster["c"],
                           threshold_scheme=self.cfg["threshold_scheme"],
                           replica_pubkeys=self.flood.public_keys())
        self.sig_manager = SigManager(
            keys, batch_fn=verify_batch_mixed,
            device_min_batch=defaults.device_min_verify_batch)
        secret = self.flood.poly.secret
        self.verifier = make_threshold_verifier(
            self.cfg["threshold_scheme"], self.flood.threshold,
            self.flood.total, bls.g2_mul(bls.G2_GEN, secret),
            [bls.g2_mul(bls.G2_GEN, s) for s in self.flood.secret_shares()])
        self._breaker0 = breaker_snapshot()
        for _ in range(self.params["warmup_slots"]):
            self._slot(record=False)

    def _produce(self) -> None:
        j = 0
        while not self._stop.is_set():
            item = (j,) + self.flood.slot(j)
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    pass
            j += 1

    def _slot(self, record: bool = True) -> float:
        """One slot through the program; returns when it ended."""
        from cellbench.run import span
        t_in = time.monotonic()
        with span("wait_for_signed_input"):
            j, items, d, offered = self._q.get()
        t0 = time.monotonic()
        if t0 - t_in > 0.001:
            self.input_waits += 1
        with span("verify_batch"):
            verdicts = self.sig_manager.verify_batch(items)
        t1 = time.monotonic()
        with span("accumulate_combine_verify"):
            digest = self.flood.digests[d]
            acc = self.verifier.new_accumulator(False)
            acc.set_expected_digest(digest)
            for share_id, share in offered:
                acc.add(share_id, share)
            cert = acc.get_full_signed_data()
            ok = self.verifier.verify(digest, cert)
        t2 = time.monotonic()
        if record:
            self.slots.append(dict(
                slot=j, items=items, d=d, verdicts=list(verdicts),
                cert=cert, ok=bool(ok), done=t2,
                verify_ms=(t1 - t0) * 1e3, combine_ms=(t2 - t1) * 1e3))
        return t2

    # -----------------------------------------------------------------
    def _counters(self) -> dict:
        return {"kernels": kernel_profile(), "slots": len(self.slots)}

    def measure(self, seconds: float, tracer) -> None:
        """Slots are started for `seconds`; the window closes when the
        last of them ends, so that it holds whole slots only and the
        rate is not counted in steps of one (a slot is 1/74 of 48 s)."""
        self.before = self.traced_from = self._counters()
        self.t_open = self.t_close = time.monotonic()
        last_start = self.t_open + seconds
        while time.monotonic() < last_start:
            if tracer.due(last_start):
                self.traced_from = self._counters()
                tracer.start()
            self.t_close = self._slot()
        self.after = self._counters()
        tracer.stop()

    def finish(self) -> None:
        self._stop.set()
        self._producer.join(30)
        self.attempted = len(self.slots) * self.sigs_per_slot
        self.failed = sum(not s["ok"] for s in self.slots) \
            * self.sigs_per_slot
        say(phase="window", seconds=round(self.t_close - self.t_open, 3),
            slots=len(self.slots), slots_failed=self.failed
            // self.sigs_per_slot, input_waits=self.input_waits,
            **({"verify_ms_p50": round(statistics.median(
                s["verify_ms"] for s in self.slots), 2),
                "combine_ms_p50": round(statistics.median(
                    s["combine_ms"] for s in self.slots), 2)}
               if self.slots else {}))

    @property
    def sigs_per_slot(self) -> int:
        return self.mix["messages_per_slot"] + self.mix["shares_per_slot"]

    def end_to_end(self) -> dict:
        return {"flood_sigs_per_s":
                (self.attempted - self.failed)
                / (self.t_close - self.t_open)}

    def layer_context(self) -> dict:
        return dict(before=self.before, after=self.after,
                    traced_from=self.traced_from, slots=self.slots,
                    points_per_combine=self.mix["shares_per_slot"],
                    window_s=self.t_close - self.t_open)

    # -----------------------------------------------------------------
    def check(self, cmp) -> None:
        """Certificates of every slot of the window, and the verdicts
        of a seeded sample of slots with the last one in it, against the
        plain references."""
        want_cert = {}
        cert_wrong = unverified = 0
        for s in self.slots:
            if s["d"] not in want_cert:
                want_cert[s["d"]] = self.flood.reference_certificate(s["d"])
            cert_wrong += s["cert"] != want_cert[s["d"]]
            unverified += not s["ok"]
        rng = random.Random(f"{self.seed}/check")
        k = min(self.params["check_slots"], len(self.slots))
        sample = rng.sample(self.slots[:-1], k - 1) + self.slots[-1:] \
            if k else []
        mismatches = compared = 0
        for s in sample:
            want = self.flood.reference_verdicts(s["items"])
            got = [bool(v) for v in s["verdicts"]]
            mismatches += (abs(len(got) - len(want))
                           + sum(g != w for g, w in zip(got, want)))
            compared += len(want)
        cmp.add("verdict_mismatches", mismatches, 0)
        cmp.add("certificate_mismatches", cert_wrong, 0)
        cmp.add("certificates_unverified", unverified, 0)
        cmp.add("slots_missing", int(not self.slots), 0)
        cmp.add("degraded_verifies",
                self.sig_manager.degraded_verifies.value, 0)
        cmp.add("breaker_events", breaker_events(self._breaker0), 0)
        say(phase="check", slots=len(self.slots),
            certificates_compared=len(self.slots),
            verdict_slots_sampled=len(sample), verdicts_compared=compared,
            sigs_device_dispatched=(
                self.sig_manager.sigs_device_dispatched.value))

    def close(self) -> None:
        self._stop.set()
        if self._producer is not None:
            self._producer.join(30)
