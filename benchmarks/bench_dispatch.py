"""Dispatcher throughput under synthetic verified-traffic flood:
admission plane ON vs OFF.

The measured pipeline is one BACKUP replica's full ingest path — the
transport upcall (`on_new_message`) through parse, client-signature
verification, and the dispatcher handler that arms the dead-primary
liveness clock — with a null transport (sends dropped), so the number
is the replica's message-processing rate, not the network's.

Two flood shapes per mode, back-to-back A/B pairs:

  * distinct   — M individually-signed, never-repeated ClientRequests:
    every message pays a real signature verification. Admission ON
    coalesces them into per-drain `verify_batch` calls on the worker
    pool; OFF runs the legacy dispatcher-unpack + req_batcher path.
  * storm      — K distinct requests replayed to M total (the
    retransmit-flood shape): admission's header peek + within-drain
    duplicate collapse + the SigManager memo shed the repeats before
    the dispatcher pays a full unpack for each.

Completion is observed on the CONSUMER side (admission `processed`
marker / dispatcher `handled_external`, empty queues, no in-flight
verifies), so elapsed time covers the whole pipeline drain.

A third scenario, `--principals N` (ISSUE 19), measures the
million-principal client plane: a backup replica configured with an
N-client universe is flooded from principals strided across the whole
range, then the flood is replayed (the retransmit pass). The client
pubkey table is VIRTUAL (derived on demand from the cluster seed, never
materialized), the client table is the bounded LRU, and the leg asserts
the structural claims — resident records stay under `client_table_max`,
RSS stays under an absolute ceiling, and the verified-signature memo
hit-rate on the replay pass holds at N relative to the 10k baseline leg
run first in the same process. At full scale the leg runs a
sharded-vs-unsharded admission A/B (admission_key_sharding on/off).

Usage: python -m benchmarks.bench_dispatch [--msgs 1200] [--distinct 64]
       [--samples 2] [--workers 2] [--smoke]
       [--principals 1000000 [--table-max 2048] [--rss-ceiling-mb 4096]]
Prints one JSON line per (shape, mode, sample) plus a summary line with
the per-shape median speedups. --smoke runs a tiny fixed shape for
tier-1 (tests/test_bench_dispatch_smoke.py).
"""
from __future__ import annotations

import argparse
import json
import statistics
import time
from collections import OrderedDict
from typing import Iterator, List, Mapping, Optional

from tpubft.comm.interfaces import (ConnectionStatus, ICommunication,
                                    IReceiver, NodeNum)
from tpubft.consensus import messages as m
from tpubft.consensus.keys import ClusterKeys
from tpubft.consensus.replica import Replica
from tpubft.utils.config import ReplicaConfig

F = 1
CLIENTS = 2
SEED = b"bench-dispatch"


class NullComm(ICommunication):
    """Counts sends, delivers nothing: the replica under flood must not
    spend the measurement window on real sockets."""

    def __init__(self) -> None:
        self.sent = 0
        self._running = False

    def start(self, receiver: IReceiver) -> None:
        self._running = True

    def stop(self) -> None:
        self._running = False

    def is_running(self) -> bool:
        return self._running

    def send(self, dest: NodeNum, data: bytes) -> None:
        self.sent += 1

    def get_connection_status(self, node: NodeNum) -> ConnectionStatus:
        return ConnectionStatus.CONNECTED


def _make_replica(workers: int, **cfg_overrides):
    """One backup replica (id 1 of n=4, view 0) with a null transport.
    The view-change timer is parked: a flood bench must not complain its
    way into a view change mid-measurement."""
    from tpubft.apps.counter import CounterHandler
    cfg = ReplicaConfig(replica_id=1, f_val=F,
                        num_of_client_proxies=CLIENTS,
                        admission_workers=workers,
                        view_change_timer_ms=3_600_000,
                        **cfg_overrides)
    keys = ClusterKeys.generate(cfg, CLIENTS, seed=SEED)
    rep = Replica(cfg, keys.for_node(1), NullComm(), CounterHandler())
    rep.start()
    return rep, keys, cfg.n_val + cfg.num_ro_replicas


def _signed_requests(keys, first_client: int, count: int,
                     base_seq: int) -> List[tuple]:
    """`count` distinct signed requests round-robined over the client
    principals; returns [(client_id, packed bytes)]."""
    signers = {c: keys.for_node(c).my_signer()
               for c in range(first_client, first_client + CLIENTS)}
    out = []
    for i in range(count):
        cid = first_client + i % CLIENTS
        req = m.ClientRequestMsg(sender_id=cid,
                                 req_seq_num=base_seq + i // CLIENTS,
                                 flags=0, request=b"flood-%d" % i,
                                 cid="", signature=b"")
        req.signature = signers[cid].sign(req.signed_payload())
        out.append((cid, req.pack()))
    return out


def _drain_done(rep, injected: int, distinct: int) -> bool:
    if rep.admission is not None:
        ingested = rep.admission.processed >= injected
    else:
        ingested = rep.dispatcher.handled_external >= injected
    return (ingested
            and rep.incoming.external_depth == 0
            and rep.incoming.internal_depth == 0
            and not rep._req_verifying
            and len(rep._forwarded) >= distinct)


def _run_flood(rep, flood: List[tuple], distinct: int,
               timeout_s: float = 300.0,
               injected_before: int = 0) -> Optional[float]:
    """`injected_before`: messages this replica already consumed in a
    prior pass (the ingest markers are cumulative — a replay pass must
    wait for ITS messages, not return on the first pass's count)."""
    t0 = time.perf_counter()
    for cid, raw in flood:
        rep.on_new_message(cid, raw)
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if _drain_done(rep, injected_before + len(flood), distinct):
            return time.perf_counter() - t0
        time.sleep(0.002)
    return None


def run_pair(shape: str, msgs: int, distinct: int, workers: int,
             sample: int) -> List[dict]:
    """One back-to-back A/B pair (fresh replica per mode, same flood
    content), so host noise hits both legs alike."""
    rows = []
    for mode, w in (("admission", workers), ("inline", 0)):
        rep, keys, first_client = _make_replica(w)
        try:
            base_seq = int(time.time() * 1e6)
            uniq = _signed_requests(keys, first_client,
                                    distinct if shape == "storm" else msgs,
                                    base_seq)
            flood = (uniq * (msgs // len(uniq) + 1))[:msgs] \
                if shape == "storm" else uniq
            dt = _run_flood(rep, flood, min(distinct, msgs)
                            if shape == "storm" else msgs)
            row = {
                "bench": "dispatch_flood", "shape": shape, "mode": mode,
                "sample": sample, "msgs": msgs,
                "distinct": len(uniq), "admission_workers": w,
                "secs": round(dt, 3) if dt else None,
                "msgs_per_sec": round(msgs / dt, 1) if dt else None,
            }
            if rep.admission is not None:
                c = rep.admission.metrics.counters
                row["adm"] = {k: v.value for k, v in c.items()}
            sm = rep.sig.metrics.counters
            row["sig"] = {k: sm[k].value for k in
                          ("memo_hits", "batched_verifies",
                           "scalar_fallbacks")}
            rows.append(row)
        finally:
            rep.stop()
    return rows


def run(msgs: int, distinct: int, samples: int, workers: int,
        shapes=("distinct", "storm"), profile: bool = False) -> List[dict]:
    if profile:
        from tpubft.utils import flight
        flight.reset()
    rows = []
    for shape in shapes:
        for s in range(samples):
            pair = run_pair(shape, msgs, distinct, workers, s)
            rows.extend(pair)
            for r in pair:
                print(json.dumps(r), flush=True)
    # summary: per-shape median speedup over the recorded pairs
    summary = {"bench": "dispatch_flood_summary", "msgs": msgs,
               "workers": workers}
    if profile:
        # the backup-flood shape orders no slots, so the interesting
        # profile here is the ingest plane + kernels; stage_breakdown
        # is attached for symmetry with bench_e2e --profile (it fills
        # up when a shape does order traffic)
        from tpubft.utils import flight
        summary["recorder_enabled"] = flight.enabled()
        summary["stage_breakdown"] = flight.stage_summary()
        summary["kernel_profile"] = flight.kernel_profiler().snapshot()
    for shape in shapes:
        ons = [r["msgs_per_sec"] for r in rows
               if r["shape"] == shape and r["mode"] == "admission"
               and r["msgs_per_sec"]]
        offs = [r["msgs_per_sec"] for r in rows
                if r["shape"] == shape and r["mode"] == "inline"
                and r["msgs_per_sec"]]
        if ons and offs and len(ons) == len(offs):
            ratios = [a / b for a, b in zip(ons, offs)]
            summary[f"{shape}_speedup_median"] = round(
                statistics.median(ratios), 2)
            summary[f"{shape}_speedups"] = [round(x, 2) for x in ratios]
    print(json.dumps(summary), flush=True)
    rows.append(summary)
    return rows


# ---------------------------------------------------------------------
# --principals: million-principal client plane (ISSUE 19)
# ---------------------------------------------------------------------

class LazyClientKeys(Mapping):
    """Virtual `client_pubkeys` for huge principal universes: derives a
    principal's pubkey on demand from the cluster seed (the exact bytes
    ClusterKeys.generate would have produced) instead of materializing
    N entries up front. SigManager keeps non-dict mappings by reference
    for precisely this shape; a small LRU memo keeps repeat lookups
    from the verify plane cheap without growing with the universe."""

    _MEMO_MAX = 8192

    def __init__(self, seed: bytes, scheme: str, first_client: int,
                 count: int, extra: dict) -> None:
        from tpubft.consensus.keys import _derive_seed
        from tpubft.crypto.cpu import make_signer
        self._derive = lambda cl: make_signer(
            scheme, seed=_derive_seed(seed, "client", cl)).public_bytes()
        self._range = range(first_client, first_client + count)
        self._extra = dict(extra)      # operator principal
        self._memo: "OrderedDict[int, bytes]" = OrderedDict()

    def __getitem__(self, cl: int) -> bytes:
        pk = self._extra.get(cl)
        if pk is not None:
            return pk
        if cl not in self._range:
            raise KeyError(cl)
        pk = self._memo.get(cl)
        if pk is None:
            pk = self._memo[cl] = self._derive(cl)
            while len(self._memo) > self._MEMO_MAX:
                self._memo.popitem(last=False)
        return pk

    def __len__(self) -> int:
        return len(self._range) + len(self._extra)

    def __iter__(self) -> Iterator[int]:
        yield from self._range
        yield from (k for k in self._extra if k not in self._range)


def _rss_mb() -> int:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) // 1024
    return -1


def _make_principals_replica(scale: int, workers: int, **cfg_overrides):
    """Backup replica fronting a `scale`-principal client universe.
    Client key material is virtual (LazyClientKeys) and the client table
    is the bounded pager (client_table_max must stay > 0 here — the
    legacy eager table would materialize `scale` records at boot)."""
    from tpubft.apps.counter import CounterHandler
    cfg = ReplicaConfig(replica_id=1, f_val=F,
                        num_of_client_proxies=scale,
                        admission_workers=workers,
                        view_change_timer_ms=3_600_000,
                        **cfg_overrides)
    assert cfg.client_table_max > 0, "principals bench needs paged table"
    keys = ClusterKeys.generate(cfg, 0, seed=SEED)   # 0 eager client keys
    first_client = cfg.n_val + cfg.num_ro_replicas
    keys.client_pubkeys = LazyClientKeys(
        SEED, keys.client_sig_scheme, first_client, scale,
        extra=keys.client_pubkeys)
    rep = Replica(cfg, keys.for_node(1), NullComm(), CounterHandler())
    rep.start()
    return rep, first_client


def _principal_flood(scheme: str, first_client: int, scale: int,
                     distinct: int, base_seq: int) -> List[tuple]:
    """`distinct` signed requests from principals strided across the
    whole universe (each principal sends once — the cold-contact shape
    that exercises demand paging, not per-client request streams)."""
    from tpubft.consensus.keys import _derive_seed
    from tpubft.crypto.cpu import make_signer
    stride = max(1, scale // distinct)
    out = []
    for i in range(min(distinct, scale)):
        cid = first_client + i * stride
        signer = make_signer(scheme, seed=_derive_seed(SEED, "client", cid))
        req = m.ClientRequestMsg(sender_id=cid, req_seq_num=base_seq,
                                 flags=0, request=b"p-%d" % i,
                                 cid="", signature=b"")
        req.signature = signer.sign(req.signed_payload())
        out.append((cid, req.pack()))
    return out


def _principals_leg(scale: int, distinct: int, workers: int,
                    table_max: int, sharded: bool) -> dict:
    """One leg: cold flood from `distinct` principals out of a `scale`
    universe, then a replay of the same bytes (the retransmit pass the
    verify memo and client-table LRU exist for)."""
    # autotuning off: the client_table_max knob would (correctly) GROW
    # under a 100%-cold-miss flood, but this leg measures the FIXED
    # bound — the knob's reactions are unit-test/bench_autotune scope
    rep, first_client = _make_principals_replica(
        scale, workers, client_table_max=table_max,
        admission_key_sharding=sharded, autotune_enabled=False)
    try:
        base_seq = int(time.time() * 1e6)
        flood = _principal_flood(rep.keys.client_sig_scheme, first_client,
                                 scale, distinct, base_seq)
        t0 = time.perf_counter()
        dt_cold = _run_flood(rep, flood, len(flood))
        dt_replay = _run_flood(rep, flood, len(flood),
                               injected_before=len(flood)) \
            if dt_cold is not None else None
        total = time.perf_counter() - t0
        sm = rep.sig.metrics.counters
        memo_hits = sm["memo_hits"].value
        row = {
            "bench": "dispatch_principals", "principals": scale,
            "distinct": len(flood), "workers": workers,
            "mode": "sharded" if sharded and workers > 1 else "unsharded",
            "client_table_max": table_max,
            "cold_secs": round(dt_cold, 3) if dt_cold else None,
            "replay_secs": round(dt_replay, 3) if dt_replay else None,
            "msgs_per_sec": round(2 * len(flood) / total, 1)
            if dt_replay else None,
            "rss_mb": _rss_mb(),
            "resident_clients": rep.clients.resident_count,
            "client_table": {"hits": rep.clients.table_hits,
                             "misses": rep.clients.table_misses,
                             "evictions": rep.clients.table_evictions},
            # replay-pass memo hit-rate: of the replayed signatures, how
            # many were shed by the verified-signature memo
            "memo_hits": memo_hits,
            "memo_hit_rate": round(memo_hits / len(flood), 3),
            "sig": {k: sm[k].value for k in
                    ("batched_verifies", "scalar_fallbacks",
                     "verifier_evictions")},
        }
        if rep.admission is not None:
            row["adm"] = {k: v.value
                          for k, v in rep.admission.metrics.counters.items()}
        return row
    finally:
        rep.stop()


def run_principals(principals: int, distinct: int, workers: int,
                   table_max: int, rss_ceiling_mb: int,
                   baseline: int = 10_000) -> List[dict]:
    """The ISSUE 19 scenario: 10k-principal baseline leg, then the full-
    scale leg(s). At full scale, sharded-vs-unsharded admission A/B.
    Asserts the structural claims (bounded residency, RSS ceiling, memo
    hit-rate holding vs the baseline) — a regression fails the bench,
    not just a number in a row."""
    # the flood must outrun the table or the leg never proves eviction
    distinct = max(distinct, table_max + table_max // 2)
    legs = [(min(baseline, principals), True)]
    if principals > baseline:
        legs += [(principals, True)]
        if workers > 1:
            legs += [(principals, False)]
    rows = []
    for scale, sharded in legs:
        row = _principals_leg(scale, distinct, workers, table_max, sharded)
        rows.append(row)
        print(json.dumps(row), flush=True)
    base, tail = rows[0], rows[1:]
    summary = {"bench": "dispatch_principals_summary",
               "principals": principals, "distinct": distinct,
               "workers": workers, "client_table_max": table_max,
               "rss_ceiling_mb": rss_ceiling_mb}
    if len(tail) == 2:      # sharded + unsharded full-scale pair
        a, b = tail[0]["msgs_per_sec"], tail[1]["msgs_per_sec"]
        if a and b:
            summary["sharded_speedup"] = round(a / b, 2)
    for row in rows:
        assert row["replay_secs"] is not None, f"leg did not drain: {row}"
        # bounded residency: the LRU held (the pinned-burst slack is
        # _EVICT_SCAN_MAX, tiny next to the bound)
        assert row["resident_clients"] <= table_max + 8, row
        assert row["rss_mb"] < rss_ceiling_mb, \
            f"RSS {row['rss_mb']}MB over {rss_ceiling_mb}MB ceiling"
    for row in tail:
        # the replay-pass memo hit-rate must hold at full scale: the
        # memo is keyed by (principal, digest, sig), so universe size
        # must not dilute it
        assert row["memo_hit_rate"] >= 0.9 * base["memo_hit_rate"], \
            (row["memo_hit_rate"], base["memo_hit_rate"])
    summary["ok"] = True
    print(json.dumps(summary), flush=True)
    rows.append(summary)
    return rows


def smoke_principals() -> dict:
    """Tier-1 shape: a 10k-principal universe, a flood wider than the
    client table, replayed — asserts bounded residency, real evictions,
    demand re-paging, and the replay memo shed (structure, not speed)."""
    rows = run_principals(principals=10_000, distinct=96, workers=1,
                          table_max=64, rss_ceiling_mb=8192)
    leg = rows[0]
    return {
        "ok": bool(rows[-1].get("ok")),
        "drained": leg["replay_secs"] is not None,
        "bounded": leg["resident_clients"] <= 64 + 8,
        "evicted": leg["client_table"]["evictions"] > 0,
        "repaged": leg["client_table"]["misses"] > leg["distinct"] // 2,
        "memo_shed": leg["memo_hits"] > 0,
        "leg": leg,
    }


def smoke() -> dict:
    """Tier-1 shape: tiny flood through both modes; asserts both drain
    and that the admission plane actually shed the storm repeats before
    the dispatcher (the structural property, not a perf number —
    wall-clock ratios are not asserted in CI)."""
    rows = run(msgs=300, distinct=16, samples=1, workers=1,
               shapes=("storm",))
    on = next(r for r in rows if r.get("mode") == "admission")
    off = next(r for r in rows if r.get("mode") == "inline")
    adm = on["adm"]
    return {
        "ok": bool(on["secs"] and off["secs"]),
        "admission_drained": on["secs"] is not None,
        "inline_drained": off["secs"] is not None,
        # the dispatcher saw only the admitted survivors, not the flood
        "shed": adm["adm_drops_pre_parse"] > 0,
        "adm": adm,
    }


def device_fault(msgs: int = 360, warmup: int = 64,
                 drain_max: int = 16) -> dict:
    """Kill-the-device scenario (degradation plane): the replica runs
    the REAL device verify ride (crypto_backend=tpu on whatever jax
    backend this host has — the breaker's reaction is what's measured,
    not kernel speed). Mid-flood the ed25519 kernel is replaced with a
    raiser ("the accelerator transport died"); recorded:

      * time-to-degraded  — kill → breaker OPEN (consensus ingest keeps
        draining on the scalar engines throughout);
      * time-to-restored  — kernel restored → breaker CLOSED via the
        half-open probe batch, device path hot again.
    """
    from tpubft.ops import ed25519 as ops_ed
    from tpubft.ops.dispatch import device_breaker

    # persistent compile cache: the windowed verify kernel is a large
    # XLA program; repeat bench runs should not re-pay the compile
    from tpubft.utils.jaxcache import setup_cache
    setup_cache()

    b = device_breaker()
    rep, keys, first_client = _make_replica(
        1, crypto_backend="tpu", device_min_verify_batch=1,
        admission_drain_max=drain_max,
        breaker_failure_threshold=3, breaker_cooldown_ms=500)
    # bound probe-failure escalation so time-to-restored reflects the
    # configured cooldown, not however long the kill window lasted
    b.configure(max_cooldown_s=1.0)
    b.reset()
    row = {"bench": "dispatch_device_fault", "msgs": msgs,
           "warmup": warmup, "drain_max": drain_max}
    real_kernel = ops_ed.verify_kernel

    def boom(*a, **kw):
        raise RuntimeError("injected device loss")

    try:
        base_seq = int(time.time() * 1e6)
        flood = _signed_requests(keys, first_client, warmup, base_seq)
        dt = _run_flood(rep, flood, warmup, timeout_s=600.0)
        row["warmup_secs"] = round(dt, 3) if dt else None
        row["device_path_proven"] = \
            rep.sig.sigs_device_dispatched.value > 0
        injected = warmup

        # ---- kill the device mid-run ----
        ops_ed.verify_kernel = boom
        t_kill = time.perf_counter()
        t_open = None
        sent = 0
        while sent < msgs:
            chunk = _signed_requests(keys, first_client, drain_max,
                                     base_seq + 10_000 + sent)
            for cid, raw in chunk:
                rep.on_new_message(cid, raw)
            sent += len(chunk)
            injected += len(chunk)
            deadline = time.monotonic() + 30
            while rep.admission.processed < injected \
                    and time.monotonic() < deadline:
                if t_open is None and b.state == "open":
                    t_open = time.perf_counter()
                time.sleep(0.001)
            if t_open is None and b.state == "open":
                t_open = time.perf_counter()
        row["time_to_degraded_ms"] = (
            round((t_open - t_kill) * 1e3, 1) if t_open else None)
        # goodput continued: everything injected after the kill fully
        # drained through the scalar engines
        row["drained_while_degraded"] = \
            rep.admission.processed >= injected
        row["degraded_verifies"] = rep.sig.degraded_verifies.value
        row["scalar_fallbacks"] = rep.sig.scalar_fallbacks.value

        # ---- restore: half-open probe re-admits the device ----
        ops_ed.verify_kernel = real_kernel
        t_restore = time.perf_counter()
        t_closed = None
        deadline = time.monotonic() + 60
        probe_seq = base_seq + 50_000
        while time.monotonic() < deadline:
            # distinct seqs each tick: a duplicate would memo-hit and
            # never reach the device, starving the half-open probe
            probe_seq += 10
            chunk = _signed_requests(keys, first_client, 4, probe_seq)
            for cid, raw in chunk:
                rep.on_new_message(cid, raw)
            injected += len(chunk)
            time.sleep(0.05)
            if b.state == "closed":
                t_closed = time.perf_counter()
                break
        row["time_to_restored_ms"] = (
            round((t_closed - t_restore) * 1e3, 1) if t_closed else None)
        row["breaker"] = b.snapshot()
        row["health"] = rep.health.verdict()["verdict"]
        row["ok"] = bool(row["device_path_proven"] and t_open
                         and t_closed and row["drained_while_degraded"])
        return row
    finally:
        ops_ed.verify_kernel = real_kernel
        rep.stop()
        b.configure(failure_threshold=3, cooldown_s=2.0,
                    max_cooldown_s=32.0)
        b.reset()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--msgs", type=int, default=1200,
                    help="flood size per sample")
    ap.add_argument("--distinct", type=int, default=64,
                    help="distinct signed requests in the storm shape")
    ap.add_argument("--samples", type=int, default=2,
                    help="back-to-back A/B pairs per shape")
    ap.add_argument("--workers", type=int, default=1,
                    help="admission_workers for the ON mode")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--principals", type=int, default=0,
                    help="million-principal client-plane scenario: "
                         "universe size for the full-scale leg")
    ap.add_argument("--table-max", type=int, default=2048,
                    help="client_table_max for the principals legs")
    ap.add_argument("--rss-ceiling-mb", type=int, default=4096,
                    help="asserted RSS ceiling for the principals legs")
    ap.add_argument("--profile", action="store_true",
                    help="attach the flight recorder's stage breakdown "
                         "and kernel profile to the summary row")
    ap.add_argument("--device-fault", action="store_true",
                    help="kill-the-device scenario: time-to-degraded / "
                         "time-to-restored through the breaker")
    args = ap.parse_args()
    if args.smoke:
        print(json.dumps(smoke()), flush=True)
        return
    if args.principals:
        run_principals(args.principals, args.distinct * 8, args.workers,
                       args.table_max, args.rss_ceiling_mb)
        return
    if args.device_fault:
        print(json.dumps(device_fault()), flush=True)
        return
    run(args.msgs, args.distinct, args.samples, args.workers,
        profile=args.profile)


if __name__ == "__main__":
    main()
