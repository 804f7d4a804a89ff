"""End-to-end simpleKVBC ordering throughput (BASELINE configs 1-2).

The consensus-level number the reference never published: ops/sec a
client sees against a live cluster (reference measurement path:
tests/simpleKVBC TesterClient + Apollo's bft.py; kvbc add-block
throughput harness kvbc/benchmark/kvbcbench/main.cpp).

Configs (BASELINE.json `configs`):
  1. n=4 (f=1), multisig-ed25519 commit certs   — config 1
  2. n=7 (f=2), threshold-bls commit certs      — config 2
  3. n=31 (f=10), secp256k1 client sigs + threshold-bls commit certs
     (the Apollo 31-replica cluster shape)       — config 3
  5. n=4 (f=1), ECDSA-P256 clients + threshold-bls over TLS, with a
     view-change storm (primary paused every storm-period) — config 5
Each runs with the backends named by --backends: "tpu" needs the chip
(or JAX_PLATFORMS=cpu, the XLA-CPU rehearsal, and the row then says
nothing about a device). --processes with a device backend is refused:
a chip serves one process (tpubft.crypto.backend.check_process_fanout,
ROADMAP C6) — the in-process cluster is how n replicas share one chip.
(Config 4 — the n=1000 synthetic PrePrepare/share flood — is the
separate benchmarks/bench_flood.py: it measures the crypto plane at a
scale no single-host cluster can reach.)

Usage: python -m benchmarks.bench_e2e [--secs 10] [--clients 4]
       [--configs 1,2] [--backends cpu,tpu] [--processes]
Prints one JSON line per (config, backend).
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

def fsync_probe_ms(dir_path: str = None, samples: int = 5) -> float:
    """Median cost of one 4KiB write+fsync on the disk under
    `dir_path` (default: the tempdir the replica DBs land in) —
    machine-readable context for every row: the shared-disk fsync is
    nonstationary (2-21ms observed across rounds) and dominates
    run-to-run variance on the write path, which is exactly what the
    durability pipeline's group commit amortizes."""
    import os
    import statistics as stats
    import tempfile
    d = dir_path or tempfile.gettempdir()
    times = []
    try:
        fd, path = tempfile.mkstemp(dir=d, prefix="fsync-probe-")
        try:
            payload = b"\x5a" * 4096
            for _ in range(samples):
                t0 = time.perf_counter()
                os.write(fd, payload)
                os.fsync(fd)
                times.append((time.perf_counter() - t0) * 1e3)
        finally:
            os.close(fd)
            os.unlink(path)
    except OSError:
        return -1.0                       # unprobeable filesystem
    return round(stats.median(times), 3)


def _dur_group_len(runs, groups) -> float:
    """runs-per-group amortization factor (None until a group landed)."""
    runs, groups = runs or 0, groups or 0
    return round(runs / groups, 2) if groups else None


CONFIGS = {
    1: dict(f=1, threshold_scheme="multisig-ed25519"),
    2: dict(f=2, threshold_scheme="threshold-bls"),
    3: dict(f=10, threshold_scheme="threshold-bls",
            client_sig_scheme="ecdsa-secp256k1",
            # a 31-replica co-located cluster pays ~n pairing checks per
            # round on one host: keep the VC timer out of the measurement,
            # stop the 300ms fast-path timer from firing on >600ms
            # co-location slots (spurious slow-path crypto), and don't
            # pipeline slots (overlap amplifies the n=31 contention —
            # depth 1 measured 1.8x depth 3 on a 1-core host)
            view_change_timer_ms=30000,
            fast_path_timeout_ms=5000,
            concurrency_level=1),
    5: dict(f=1, threshold_scheme="threshold-bls",
            client_sig_scheme="ecdsa-p256", transport="tls",
            storm_period_s=4.0),
}


def _handler_factory(_r=None):
    return skvbc.SkvbcHandler(KeyValueBlockchain(MemoryDB()))


def _drive(make_kv, config: int, backend: str, secs: float,
           clients: int, mode: str = None,
           warmup_timeout_ms: int = 20000,
           client_batch: int = 1, op_timeout_ms: int = 8000) -> dict:
    """Shared workload driver: `make_kv(idx)` returns a SkvbcClient
    bound to client `idx`; one stats pipeline serves both harness
    modes (so BASELINE numbers can never drift between them).
    client_batch>1 sends that many independent transactions per wire
    message (ClientBatchRequestMsg); each counts as one op."""
    cfg = CONFIGS[config]
    counts = [0] * clients
    lats: List[List[float]] = [[] for _ in range(clients)]
    stop_at = [0.0]

    def worker(idx: int) -> None:
        kv = make_kv(idx)
        i = 0
        while time.monotonic() < stop_at[0]:
            t0 = time.monotonic()
            try:
                if client_batch > 1:
                    ws = [[(b"bench-%d-%d" % (idx, (i + j) % 64),
                            b"v%d" % (i + j))]
                          for j in range(client_batch)]
                    rs = kv.write_batch(ws, timeout_ms=op_timeout_ms)
                    dt = time.monotonic() - t0
                    ok = sum(1 for r in rs if r.success)
                    if ok:
                        counts[idx] += ok
                        lats[idx].append(dt)
                    i += client_batch
                    continue
                r = kv.write([(b"bench-%d-%d" % (idx, i % 64),
                               b"v%d" % i)], timeout_ms=op_timeout_ms)
            except Exception:  # noqa: BLE001 — lossy transports time out
                i += client_batch if client_batch > 1 else 1
                continue
            dt = time.monotonic() - t0
            if r.success:
                counts[idx] += 1
                lats[idx].append(dt)
            i += 1

    # warmup: first write pays kernel compiles on the tpu backend
    assert make_kv(0).write([(b"warmup", b"w")],
                            timeout_ms=warmup_timeout_ms).success, \
        "cluster failed to order the warmup write"
    stop_at[0] = time.monotonic() + secs
    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    total = sum(counts)
    all_lats = sorted(x for ls in lats for x in ls)
    row = {
        "config": config, "n": 3 * cfg["f"] + 1, "f": cfg["f"],
        "threshold_scheme": cfg["threshold_scheme"],
        "client_sig_scheme": cfg.get("client_sig_scheme", "ed25519"),
        "transport": cfg.get("transport", "udp/loopback"),
        "backend": backend,
        "clients": clients, "secs": round(wall, 2), "ops": total,
        **({"client_batch": client_batch} if client_batch > 1 else {}),
        "ops_per_sec": round(total / wall, 1),
        "mean_latency_ms": round(statistics.mean(all_lats) * 1e3, 2)
        if all_lats else None,
        "p90_latency_ms": round(all_lats[int(len(all_lats) * 0.9)] * 1e3, 2)
        if all_lats else None,
    }
    if mode:
        row["mode"] = mode
    return row


def run_config(config: int, backend: str, secs: float,
               clients: int, client_batch: int = 1,
               extra_overrides: dict = None,
               op_timeout_ms: int = 8000,
               profile: bool = False) -> dict:
    cfg = CONFIGS[config]
    if cfg.get("transport") or cfg.get("storm_period_s"):
        # TLS transport and the VC storm only exist on real processes; an
        # in-process row must not claim a fidelity it didn't run with
        raise SystemExit(
            f"config {config} requires --processes (tls/storm fidelity)")
    # every ReplicaConfig field in the CONFIGS entry flows through (f and
    # the process-only keys are harness-level); cherry-picking fields
    # here silently dropped new tunings
    overrides = {k: v for k, v in cfg.items()
                 if k not in ("f", "transport", "storm_period_s")}
    overrides.setdefault("client_sig_scheme", "ed25519")
    overrides["crypto_backend"] = backend
    overrides.update(extra_overrides or {})
    if profile:
        # fresh recorder so the stage breakdown covers exactly this run
        from tpubft.utils import flight
        flight.reset()
    with InProcessCluster(f=cfg["f"], num_clients=clients,
                          handler_factory=_handler_factory,
                          cfg_overrides=overrides) as cluster:
        row = _drive(lambda i: skvbc.SkvbcClient(cluster.client(i)),
                     config, backend, secs, clients,
                     warmup_timeout_ms=60000 if cfg["f"] > 2 else 20000,
                     client_batch=client_batch,
                     op_timeout_ms=op_timeout_ms)
        row["fsync_probe_ms"] = fsync_probe_ms()

        def _dur(i: int, name: str) -> int:
            try:   # pipeline-off legs have no durability component
                return cluster.metric(i, "counters", name,
                                      component="durability") or 0
            except KeyError:
                return 0

        n = 3 * cfg["f"] + 1
        row["dur_group_len"] = _dur_group_len(
            sum(_dur(i, "dur_runs") for i in range(n)),
            sum(_dur(i, "dur_groups") for i in range(n)))
        if overrides.get("optimistic_replies"):
            # the optimistic plane's own evidence: slots released to
            # the reply path before the pairing verify landed, and any
            # deferred-cert failures (must be 0 on an honest cluster)
            row["opt_releases"] = sum(
                cluster.metric(i, "counters", "optimistic_releases")
                for i in range(n))
            row["cert_async_failures"] = sum(
                cluster.metric(i, "counters", "cert_async_failures")
                for i in range(n))
        if extra_overrides:
            row["overrides"] = dict(extra_overrides)
        if profile:
            # per-slot stage breakdown (adm_wait/dispatch/prepare/
            # commit/exec/reply) + kernel profile, folded by the flight
            # recorder across every replica of the in-process cluster
            from tpubft.utils import flight
            row["stage_breakdown"] = flight.stage_summary()
            row["kernel_profile"] = flight.kernel_profiler().snapshot()
            # autotuner state (knob values + decision log per replica)
            # while the controllers are still registered — bench_autotune
            # joins this to the A/B goodput rows
            tuning = {name: state for name, state
                      in flight._provider_payloads().items()
                      if name.startswith("tuning")}
            if tuning:
                row["tuning_state"] = tuning
        return row


def _storm(net, stop_evt, period_s: float) -> None:
    """View-change storm driver (config 5): pause the CURRENT primary for
    a view-change-timeout's worth of silence, resume it, repeat — every
    cycle forces a real view change while clients keep submitting. The
    primary is read from live metrics (a spontaneous, load-induced view
    change must not desynchronize the storm into pausing backups)."""
    while not stop_evt.wait(period_s):
        views = [net.current_view(r) for r in range(net.n)]
        view = max((v for v in views if v is not None), default=0)
        r = view % net.n                 # round-robin primary assignment
        net.pause_replica(r)
        # hold past the VC timeout so the complaint quorum forms
        interrupted = stop_evt.wait(net.view_change_timeout_ms / 1000.0
                                    + 1.0)
        net.resume_replica(r)
        if interrupted:
            return


def run_config_processes(config: int, backend: str, secs: float,
                         clients: int, client_batch: int = 1,
                         extra_overrides: dict = None,
                         op_timeout_ms: int = 8000) -> dict:
    """REAL replica OS processes (BftTestNetwork) — no shared-GIL
    inflation; this is the deployment-shaped number."""
    import tempfile
    import threading as _t

    from tpubft.testing.network import BftTestNetwork
    cfg = CONFIGS[config]
    # ReplicaConfig fields without a dedicated BftTestNetwork parameter
    # ride the generic --config-override plumbing — process rows must run
    # the same tunings as the in-process rows
    flagged = ("f", "transport", "storm_period_s", "threshold_scheme",
               "client_sig_scheme", "view_change_timer_ms")
    overrides = {k: v for k, v in cfg.items() if k not in flagged}
    overrides.update(extra_overrides or {})
    with tempfile.TemporaryDirectory() as tmp, \
            BftTestNetwork(f=cfg["f"], num_clients=max(4, clients),
                           db_dir=tmp, crypto_backend=backend,
                           threshold_scheme=cfg["threshold_scheme"],
                           client_sig_scheme=cfg.get("client_sig_scheme",
                                                     "ed25519"),
                           view_change_timeout_ms=cfg.get(
                               "view_change_timer_ms", 3000),
                           transport=cfg.get("transport", "udp"),
                           cfg_overrides=overrides) as net:
        storm_stop = None
        storm_thread = None
        if cfg.get("storm_period_s"):
            storm_stop = _t.Event()
            storm_thread = _t.Thread(target=_storm,
                                     args=(net, storm_stop,
                                           cfg["storm_period_s"]),
                                     daemon=True)
            storm_thread.start()
        try:
            row = _drive(net.skvbc_client, config, backend, secs, clients,
                         mode="processes",
                         warmup_timeout_ms=60000 if cfg["f"] > 2
                         else 20000, client_batch=client_batch,
                         op_timeout_ms=op_timeout_ms)
        finally:
            if storm_stop is not None:
                storm_stop.set()
                storm_thread.join(timeout=10)
        if cfg.get("storm_period_s"):
            row["storm_period_s"] = cfg["storm_period_s"]
        # probe the filesystem the replica DBs actually live on — the
        # process rows are the ones where the ledger rides a real disk
        row["fsync_probe_ms"] = fsync_probe_ms(tmp)
        runs = groups = 0
        for r in range(net.n):
            # ONE snapshot per replica: both counters must come from
            # the same instant or the ratio can straddle a group
            # boundary mid-commit
            snap = (net.metrics(r).snapshot() or {}).get("components", {})
            counters = (snap.get("durability") or {}).get("counters", {})
            runs += counters.get("dur_runs") or 0
            groups += counters.get("dur_groups") or 0
        row["dur_group_len"] = _dur_group_len(runs, groups)
        if extra_overrides:
            row["overrides"] = dict(extra_overrides)
        return row


def smoke(secs: float = 2.0, clients: int = 2) -> dict:
    """Tier-1 shape (mirrors bench_st --smoke): order real traffic
    through config 1, so the ordering path — including the
    dispatcher↔executor handoff and the group-commit seal — has a
    collection-time + runtime guard in CI. Run it under
    TPUBFT_THREADCHECK=1 to arm the lock-order checker across the
    handoff (tests/test_bench_e2e_smoke.py does)."""
    from tpubft.utils.racecheck import get_watchdog
    # the optimistic-replies leg lives in smoke_optimistic() (its own
    # tier-1 test) — not duplicated here
    row = run_config(1, "cpu", secs, clients)
    return {"lane": {"ok": row["ops"] > 0, "ops": row["ops"],
                     "ops_per_sec": row["ops_per_sec"]},
            "stall_reports": get_watchdog().stall_reports}


def smoke_optimistic(secs: float = 2.0, clients: int = 2) -> dict:
    """Tier-1 A/B shape for the optimistic reply plane (ISSUE 18): the
    same config-1 workload with `optimistic_replies` on then off, one
    JSON row with the PR 4 `degraded`/`probe_error` convention — the
    row degrades (rather than fails) when the plane never actually
    released a slot, so CI flags a silently-inert plane without
    guessing at throughput on a noisy host."""
    from tpubft.utils.racecheck import get_watchdog
    on = run_config(1, "cpu", secs, clients,
                    extra_overrides={"optimistic_replies": True})
    off = run_config(1, "cpu", secs, clients,
                     extra_overrides={"optimistic_replies": False})
    row = {
        "bench": "e2e-optimistic-smoke", "unit": "ops",
        "value": on["ops"],
        "on_ops": on["ops"], "off_ops": off["ops"],
        "on_ops_per_sec": on["ops_per_sec"],
        "off_ops_per_sec": off["ops_per_sec"],
        "on_p90_latency_ms": on["p90_latency_ms"],
        "off_p90_latency_ms": off["p90_latency_ms"],
        "opt_releases": on.get("opt_releases", 0),
        "cert_async_failures": on.get("cert_async_failures", 0),
        "stall_reports": get_watchdog().stall_reports,
        "degraded": False, "probe_error": "",
    }
    problems = []
    if not on["ops"] or not off["ops"]:
        problems.append("a leg ordered zero traffic")
    if not row["opt_releases"]:
        problems.append("optimistic plane never released a slot")
    if row["cert_async_failures"]:
        problems.append("deferred cert verification failed on an "
                        "honest cluster")
    if problems:
        row["degraded"] = True
        row["probe_error"] = "; ".join(problems)
    return row


def main() -> None:
    from tpubft.utils.jaxcache import setup_cache
    setup_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--secs", type=float, default=10.0)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--client-batch", type=int, default=1,
                    help=">1: transactions per wire message "
                         "(ClientBatchRequestMsg)")
    ap.add_argument("--configs", default="1,2")
    ap.add_argument("--backends", default="cpu")
    ap.add_argument("--processes", action="store_true",
                    help="real replica OS processes instead of the "
                         "in-process cluster")
    ap.add_argument("--override", action="append", default=[],
                    metavar="FIELD=VALUE",
                    help="extra ReplicaConfig override applied to every "
                         "replica (repeatable) — e.g. "
                         "execution_max_accumulation=1")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fixed shape for CI")
    ap.add_argument("--smoke-optimistic", action="store_true",
                    help="tiny fixed optimistic-replies A/B shape for "
                         "CI: one JSON row (degraded/probe_error "
                         "convention)")
    ap.add_argument("--optimistic-off", action="store_true",
                    help="A/B control leg: run with the optimistic "
                         "reply plane OFF (replies certificate-gated). "
                         "Without this flag the bench runs the plane ON "
                         "— pair alternating on/off invocations")
    ap.add_argument("--profile", action="store_true",
                    help="attach the flight recorder's per-slot stage "
                         "breakdown (adm_wait/dispatch/prepare/commit/"
                         "exec/reply) and kernel profile to each row "
                         "(in-process configs only)")
    ap.add_argument("--timeout-ms", type=int, default=8000,
                    help="per-op client timeout; raise for saturated "
                         "deep-batch shapes so a slow config degrades "
                         "gracefully instead of timing out")
    args = ap.parse_args()
    if args.smoke:
        print(json.dumps(smoke()), flush=True)
        return
    if args.smoke_optimistic:
        print(json.dumps(smoke_optimistic()), flush=True)
        return
    from tpubft.utils.config import parse_config_overrides
    extra = parse_config_overrides(args.override)
    if args.optimistic_off:
        extra["optimistic_replies"] = False
    else:
        # the measured configuration IS the optimistic plane (ISSUE 18);
        # --optimistic-off is the paired control leg
        extra.setdefault("optimistic_replies", True)
    if args.profile and args.processes:
        raise SystemExit("--profile reads the in-process flight "
                         "recorder; with --processes take per-replica "
                         "dumps (status get flight) and merge them "
                         "with tools/tpuprof.py instead")
    for config in [int(x) for x in args.configs.split(",")]:
        for backend in args.backends.split(","):
            if args.processes:
                row = run_config_processes(
                    config, backend, args.secs, args.clients,
                    args.client_batch, extra_overrides=extra,
                    op_timeout_ms=args.timeout_ms)
            else:
                row = run_config(
                    config, backend, args.secs, args.clients,
                    args.client_batch, extra_overrides=extra,
                    op_timeout_ms=args.timeout_ms, profile=args.profile)
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
