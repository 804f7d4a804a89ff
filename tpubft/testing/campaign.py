"""Deterministic, seeded chaos-campaign engine.

Every adversarial scenario this repo grew so far — Apollo-style process
kills, SIGSTOP partitions, per-link drop planes, byzantine strategies,
breaker trips — existed as one-off tests drawing from unseeded RNGs: a
failure that showed up once could not be replayed. This module composes
those primitives into a *campaign*: a matrix of named scenarios where

  * every random draw flows from one ``random.Random(seed)`` (each
    scenario gets a sub-RNG derived as SHA-256(master_seed, name), so
    adding or reordering scenarios never perturbs the others' draws);
  * every scheduled action and draw is appended to an **event log**
    whose canonical-JSON SHA-256 digest is the campaign's identity —
    running the same seed twice yields the identical digest, so a red
    run attaches ``(seed, digest)`` to the bug report and anyone
    replays the exact fault schedule;
  * verdicts, recovery-time stats, and wall-clock live OUTSIDE the
    digest (they are measurements, not schedule).

Two scenario kinds: ``inproc`` (InProcessCluster over the loopback bus —
the tier-1 smoke matrix; seconds per scenario) and ``process`` (real
replica subprocesses via BftTestNetwork with SIGSTOP/SIGKILL and the
per-link fault plane — the full matrix, run by ``bench_chaos.py``).

Recovery invariants asserted by every scenario that crashes something:
exactly-once replay (no double-applied request), no ledger divergence
(all live replicas converge on the same state), and re-convergence
within the scenario's time budget.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

DEFAULT_SEED = 20260803

# ----------------------------------------------------------------------
# event log + context
# ----------------------------------------------------------------------


class EventLog:
    """Append-only schedule record. Only *scheduled* facts belong here
    (injected faults, seeded draws, logical step order) — never
    wall-clock readings or measured outcomes, which would break the
    replay-digest contract."""

    def __init__(self) -> None:
        self.events: List[dict] = []

    def append(self, scenario: str, action: str, **params) -> None:
        self.events.append({"i": len(self.events), "scenario": scenario,
                            "action": action, **params})

    def digest(self) -> str:
        blob = json.dumps(self.events, sort_keys=True,
                          separators=(",", ":")).encode()
        return hashlib.sha256(blob).hexdigest()


def sub_seed(master: int, name: str) -> int:
    h = hashlib.sha256(f"{master}:{name}".encode()).digest()
    return int.from_bytes(h[:8], "big")


class ScenarioContext:
    """One scenario's handle: its derived RNG, its slice of the event
    log, a scratch dir, and polling helpers."""

    def __init__(self, name: str, master_seed: int, log: EventLog,
                 tmp_root: str) -> None:
        import random
        self.name = name
        self.master_seed = master_seed
        self.rng = random.Random(sub_seed(master_seed, name))
        self._log = log
        self._tmp_root = tmp_root
        self._tmpdir: Optional[str] = None

    # ---- schedule (digested) ----
    def event(self, action: str, **params) -> None:
        self._log.append(self.name, action, **params)

    def randint(self, label: str, a: int, b: int) -> int:
        v = self.rng.randint(a, b)
        self.event("draw", label=label, value=v)
        return v

    def choice(self, label: str, seq):
        v = self.rng.choice(list(seq))
        self.event("draw", label=label, value=v)
        return v

    def cluster_seed(self) -> bytes:
        return f"chaos-{self.name}-{self.master_seed}".encode()

    # ---- scratch ----
    @property
    def tmpdir(self) -> str:
        if self._tmpdir is None:
            self._tmpdir = os.path.join(self._tmp_root,
                                        self.name.replace("/", "_"))
            os.makedirs(self._tmpdir, exist_ok=True)
        return self._tmpdir

    # ---- measurement (NOT digested) ----
    @staticmethod
    def wait_until(pred: Callable[[], bool], timeout: float,
                   poll: float = 0.05, what: str = "condition") -> float:
        """Poll until pred() is truthy; returns elapsed seconds. Raises
        AssertionError on timeout (the scenario's red verdict)."""
        t0 = time.monotonic()
        deadline = t0 + timeout
        while time.monotonic() < deadline:
            if pred():
                return time.monotonic() - t0
            time.sleep(poll)
        raise AssertionError(f"{what} not reached within {timeout:.0f}s")


# ----------------------------------------------------------------------
# scenario specs + campaign runner
# ----------------------------------------------------------------------


@dataclass
class ScenarioSpec:
    name: str
    fn: Callable[[ScenarioContext], dict]
    kind: str                       # "inproc" | "process"
    time_budget_s: float
    tags: tuple = ()


@dataclass
class ScenarioVerdict:
    name: str
    ok: bool
    duration_s: float
    time_budget_s: float
    stats: dict = field(default_factory=dict)
    error: str = ""
    # flight-recorder artifact captured at the moment of a red verdict
    # (rings + kernel profile + slot timings): the timeline that led to
    # the failure rides the bug report, not just the assertion text
    flight_dump: str = ""

    def as_dict(self) -> dict:
        out = {"name": self.name, "ok": self.ok,
               "duration_s": round(self.duration_s, 3),
               "time_budget_s": self.time_budget_s,
               "stats": self.stats, "error": self.error}
        if self.flight_dump:
            out["flight_dump"] = self.flight_dump
        return out


class ChaosCampaign:
    def __init__(self, seed: int = DEFAULT_SEED,
                 specs: Optional[List[ScenarioSpec]] = None,
                 keep_tmp: bool = False) -> None:
        self.seed = seed
        self.specs = specs if specs is not None else smoke_matrix()
        self.keep_tmp = keep_tmp

    def run(self) -> dict:
        log = EventLog()
        verdicts: List[ScenarioVerdict] = []
        tmp_root = tempfile.mkdtemp(prefix="tpubft-chaos-")
        try:
            for spec in self.specs:
                ctx = ScenarioContext(spec.name, self.seed, log, tmp_root)
                ctx.event("begin", kind=spec.kind)
                t0 = time.monotonic()
                try:
                    stats = spec.fn(ctx) or {}
                    dt = time.monotonic() - t0
                    ok = dt <= spec.time_budget_s
                    err = ("" if ok else
                           f"over time budget: {dt:.1f}s > "
                           f"{spec.time_budget_s:.0f}s")
                except Exception as e:  # noqa: BLE001 — red verdict
                    dt = time.monotonic() - t0
                    stats, ok = {}, False
                    err = f"{type(e).__name__}: {e}"
                finally:
                    self._cleanup_globals()
                fdump = ""
                if not ok:
                    # red verdict: capture the flight recorder BEFORE
                    # the next scenario overwrites the rings (the dump
                    # is measurement, not schedule — never digested)
                    from tpubft.utils import flight
                    fdump = flight.dump(
                        reason=f"chaos-red-{spec.name}",
                        extra={"error": err}) or ""
                verdicts.append(ScenarioVerdict(
                    spec.name, ok, dt, spec.time_budget_s, stats, err,
                    flight_dump=fdump))
        finally:
            if not self.keep_tmp:
                shutil.rmtree(tmp_root, ignore_errors=True)
        degraded = [v for v in verdicts if v.stats.get("degraded")]
        artifact = {
            "seed": self.seed,
            "scenarios": [v.as_dict() for v in verdicts],
            "passed": sum(1 for v in verdicts if v.ok),
            "failed": sum(1 for v in verdicts if not v.ok),
            "event_log": log.events,
            "event_log_digest": log.digest(),
            "recovery_s": {v.name: v.stats["recovery_s"]
                           for v in verdicts if "recovery_s" in v.stats},
        }
        if degraded:
            # PR 4's convention: a degraded artifact names WHY, so a
            # reader can tell injected degradation from a perf story
            artifact["degraded"] = True
            artifact["probe_error"] = "; ".join(
                v.stats.get("probe_error", v.name) for v in degraded)
        return artifact

    @staticmethod
    def _cleanup_globals() -> None:
        """Process-wide state a scenario may have mutated must never
        leak into the next scenario (or a later test): disarm
        crashpoints, release parked threads, close the breaker."""
        from tpubft.testing import crashpoints as cp
        cp.disarm_all()
        cp.release_parked()
        try:
            from tpubft.ops.dispatch import device_breaker
            device_breaker().reset()
        except Exception:  # noqa: BLE001 — cleanup is best-effort
            pass
        try:
            # the mesh plane is process-wide too: injected chip faults,
            # eviction state, and the shard-count cap must never leak
            # into the next scenario's crypto traffic
            from tpubft.parallel import sharding
            sharding.clear_chip_faults()
            sharding.mesh_manager().reset()
        except Exception:  # noqa: BLE001 — cleanup is best-effort
            pass
        try:
            # the autotuner's ECDSA crossover override is process-wide
            # (all replicas share the device): a scenario whose
            # controllers moved it must not leak tuned routing into
            # the next scenario's clusters
            from tpubft.crypto import tpu
            tpu.set_ecdsa_crossover(None)
        except Exception:  # noqa: BLE001 — cleanup is best-effort
            pass
        try:
            # the offload pool is process-wide: quarantined helpers,
            # per-helper breaker trips and lease counters from one
            # scenario must not leak into the next one's crypto traffic
            from tpubft.offload.pool import reset_offload_pool
            reset_offload_pool()
        except Exception:  # noqa: BLE001 — cleanup is best-effort
            pass


# ----------------------------------------------------------------------
# smoke matrix (in-process; tier-1 wires this via bench_chaos --smoke)
# ----------------------------------------------------------------------

_FAST_VC = {"view_change_timer_ms": 900}


def _counter_cluster(ctx: ScenarioContext, **kw):
    from tpubft.testing.cluster import InProcessCluster
    kw.setdefault("cfg_overrides", dict(_FAST_VC))
    kw.setdefault("f", 1)
    return InProcessCluster(seed=ctx.cluster_seed(), **kw)


def _persistent_factories(ctx: ScenarioContext):
    from tpubft.apps.counter import PersistentCounterHandler
    from tpubft.consensus.persistent import FilePersistentStorage
    base = ctx.tmpdir

    def storage_factory(r: int):
        return FilePersistentStorage(os.path.join(base, f"r{r}.wal"))

    def handler_factory(r: int):
        return PersistentCounterHandler(os.path.join(base, f"c{r}.state"))

    return storage_factory, handler_factory


def _wait_converged(ctx: ScenarioContext, cluster, expected: int,
                    replicas, timeout: float, what: str) -> float:
    """No-ledger-divergence check for counter clusters: every live
    replica's applied state reaches the same expected value."""
    return ctx.wait_until(
        lambda: all(cluster.handlers[r].value == expected
                    for r in replicas),
        timeout, what=what)


def scenario_wrong_digest_primary(ctx: ScenarioContext) -> dict:
    """Wrong-digest primary (corrupted PrePrepare broadcast): backups
    reject every proposal, view-change away, and the honest quorum
    commits; the byzantine replica still converges as a backup."""
    from tpubft.apps import counter
    amount = ctx.randint("add", 1, 1000)
    ctx.event("byzantine", replica=0, strategy="corrupt-preprepare")
    with _counter_cluster(ctx, byzantine={0: "corrupt-preprepare"}) \
            as cluster:
        cl = cluster.client()
        t0 = time.monotonic()
        reply = cl.send_write(counter.encode_add(amount), timeout_ms=30000)
        recovery = time.monotonic() - t0
        assert counter.decode_reply(reply) == amount
        for r in (1, 2, 3):
            assert cluster.replicas[r].view >= 1, \
                f"replica {r} never left the corrupt primary's view"
        _wait_converged(ctx, cluster, amount, (1, 2, 3), 15,
                        "honest replicas converge")
    return {"recovery_s": round(recovery, 3)}


def scenario_equivocating_primary(ctx: ScenarioContext) -> dict:
    """Truly equivocating primary (both forks validly signed): the
    backups split across two digests, neither can commit, and the
    view change must resolve ONE fork deterministically — the cluster
    commits exactly once, never both forks."""
    from tpubft.apps import counter
    amount = ctx.randint("add", 1, 1000)
    ctx.event("byzantine", replica=0, strategy="equivocate")
    with _counter_cluster(ctx, byzantine={0: "equivocate"}) as cluster:
        cl = cluster.client()
        t0 = time.monotonic()
        reply = cl.send_write(counter.encode_add(amount), timeout_ms=45000)
        recovery = time.monotonic() - t0
        # exactly-once across the fork: the counter reflects ONE apply
        assert counter.decode_reply(reply) == amount
        for r in (1, 2, 3):
            assert cluster.replicas[r].view >= 1, \
                f"replica {r} never left the equivocating primary's view"
        _wait_converged(ctx, cluster, amount, (1, 2, 3), 15,
                        "honest replicas converge on one fork")
    return {"recovery_s": round(recovery, 3)}


def scenario_partition_heal(ctx: ScenarioContext) -> dict:
    """Asymmetric backup partition (2→3 dropped, 3→2 flows): liveness
    must not suffer at all; after heal everyone converges."""
    from tpubft.apps import counter
    frm, to = 2, 3
    ctx.event("partition", frm=frm, to=to, mode="asymmetric")
    healed = threading.Event()

    def drop(s, d, data):
        if not healed.is_set() and s == frm and d == to:
            return None
        return data

    with _counter_cluster(ctx) as cluster:
        cluster.bus.add_hook(drop)
        cl = cluster.client()
        total = 0
        n_writes = ctx.randint("writes", 3, 5)
        for i in range(n_writes):
            delta = ctx.randint(f"add{i}", 1, 50)
            total += delta
            reply = cl.send_write(counter.encode_add(delta),
                                  timeout_ms=20000)
            assert counter.decode_reply(reply) == total, \
                "ordering wedged under a one-way link cut"
        ctx.event("heal", frm=frm, to=to)
        healed.set()
        t0 = time.monotonic()
        _wait_converged(ctx, cluster, total, range(cluster.n), 20,
                        "all replicas converge after heal")
        recovery = time.monotonic() - t0
    return {"recovery_s": round(recovery, 3), "writes": n_writes}


def scenario_breaker_viewchange(ctx: ScenarioContext) -> dict:
    """COMPOUND: the device circuit breaker trips (all replicas of the
    process share the device, PR 5) and the primary dies while the
    plane is degraded — the view change must complete on the scalar
    fallback and ordering must resume, still degraded."""
    from tpubft.apps import counter
    from tpubft.ops.dispatch import device_breaker
    from tpubft.utils.breaker import CLOSED
    b = device_breaker()
    with _counter_cluster(ctx) as cluster:
        cl = cluster.client()
        assert counter.decode_reply(
            cl.send_write(counter.encode_add(3),
                          timeout_ms=30000)) == 3
        ctx.event("breaker_trip", threshold=b.failure_threshold)
        for _ in range(b.failure_threshold):
            b.record_failure(kind="chaos", cause="injected")
        assert b.state != CLOSED, "breaker did not trip"
        ctx.event("kill_primary", replica=0)
        cluster.kill(0)
        t0 = time.monotonic()
        reply = cl.send_write(counter.encode_add(4), timeout_ms=30000)
        recovery = time.monotonic() - t0
        assert counter.decode_reply(reply) == 7
        assert b.state != CLOSED, \
            "breaker silently closed without a probe verdict"
        for r in (1, 2, 3):
            assert cluster.replicas[r].view >= 1
        _wait_converged(ctx, cluster, 7, (1, 2, 3), 15,
                        "survivors converge while degraded")
        trips = b.trips
    return {"recovery_s": round(recovery, 3), "degraded": True,
            "breaker_trips": trips,
            "probe_error": "device breaker tripped by chaos injection "
                           "(%d consecutive failures)" % b.failure_threshold}


def scenario_fused_flush_bad_share(ctx: ScenarioContext) -> dict:
    """Byzantine shares inside fused combine flushes (ISSUE 11): a
    backup corrupts every threshold share it sends while pipelined load
    keeps several slots per flush. Each poisoned combine must fail ONLY
    its own slot (bad-share identification drops the byzantine share
    and the honest 2f+c+1 re-combine lands); sibling slots in the same
    batch commit on schedule, no view change, no divergence."""
    from tpubft.apps import counter
    byz = ctx.choice("byz", (1, 2, 3))
    ctx.event("byzantine", replica=byz, strategy="corrupt-shares")
    n_per_client = 4
    deltas = [[ctx.randint(f"add{c}_{i}", 1, 50)
               for i in range(n_per_client)] for c in (0, 1)]
    with _counter_cluster(ctx, byzantine={byz: "corrupt-shares"},
                          num_clients=2) as cluster:
        # pipelined writers: two clients in parallel so combine flushes
        # carry sibling slots alongside the poisoned shares
        errs = []

        def drive(idx: int) -> None:
            cl = cluster.client(idx)
            try:
                for d in deltas[idx]:
                    cl.send_write(counter.encode_add(d),
                                  timeout_ms=30000)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        t0 = time.monotonic()
        threads = [threading.Thread(target=drive, args=(c,))
                   for c in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, f"writes failed under byzantine shares: {errs}"
        total = sum(sum(ds) for ds in deltas)
        recovery = time.monotonic() - t0
        _wait_converged(ctx, cluster, total,
                        [r for r in range(cluster.n) if r != byz], 20,
                        "honest replicas converge despite poisoned "
                        "shares in every flush")
        # sibling-slot schedule: ordering never needed a view change —
        # bad-share identification isolated the byzantine share per
        # slot instead of stalling the pipeline into the VC timer
        for r in range(cluster.n):
            if r != byz:
                assert cluster.replicas[r].view == 0, \
                    f"replica {r} view-changed away under isolated " \
                    f"bad shares"
        # the fused plane was actually exercised on some honest replica
        # (collector roles rotate; at least one honest collector
        # drained flushes)
        batches = sum(cluster.metric(r, "counters", "combine_batches")
                      for r in range(cluster.n) if r != byz)
        assert batches > 0, "fused combine batcher never drained"
    return {"recovery_s": round(recovery, 3),
            "combine_batches": batches}


def scenario_autotune_stability(ctx: ScenarioContext) -> dict:
    """Autotuner control-loop stability (ISSUE 14): a breaker flap plus
    a load step must leave every knob convergent — the degraded rule
    resets tuned knobs to their defaults the moment the breaker opens
    (the controller never fights the degradation plane), tuning resumes
    only after the healthy warmup, and across the whole scenario no
    knob oscillates (bounded direction flips) or leaves its bounds."""
    from tpubft.apps import counter
    from tpubft.ops.dispatch import device_breaker
    from tpubft.utils.breaker import CLOSED
    b = device_breaker()
    # scheduled facts: the operator-style knob nudges the reset must
    # undo, and the load-step deltas
    flush_nudge = ctx.randint("flush_nudge", 600, 1200)
    acc_nudge = ctx.randint("acc_nudge", 2, 6)
    deltas = [[ctx.randint(f"step{c}_{i}", 1, 50) for i in range(4)]
              for c in (0, 1)]
    ctx.event("knob_nudge", combine_flush_us=flush_nudge,
              execution_max_accumulation=acc_nudge)
    ctx.event("breaker_flap", threshold=b.failure_threshold)
    MAX_FLIPS = 4
    with _counter_cluster(ctx, num_clients=2, cfg_overrides={
            "view_change_timer_ms": 2500,
            "autotune_enabled": True,
            "autotune_interval_ms": 40,
            "autotune_cooldown_ms": 80}) as cluster:
        reps = list(cluster.replicas.values())
        assert all(r.tuning is not None for r in reps)
        cl = cluster.client()
        assert counter.decode_reply(
            cl.send_write(counter.encode_add(1), timeout_ms=30000)) == 1
        # operator-style nudges away from the defaults, so the degraded
        # reset has real work to prove
        for r in reps:
            r.tuning.registry.set("combine_flush_us", flush_nudge)
            r.tuning.registry.set("execution_max_accumulation",
                                  acc_nudge)
        # breaker flap: trip OPEN; every controller (all replicas share
        # the process-wide device) must back its knobs off to defaults
        for _ in range(b.failure_threshold):
            b.record_failure(kind="chaos", cause="injected")
        assert b.state != CLOSED, "breaker did not trip"

        def all_reset() -> bool:
            return all(
                r.tuning.registry.get("combine_flush_us")
                == r.cfg.combine_flush_us
                and r.tuning.registry.get("execution_max_accumulation")
                == r.cfg.execution_max_accumulation for r in reps)

        t0 = time.monotonic()
        ctx.wait_until(all_reset, 15,
                       what="degraded reset backs every knob to default")
        reset_s = time.monotonic() - t0
        assert all(r.exec_lane.max_accumulation
                   == r.cfg.execution_max_accumulation for r in reps), \
            "reset reached the registry but not the live actuator"
        b.reset()
        # load step under the restored device: two pipelined writers;
        # the controller may tune, but must not oscillate
        errs: list = []

        def drive(idx: int) -> None:
            c = cluster.client(idx)
            try:
                for d in deltas[idx]:
                    c.send_write(counter.encode_add(d),
                                 timeout_ms=30000)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        threads = [threading.Thread(target=drive, args=(c,))
                   for c in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, f"load step failed: {errs}"
        total = 1 + sum(sum(ds) for ds in deltas)
        _wait_converged(ctx, cluster, total, range(cluster.n), 20,
                        "cluster converges through the flap + step")
        # stability: bounded direction flips per knob, values in bounds
        worst_flips = 0
        steps = resets = 0
        for r in reps:
            snap = r.tuning.registry.snapshot()
            for name, k in snap.items():
                assert k["lo"] <= k["value"] <= k["hi"], \
                    f"{name} out of bounds: {k}"
                worst_flips = max(worst_flips, k["direction_flips"])
            assert worst_flips <= MAX_FLIPS, \
                f"knob oscillation on replica {r.id}: {snap}"
            steps += r.tuning.m_steps.value
            resets += r.tuning.m_resets.value
        assert resets >= cluster.n, \
            "not every controller observed the degraded episode"
    return {"recovery_s": round(reset_s, 3),
            "tune_steps": steps, "reset_episodes": resets,
            "max_direction_flips": worst_flips}


def scenario_mesh_chip_fault_flood(ctx: ScenarioContext) -> dict:
    """Multi-chip crypto-plane chaos (ISSUE 16): one mesh chip dies in
    the middle of an ed25519 verification flood. The chip's own breaker
    (`device.chip<N>`) must evict exactly that chip and rebalance the
    flood over the survivors — the plane stays BATCHED (the GLOBAL
    device breaker never trips, so nothing falls back to scalar) and no
    verdict in the flood is dropped or flipped. After the chip heals,
    the cooldown probe re-admits it and the full-width plane verifies
    the same flood byte-identically."""
    import numpy as np
    from tpubft.crypto import cpu
    from tpubft.ops import dispatch
    from tpubft.ops import ed25519 as ops_ed25519
    from tpubft.parallel import sharding
    from tpubft.utils.breaker import CLOSED

    mgr = dispatch.crypto_mesh()
    mgr.reset()
    sharding.clear_chip_faults()
    full = mgr.device_count()
    if full < 2:
        # single-chip host: there is no mesh to degrade — report the
        # run degraded (PR 4's artifact convention) instead of going
        # vacuously green on an unexercised plane
        ctx.event("mesh_unavailable", devices=full)
        return {"recovery_s": 0.0, "degraded": True,
                "probe_error": "single-chip host: mesh plane "
                               "unavailable (%d device)" % full}
    # flood schedule: forged signatures every `stride` items, so every
    # shard of every width carries both valid and forged lanes
    stride = ctx.randint("forge_stride", 3, 9)
    n_batches = ctx.randint("flood_batches", 3, 5)
    n = 64
    signer = cpu.Ed25519Signer.generate(seed=ctx.cluster_seed())
    pk = signer.public_bytes()
    items = []
    for i in range(n):
        m = b"flood-%d" % i
        sig = signer.sign(m)
        if i % stride == 0:
            sig = sig[:4] + bytes([sig[4] ^ 0xFF]) + sig[5:]
        items.append((m, sig, pk))
    want = [i % stride != 0 for i in range(n)]
    # healthy full-width baseline
    assert dispatch.mesh_plan().n == full, "mesh not at full width"
    assert np.asarray(ops_ed25519.verify_batch(items)).tolist() == want
    victim = ctx.choice("victim",
                        [d.id for d in dispatch.mesh_plan().devices])
    ctx.event("chip_fault", device=victim)
    sharding.inject_chip_fault(victim)
    t0 = time.monotonic()
    verdicts = [np.asarray(ops_ed25519.verify_batch(items)).tolist()
                for _ in range(n_batches)]
    recovery = time.monotonic() - t0
    assert all(v == want for v in verdicts), \
        "flood dropped/flipped verdicts across the eviction"
    snap = mgr.snapshot()
    assert snap["evicted"] == [victim], snap
    assert dispatch.mesh_plan().n == full - 1, \
        "plane did not rebalance onto the survivors"
    assert dispatch.device_breaker().state == CLOSED, \
        "global breaker tripped — the plane fell back to scalar"
    # chip heals: the cooldown probe must re-admit it into the plan
    ctx.event("heal", device=victim)
    sharding.clear_chip_faults()
    b = mgr.chip_breaker(victim)
    b.configure(cooldown_s=0.05)
    try:
        ctx.wait_until(lambda: dispatch.mesh_plan().n == full, 10,
                       what="healed chip re-admitted after cooldown")
    finally:
        b.configure(cooldown_s=2.0)
    assert mgr.snapshot()["readmits"] >= 1
    assert np.asarray(ops_ed25519.verify_batch(items)).tolist() == want
    return {"recovery_s": round(recovery, 3),
            "rebalance_ms": snap["last_rebalance_ms"],
            "flood_batches": n_batches,
            "shards_after_eviction": full - 1}


def scenario_offload_byzantine_helper_flood(ctx: ScenarioContext) -> dict:
    """Verified crypto-offload under a lying helper (ISSUE 20): a
    4-replica TPU-backend cluster leases its threshold combines to two
    helpers; mid-way through a 2-client write flood one helper turns
    Byzantine (wrong-but-on-curve points — the strongest lie, it passes
    every shape check). The on-replica soundness check must catch every
    lie BEFORE it can influence a verdict: no write fails, no replica
    view-changes or diverges, the liar is breaker-evicted into
    quarantine (no auto re-admission), and the flood continues on the
    honest helper + local fallback. Replayed with the same seed the
    event-log digest is byte-identical."""
    from tpubft.apps import counter
    from tpubft.offload.helper import HelperServer
    from tpubft.offload.pool import InprocHelper, get_offload_pool
    from tpubft.utils.breaker import get_breaker

    pool = get_offload_pool()
    pool.reset()
    honest = HelperServer("h-honest", strategy="honest")
    liar = HelperServer("h-liar", strategy="honest")   # flips mid-flood
    pool.add_helper(InprocHelper("h-honest", honest))
    pool.add_helper(InprocHelper("h-liar", liar))
    n_per_phase = 2
    deltas = [[ctx.randint(f"add{c}_{i}", 1, 50)
               for i in range(2 * n_per_phase)] for c in (0, 1)]
    ctx.event("helpers", roster=["h-honest", "h-liar"])
    overrides = {"crypto_backend": "tpu", "device_min_verify_batch": 1,
                 # adaptive resolves to multisig-ed25519 at n=4 — pin
                 # the BLS threshold system or there is nothing to lease
                 "threshold_scheme": "threshold-bls",
                 "offload_enabled": True,
                 # generous lease deadline: XLA-CPU pairing checks on a
                 # shared core can take >200ms — a deadline miss would
                 # reclassify the LIAR as merely sick
                 "offload_lease_timeout_ms": 30000,
                 "view_change_timer_ms": 30000}
    with _counter_cluster(ctx, num_clients=2,
                          cfg_overrides=overrides) as cluster:
        errs: list = []

        def drive(idx: int, lo: int, hi: int) -> None:
            cl = cluster.client(idx)
            try:
                for d in deltas[idx][lo:hi]:
                    cl.send_write(counter.encode_add(d),
                                  timeout_ms=60000)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        def flood(lo: int, hi: int) -> None:
            threads = [threading.Thread(target=drive, args=(c, lo, hi))
                       for c in (0, 1)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

        t0 = time.monotonic()
        flood(0, n_per_phase)                 # phase 1: both honest
        ctx.event("helper_flip", helper="h-liar",
                  strategy="wrong-on-curve")
        liar.set_strategy("wrong-on-curve")
        flood(n_per_phase, 2 * n_per_phase)   # phase 2: liar active
        recovery = time.monotonic() - t0
        assert not errs, f"writes failed under a lying helper: {errs}"
        total = sum(sum(ds) for ds in deltas)
        _wait_converged(ctx, cluster, total, range(cluster.n), 30,
                        "all replicas converge past the lying helper")
        # no wrong verdict ever surfaced: ordering never needed a view
        # change — every lie was caught by the soundness check and the
        # combine re-ran locally inside the same flush
        for r in range(cluster.n):
            assert cluster.replicas[r].view == 0, \
                f"replica {r} view-changed away under a lying helper"
        snap = pool.snapshot()
        assert snap["quarantined"] == ["h-liar"], snap
        assert snap["counters"]["helper_evicted"] == 1, snap
        assert snap["counters"]["lease_rejected"] >= 1, snap
        # the tier kept working: verified leases continued on the
        # honest helper (phase 1 at minimum, phase 2 once the liar was
        # out of rotation)
        assert snap["counters"]["lease_verified"] >= 1, snap
        assert get_breaker("helper.h-liar").state == "open", \
            "liar's breaker must hold OPEN (no cooldown re-admission)"
        assert get_breaker("helper.h-honest").state == "closed", \
            "honest helper must stay admitted"
        rejected = snap["counters"]["lease_rejected"]
        verified = snap["counters"]["lease_verified"]
    return {"recovery_s": round(recovery, 3),
            "leases_verified": verified,
            "leases_rejected": rejected}


def scenario_crash_restart_replay(ctx: ScenarioContext) -> dict:
    """Plain crash recovery: a backup restarts from its WAL and replays
    to the cluster's state exactly once."""
    from tpubft.apps import counter
    sf, hf = _persistent_factories(ctx)
    victim = ctx.choice("victim", (1, 2, 3))
    with _counter_cluster(ctx, storage_factory=sf,
                          handler_factory=hf) as cluster:
        cl = cluster.client()
        total = 0
        for i in range(2):
            delta = ctx.randint(f"add{i}", 1, 50)
            total += delta
            assert counter.decode_reply(
                cl.send_write(counter.encode_add(delta),
                              timeout_ms=30000)) == total
        ctx.wait_until(lambda: cluster.replicas[victim].last_executed >= 1,
                       10, what="victim executed a prefix")
        ctx.event("crash_restart", replica=victim)
        t0 = time.monotonic()
        rep = cluster.restart(victim)
        assert rep.last_executed >= 1, "WAL recovery lost the prefix"
        delta = ctx.randint("add_post", 1, 50)
        total += delta
        assert counter.decode_reply(
            cl.send_write(counter.encode_add(delta),
                          timeout_ms=30000)) == total
        _wait_converged(ctx, cluster, total, range(cluster.n), 20,
                        "restarted replica replays exactly once")
        recovery = time.monotonic() - t0
    return {"recovery_s": round(recovery, 3)}


def scenario_spec_abort_equivocation(ctx: ScenarioContext) -> dict:
    """Equivocating primary against a ledger: replica 0 sends two
    validly-signed forks of every PrePrepare, so honest backups accept
    conflicting bodies that can never reach a commit quorum. The view
    change votes the primary out and each slot executes from the body
    committed in the new view: exactly one write lands in the ledger,
    the reply ring holds only the committed execution's reply, and the
    honest replicas converge byte-identically."""
    from tpubft.apps import skvbc
    from tpubft.kvbc import KeyValueBlockchain
    from tpubft.storage.memorydb import MemoryDB
    from tpubft.testing.cluster import InProcessCluster
    dbs: dict = {}

    def handler_factory(r):
        db = dbs.setdefault(r, MemoryDB())
        return skvbc.SkvbcHandler(
            KeyValueBlockchain(db, use_device_hashing=False))

    ctx.event("byzantine", replica=0, strategy="equivocate")
    key = b"spec-%d" % ctx.randint("key", 1, 999)
    with InProcessCluster(f=1, seed=ctx.cluster_seed(),
                          cfg_overrides=dict(_FAST_VC),
                          handler_factory=handler_factory,
                          byzantine={0: "equivocate"}) as cluster:
        kv = skvbc.SkvbcClient(cluster.client(0))
        t0 = time.monotonic()
        r = kv.write([(key, b"committed")], timeout_ms=60000)
        recovery = time.monotonic() - t0
        assert r.success, "cluster never committed past the equivocation"
        for i in (1, 2, 3):
            assert cluster.replicas[i].view >= 1, \
                f"replica {i} never left the equivocating primary's view"
        # no forked body reached the ledger: each honest chain is exactly
        # the committed history (1 block for the 1 committed write — a
        # fork that executed would add a block or skew the digest), and
        # they are byte-identical
        ctx.wait_until(
            lambda: len({cluster.handlers[i].blockchain.state_digest()
                         for i in (1, 2, 3)}) == 1
            and all(cluster.handlers[i].blockchain.last_block_id == 1
                    for i in (1, 2, 3)),
            20, what="honest ledgers converge on the committed fork")
        # the reply ring holds only the committed execution's reply
        cid = cluster.client(0).cfg.client_id
        for i in (1, 2, 3):
            rep = cluster.replicas[i]
            info = rep.clients._clients[cid]
            assert info.replies, f"replica {i} lost the reply record"
            assert all(rep.clients.was_executed(cid, s)
                       for s in info.replies)
        val = kv.read([key])
        assert val == {key: b"committed"}, val
    return {"recovery_s": round(recovery, 3)}


def scenario_optimistic_reply_cert_blackout(ctx: ScenarioContext) -> dict:
    """ISSUE 18: equivocating primary + a full commit-share/certificate
    blackout under `optimistic_replies`. The optimistic plane serves
    clients from f+1 matching INDIVIDUALLY-SIGNED replies — but a
    release still requires a structurally-valid commit certificate, so
    with every commit-path message suppressed no replica executes and a
    strict client must time out rather than accept anything weaker than
    its f+1 signed quorum. After the heal the cluster view-changes away
    from the equivocator, the write commits, the honest replicas
    converge byte-identically, and the optimistic plane re-engages
    (releases fire on the new view's certificates)."""
    from tpubft.apps import skvbc
    from tpubft.bftclient.client import TimeoutError_
    from tpubft.consensus import messages as m
    from tpubft.kvbc import KeyValueBlockchain
    from tpubft.storage.memorydb import MemoryDB
    from tpubft.testing.cluster import InProcessCluster
    dbs: dict = {}

    def handler_factory(r):
        db = dbs.setdefault(r, MemoryDB())
        return skvbc.SkvbcHandler(
            KeyValueBlockchain(db, use_device_hashing=False))

    # every message that can carry commit shares or a formed commit
    # certificate — slow path, fast path, and the PR 17 aggregation
    # overlay (message code = first two LE header bytes)
    cert_codes = {int(c) for c in (
        m.MsgCode.CommitPartial, m.MsgCode.CommitFull,
        m.MsgCode.PartialCommitProof, m.MsgCode.FullCommitProof,
        m.MsgCode.AggregateShare)}
    healed = threading.Event()

    def blackout(s, d, data):
        if not healed.is_set() \
                and int.from_bytes(data[:2], "little") in cert_codes:
            return None
        return data

    cfg = dict(_FAST_VC)
    cfg["optimistic_replies"] = True
    ctx.event("byzantine", replica=0, strategy="equivocate")
    ctx.event("blackout", what="commit-shares+certs")
    key = b"lit-%d" % ctx.randint("key", 1, 999)
    with InProcessCluster(f=1, seed=ctx.cluster_seed(),
                          cfg_overrides=cfg,
                          handler_factory=handler_factory,
                          byzantine={0: "equivocate"}) as cluster:
        cluster.bus.add_hook(blackout)
        kv = skvbc.SkvbcClient(
            cluster.client(0, require_signed_replies=True))
        # dark phase: certs cannot form, so nothing executes and no
        # signed reply exists anywhere — acceptance on anything short of
        # f+1 matching signatures would be the bug this scenario hunts
        try:
            kv.write([(b"dark", b"0")], timeout_ms=2500)
            raise AssertionError(
                "client accepted a write during the cert blackout")
        except TimeoutError_:
            pass
        for i in (1, 2, 3):
            assert cluster.replicas[i].last_executed == 0, (
                f"replica {i} executed without a commit certificate "
                "during the blackout")
            assert cluster.metric(
                i, "counters", "optimistic_releases") == 0, (
                f"replica {i} optimistically released a slot with the "
                "cert plane dark")
        ctx.event("heal")
        healed.set()
        t0 = time.monotonic()
        r = kv.write([(key, b"committed")], timeout_ms=60000)
        recovery = time.monotonic() - t0
        assert r.success, "cluster never recovered from the blackout"
        for i in (1, 2, 3):
            assert cluster.replicas[i].view >= 1, \
                f"replica {i} never left the equivocating primary's view"
        # the optimistic plane re-engages on the new view's certs
        ctx.wait_until(
            lambda: sum(cluster.metric(i, "counters",
                                       "optimistic_releases")
                        for i in (1, 2, 3)) > 0,
            15, what="optimistic releases after heal")
        # honest replicas converge byte-identically (the dark write may
        # or may not have survived in queues — they must only AGREE)
        ctx.wait_until(
            lambda: len({(cluster.handlers[i].blockchain.last_block_id,
                          cluster.handlers[i].blockchain.state_digest())
                         for i in (1, 2, 3)}) == 1,
            20, what="honest ledgers converge after the blackout")
        val = kv.read([key])
        assert val == {key: b"committed"}, val
        releases = sum(cluster.metric(i, "counters",
                                      "optimistic_releases")
                       for i in (1, 2, 3))
    return {"recovery_s": round(recovery, 3), "opt_releases": releases}


def scenario_crashpoint_exec_post_apply(ctx: ScenarioContext) -> dict:
    """Crashpoint drill 1 — exec.post_apply: a replica dies after the
    run's durable apply but before watermark/bookkeeping. Recovery from
    its WAL must replay the committed suffix EXACTLY ONCE (the durable
    at-most-once state dedups) and reach the cluster's value."""
    from tpubft.apps import counter
    from tpubft.comm.loopback import LoopbackBus
    from tpubft.consensus.persistent import FilePersistentStorage
    from tpubft.consensus.replica import Replica
    from tpubft.testing import crashpoints as cp
    from tpubft.utils.config import ReplicaConfig
    sf, hf = _persistent_factories(ctx)
    victim = 2
    hit = threading.Event()

    def crash_here() -> None:
        hit.set()
        cp.park()                 # SIGKILL analog: not one more statement

    with _counter_cluster(ctx, storage_factory=sf,
                          handler_factory=hf) as cluster:
        cl = cluster.client()
        first = ctx.randint("add1", 1, 50)
        assert counter.decode_reply(
            cl.send_write(counter.encode_add(first),
                          timeout_ms=30000)) == first
        ctx.wait_until(lambda: cluster.replicas[victim].last_executed >= 1,
                       10, what="victim applied the baseline")
        ctx.event("arm_crashpoint", point="exec.post_apply",
                  replica=victim)
        cp.arm("exec.post_apply", rid=victim, action=crash_here)
        second = ctx.randint("add2", 1, 50)
        total = first + second
        assert counter.decode_reply(
            cl.send_write(counter.encode_add(second),
                          timeout_ms=20000)) == total
        ctx.wait_until(hit.is_set, 15, what="crashpoint fired")
        ctx.event("crashed", replica=victim, point="exec.post_apply")
        # ---- recovery: restore the victim standalone from its durable
        # state (WAL + counter file + surviving reserved pages): never
        # started, so the committed-suffix replay happens in __init__ —
        # and assert it applied exactly once ----
        t0 = time.monotonic()
        cfg = ReplicaConfig(replica_id=victim, f_val=1,
                            num_of_client_proxies=2, **_FAST_VC)
        recovered = Replica(
            cfg, cluster.keys.for_node(victim),
            LoopbackBus().create(victim),
            hf(victim),
            storage=FilePersistentStorage(
                os.path.join(ctx.tmpdir, f"r{victim}.wal")),
            reserved_pages=cluster._pages_dbs[victim])
        recovery = time.monotonic() - t0
        assert recovered.handler.value == total, (
            f"replay divergence: recovered value "
            f"{recovered.handler.value} != {total} (double-applied?)")
        assert recovered.last_executed >= 2, \
            "recovery did not replay the committed suffix"
        # release the parked lane thread BEFORE cluster teardown so the
        # victim's stop() doesn't eat its full join timeout
        cp.disarm_all()
        cp.release_parked()
    return {"recovery_s": round(recovery, 3),
            "recovered_value": total}


def scenario_group_commit_crash(ctx: ScenarioContext) -> dict:
    """Crashpoint drill — dur.group_fsync (ISSUE 15): a replica's
    durability io thread dies between the group's apply and its fsync —
    runs executed, batch maybe-on-disk, watermark never published, no
    reply sent, `last_executed` never advanced. The frozen replica must
    NOT advance its watermark past the unsynced group (a reply can
    never precede its group's fsync), and recovery from the on-disk
    state must replay the committed suffix EXACTLY ONCE (the reserved-
    pages at-most-once state dedups whatever did land) — no double
    apply, no ledger divergence, `last_executed` monotone across the
    crash-restart."""
    from tpubft.apps import counter
    from tpubft.comm.loopback import LoopbackBus
    from tpubft.consensus.persistent import FilePersistentStorage
    from tpubft.consensus.replica import Replica
    from tpubft.testing import crashpoints as cp
    from tpubft.utils.config import ReplicaConfig
    sf, hf = _persistent_factories(ctx)
    victim = ctx.choice("victim", (1, 2, 3))
    hit = threading.Event()

    def crash_here() -> None:
        hit.set()
        cp.park()                 # SIGKILL analog: not one more statement

    with _counter_cluster(ctx, storage_factory=sf,
                          handler_factory=hf) as cluster:
        cl = cluster.client()
        first = ctx.randint("add1", 1, 50)
        assert counter.decode_reply(
            cl.send_write(counter.encode_add(first),
                          timeout_ms=30000)) == first
        ctx.wait_until(lambda: cluster.replicas[victim].last_executed >= 1,
                       10, what="victim's first group landed")
        frozen_at = cluster.replicas[victim].last_executed
        ctx.event("arm_crashpoint", point="dur.group_fsync",
                  replica=victim)
        cp.arm("dur.group_fsync", rid=victim, action=crash_here)
        second = ctx.randint("add2", 1, 50)
        total = first + second
        assert counter.decode_reply(
            cl.send_write(counter.encode_add(second),
                          timeout_ms=20000)) == total
        ctx.wait_until(hit.is_set, 15, what="crashpoint fired")
        ctx.event("crashed", replica=victim, point="dur.group_fsync")
        # the unsynced group must never surface: the frozen replica's
        # watermark (and so last_executed) stays where durability
        # stopped, while the healthy quorum acked the write
        assert cluster.replicas[victim].last_executed == frozen_at, (
            "last_executed advanced past a group that never fsynced — "
            "a reply could have preceded its group's durability")
        # ---- recovery: restore the victim standalone from its durable
        # state (WAL + counter file + surviving reserved pages): never
        # started, so the committed-suffix replay happens in __init__ ----
        t0 = time.monotonic()
        cfg = ReplicaConfig(replica_id=victim, f_val=1,
                            num_of_client_proxies=2, **_FAST_VC)
        recovered = Replica(
            cfg, cluster.keys.for_node(victim),
            LoopbackBus().create(victim),
            hf(victim),
            storage=FilePersistentStorage(
                os.path.join(ctx.tmpdir, f"r{victim}.wal")),
            reserved_pages=cluster._pages_dbs[victim])
        recovery = time.monotonic() - t0
        assert recovered.handler.value == total, (
            f"replay divergence after the group-fsync crash: recovered "
            f"value {recovered.handler.value} != {total} "
            f"(double-applied?)")
        assert recovered.last_executed >= 2, \
            "recovery did not replay the committed suffix"
        assert recovered.last_executed >= frozen_at, \
            "last_executed regressed across the crash-restart"
        cp.disarm_all()
        cp.release_parked()
    return {"recovery_s": round(recovery, 3), "recovered_value": total,
            "frozen_at": frozen_at}


def scenario_crashpoint_vc_persist(ctx: ScenarioContext) -> dict:
    """Crashpoint drill 2 — vc.persist: a replica dies after persisting
    its view-change intent but BEFORE broadcasting the ViewChangeMsg.
    With the old primary dead, the view-change quorum NEEDS this
    replica: its restart must resume the change from storage and
    retransmit (the pending_view persistence + _resume_view_change
    path), or the cluster wedges forever."""
    from tpubft.apps import counter
    from tpubft.testing import crashpoints as cp
    sf, hf = _persistent_factories(ctx)
    victim = 2
    hit = threading.Event()

    def crash_here() -> None:
        hit.set()
        cp.park()

    with _counter_cluster(ctx, storage_factory=sf,
                          handler_factory=hf) as cluster:
        cl = cluster.client()
        first = ctx.randint("add1", 1, 50)
        assert counter.decode_reply(
            cl.send_write(counter.encode_add(first),
                          timeout_ms=30000)) == first
        ctx.event("arm_crashpoint", point="vc.persist", replica=victim)
        cp.arm("vc.persist", rid=victim, action=crash_here)
        ctx.event("kill_primary", replica=0)
        cluster.kill(0)
        # complaints (and thus the view change the victim parks inside)
        # only fire while work is in flight — drive a write in the
        # background; it can only complete after the victim recovers,
        # because the view-change quorum (2f+1 = 3) needs all three
        # survivors and the victim crashes before broadcasting its msg
        second = ctx.randint("add2", 1, 50)
        total = first + second
        box: dict = {}

        def drive() -> None:
            try:
                box["reply"] = cl.send_write(counter.encode_add(second),
                                             timeout_ms=60000)
            except Exception as e:  # noqa: BLE001 — asserted below
                box["err"] = e

        th = threading.Thread(target=drive, daemon=True)
        th.start()
        ctx.wait_until(hit.is_set, 30,
                       what="victim crashed at vc.persist")
        ctx.event("crashed", replica=victim, point="vc.persist")
        old = cluster.replicas[victim]       # parked mid-seam
        ctx.event("crash_restart", replica=victim)
        t0 = time.monotonic()
        cluster.crash(victim)                # recover from WAL, rebind bus
        # the resumed view change must complete: 1, 3 and the recovered
        # victim reach the view-change quorum, view >= 1 activates, and
        # ordering resumes with history intact
        th.join(60)
        recovery = time.monotonic() - t0
        assert not th.is_alive() and "err" not in box, \
            f"driver write failed: {box.get('err', 'timed out')}"
        assert counter.decode_reply(box["reply"]) == total, \
            "cluster never recovered from the mid-view-change crash"
        for r in (1, 2, 3):
            assert cluster.replicas[r].view >= 1, \
                f"replica {r} stuck in view 0"
        _wait_converged(ctx, cluster, total, (1, 2, 3), 20,
                        "recovered replica rejoins the new view")
        # let the abandoned pre-crash instance observe its stop flags
        cp.disarm_all()
        cp.release_parked()
        try:
            old.stop()
        except Exception:  # noqa: BLE001 — it crashed; best-effort
            pass
    return {"recovery_s": round(recovery, 3)}


def scenario_thin_replica_failover(ctx: ScenarioContext) -> dict:
    """Read-tier failover: a thin-replica subscriber streams digest-
    verified updates (every block needs f+1 server agreement) while the
    cluster orders PRE-EXECUTED writes; its DATA server's replica is
    killed mid-stream. The client must rotate to a surviving replica
    and catch up — every committed block delivered exactly once, in
    order, with the committed bytes (no gap, no dup, no divergence)."""
    from tpubft.apps import skvbc
    from tpubft.kvbc import KeyValueBlockchain
    from tpubft.storage.memorydb import MemoryDB
    from tpubft.testing.cluster import InProcessCluster
    from tpubft.thinreplica import ThinReplicaClient

    def handler_factory(_r):
        return skvbc.SkvbcHandler(
            KeyValueBlockchain(MemoryDB(), use_device_hashing=False),
            merkle=True)

    n_pre = ctx.randint("writes_before", 3, 5)
    n_post = ctx.randint("writes_after", 3, 5)
    writes = [(b"k%03d" % i, b"v%d" % ctx.randint(f"val{i}", 1, 999))
              for i in range(n_pre + n_post)]
    victim = 1          # the subscriber's data source; NOT the primary —
    # the scenario isolates read-tier failover from ordering failover
    # (the primary-kill paths have their own scenarios)
    ctx.event("kill_data_server", replica=victim)
    overrides = dict(_FAST_VC, thin_replica_enabled=True,
                     pre_execution_enabled=True)
    with InProcessCluster(f=1, seed=ctx.cluster_seed(),
                          handler_factory=handler_factory,
                          cfg_overrides=overrides) as cluster:
        kv = skvbc.SkvbcClient(cluster.client(0))
        got: List[tuple] = []
        # data source = victim first, survivors as hash servers/fallback
        eps = [("127.0.0.1", cluster.replicas[r].thin_replica.port)
               for r in (victim, 2, 3, 0)]
        trc = ThinReplicaClient(eps, f_val=1)
        trc.STALL_TIMEOUT_S = 1.0
        trc.subscribe(lambda b, kvs: got.append((b, dict(kvs))),
                      start_block=1)
        for k, v in writes[:n_pre]:
            assert kv.write([(k, v)], pre_process=True,
                            timeout_ms=30000).success
        ctx.wait_until(lambda: len(got) >= n_pre, 20,
                       what="subscriber streamed the pre-kill blocks")
        cluster.kill(victim)            # SIGKILL analog: server vanishes
        t0 = time.monotonic()
        for k, v in writes[n_pre:]:
            assert kv.write([(k, v)], pre_process=True,
                            timeout_ms=30000).success
        total = len(writes)
        ctx.wait_until(lambda: len(got) >= total, 30,
                       what="subscriber caught up after data-server kill")
        recovery = time.monotonic() - t0
        trc.stop()
        blocks = [b for b, _ in got]
        assert blocks == list(range(1, total + 1)), \
            f"gap/dup/disorder in the resumed stream: {blocks}"
        for i, (k, v) in enumerate(writes):
            assert got[i][1] == {k: v}, \
                f"divergence at block {i + 1}: {got[i][1]}"
        # the pre-execution plane really carried the writes
        agreed = cluster.metric(0, "counters", "preexec_agreed",
                                component="preexec")
    return {"recovery_s": round(recovery, 3), "blocks": total,
            "preexec_agreed": agreed}


# ----------------------------------------------------------------------
# share-aggregation overlay scenarios (ISSUE 17)
# ----------------------------------------------------------------------


class _WanLatency:
    """WAN latency profile over the loopback bus, modeled on
    bench_st.LatencyNet (deliver-time heap + one scheduler thread): the
    bus hook intercepts replica->replica traffic and re-queues it for
    delayed direct delivery to the destination endpoint — the same tail
    the bus pump runs. Client traffic stays instant, so request
    injection is not part of the profile. Per-pair delays come from a
    caller-supplied (sender, dest) -> seconds function, letting a
    scenario shape regions rather than one flat RTT."""

    def __init__(self, bus, n_replicas: int, delay_fn) -> None:
        import heapq
        self._heapq = heapq
        self._bus = bus
        self._n = n_replicas
        self._delay = delay_fn
        self._q: list = []
        self._cv = threading.Condition()
        self._seq = 0
        self._stop = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="wan-latency")
        self._thread.start()
        bus.add_hook(self._hook)

    def _hook(self, s, d, data):
        if s >= self._n or d >= self._n or self._stop:
            return data                 # clients / teardown: instant
        with self._cv:
            self._seq += 1
            self._heapq.heappush(
                self._q, (time.monotonic() + self._delay(s, d),
                          self._seq, s, d, data))
            self._cv.notify()
        return None

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._stop and (
                        not self._q
                        or self._q[0][0] > time.monotonic()):
                    timeout = (max(self._q[0][0] - time.monotonic(), 1e-4)
                               if self._q else None)
                    self._cv.wait(timeout=timeout)
                if self._stop:
                    return
                _, _, s, d, data = self._heapq.heappop(self._q)
            ep = self._bus._endpoints.get(d)
            if ep is not None:
                ep._deliver(s, data)

    def stop(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify()
        self._thread.join(timeout=5)


def scenario_agg_tree_node_kill(ctx: ScenarioContext) -> dict:
    """Interior aggregator killed mid-flood: the shares its subtree was
    climbing through stop being forwarded, the children's parent
    timeout re-sends them DIRECT to the collector, and the cluster
    converges WITHOUT a view change — liveness under aggregation is
    never worse than the all-to-all path it replaced. The schedule
    (victim draw included) replays digest-identically."""
    from tpubft.apps import counter
    from tpubft.consensus.aggregation import overlay_for
    overrides = dict(share_aggregation="tree", agg_fanout=2,
                     agg_flush_ms=5, agg_parent_timeout_ms=150,
                     fast_path_timeout_ms=50,
                     # long enough that the fallback, not a view
                     # change, is what restores progress
                     view_change_timer_ms=6000)
    with _counter_cluster(ctx, cfg_overrides=overrides) as cluster:
        n = cluster.n
        # the view-0 overlay is deterministic: pick the interior
        # non-root aggregator every replica agrees on
        ov = overlay_for("tree", n, 2, 0, 0, 1, 16)
        victim = next(r for r in ov.order[1:] if ov.is_interior(r))
        ctx.event("kill", replica=victim, role="interior-aggregator")
        cl = cluster.client()
        total = 0
        for i in range(2):              # flood before the kill
            delta = ctx.randint(f"pre{i}", 1, 50)
            total += delta
            reply = cl.send_write(counter.encode_add(delta),
                                  timeout_ms=20000)
            assert counter.decode_reply(reply) == total
        cluster.kill(victim)
        t0 = time.monotonic()
        for i in range(3):              # flood through the dead branch
            delta = ctx.randint(f"post{i}", 1, 50)
            total += delta
            reply = cl.send_write(counter.encode_add(delta),
                                  timeout_ms=30000)
            assert counter.decode_reply(reply) == total
        recovery = time.monotonic() - t0
        live = [r for r in range(n) if r != victim]
        _wait_converged(ctx, cluster, total, live, 15,
                        "fallback path converges")
        for r in live:
            assert cluster.replicas[r].view == 0, \
                f"replica {r} view-changed; fallback should have held"
        fallbacks = sum(cluster.metric(r, "counters", "agg_fallbacks")
                        for r in live)
        assert fallbacks > 0, "no parent-timeout fallback ever fired"
    return {"recovery_s": round(recovery, 3), "victim": victim,
            "fallbacks": fallbacks}


def scenario_agg_wan_latency(ctx: ScenarioContext) -> dict:
    """Large-n two-region WAN profile (intra 2ms, inter 12ms one-way)
    under gossip aggregation with one dead replica forcing the slow
    path: the overlay keeps every node's share fan-in under the
    collector's all-to-all O(n), and commits flow without a view change
    at WAN timescales."""
    from tpubft.apps import counter
    intra_ms, inter_ms = 2, 12
    # parent timeout must clear the WHOLE slow-path slot latency (WAN
    # hops + flush windows + CPU-host BLS combines), not just one hop:
    # the fallback trigger is "slot not prepared/committed yet", so an
    # undersized value collapses the overlay back to all-to-all with
    # duplicate shares on top. 2s is comfortably past a CPU-host slot
    # and still 4x under the view-change timer.
    overrides = dict(share_aggregation="gossip", agg_fanout=3,
                     agg_flush_ms=10, agg_parent_timeout_ms=2000,
                     agg_rotate_seqs=4, fast_path_timeout_ms=80,
                     view_change_timer_ms=8000)
    ctx.event("latency_profile", intra_ms=intra_ms, inter_ms=inter_ms,
              regions=2)
    with _counter_cluster(ctx, f=3, cfg_overrides=overrides) as cluster:
        n = cluster.n                   # 10
        region = {r: r % 2 for r in range(n)}

        def delay(s, d):
            return (intra_ms if region[s] == region[d] else inter_ms) / 1e3

        wan = _WanLatency(cluster.bus, n, delay)
        try:
            victim = n - 1
            ctx.event("kill", replica=victim, role="fast-path-breaker")
            cluster.kill(victim)
            cl = cluster.client()
            total = 0
            for i in range(5):
                delta = ctx.randint(f"add{i}", 1, 50)
                total += delta
                reply = cl.send_write(counter.encode_add(delta),
                                      timeout_ms=45000)
                assert counter.decode_reply(reply) == total
            live = [r for r in range(n) if r != victim]
            _wait_converged(ctx, cluster, total, live, 30,
                            "WAN cluster converges")
            for r in live:
                assert cluster.replicas[r].view == 0
            rcvd = [cluster.metric(r, "counters", "share_msgs_received")
                    for r in live]
            absorbed = cluster.metric(0, "counters",
                                      "agg_partials_absorbed")
            assert absorbed > 0, "root never absorbed a partial"
            # the whole point: no node carries all-to-all fan-in.
            # 5 slots x 2 kinds x (n-2) senders is the collector's
            # un-aggregated load; the busiest node must sit strictly
            # under it even INCLUDING the first-slot fallback burst
            # (the dead replica seats as an interior node in some
            # rotation, so its orphans route direct from slot 2 on)
            assert max(rcvd) < 5 * 2 * (n - 2), \
                f"fan-in {max(rcvd)} not under all-to-all {5*2*(n-2)}"
        finally:
            wan.stop()
    return {"recovery_s": 0.0, "max_fan_in": max(rcvd),
            "collector_fan_in": rcvd[0], "absorbed": absorbed}


def smoke_matrix() -> List[ScenarioSpec]:
    return [
        ScenarioSpec("wrong-digest-primary", scenario_wrong_digest_primary,
                     "inproc", 60, tags=("byzantine", "view-change")),
        ScenarioSpec("equivocating-primary", scenario_equivocating_primary,
                     "inproc", 90, tags=("byzantine", "view-change")),
        ScenarioSpec("partition-heal", scenario_partition_heal,
                     "inproc", 60, tags=("partition",)),
        ScenarioSpec("breaker-viewchange", scenario_breaker_viewchange,
                     "inproc", 60, tags=("compound", "degraded",
                                         "view-change")),
        ScenarioSpec("spec-abort-equivocation",
                     scenario_spec_abort_equivocation,
                     "inproc", 90, tags=("byzantine", "view-change",
                                         "ledger")),
        ScenarioSpec("optimistic-reply-cert-blackout",
                     scenario_optimistic_reply_cert_blackout,
                     "inproc", 120, tags=("byzantine", "view-change",
                                          "optimistic-replies")),
        ScenarioSpec("fused-flush-bad-share", scenario_fused_flush_bad_share,
                     "inproc", 90, tags=("byzantine", "combine")),
        ScenarioSpec("autotune-stability", scenario_autotune_stability,
                     "inproc", 90, tags=("autotune", "degraded",
                                         "compound")),
        ScenarioSpec("mesh-chip-fault-flood", scenario_mesh_chip_fault_flood,
                     # budget sized for a COLD first run: the full- and
                     # survivor-width kernels compile inside the
                     # scenario on a 1-core host (~90s); warm it is <5s
                     "inproc", 240, tags=("mesh", "crypto", "recovery")),
        ScenarioSpec("offload-byzantine-helper-flood",
                     scenario_offload_byzantine_helper_flood,
                     # budget sized for a COLD first run: the TPU-backend
                     # combine/pairing kernels compile inside the
                     # scenario on a 1-core XLA-CPU host; warm it is
                     # a fraction of this
                     "inproc", 300, tags=("byzantine", "offload",
                                          "crypto", "recovery")),
        ScenarioSpec("crash-restart-replay", scenario_crash_restart_replay,
                     "inproc", 60, tags=("recovery",)),
        ScenarioSpec("thin-replica-failover",
                     scenario_thin_replica_failover,
                     "inproc", 90, tags=("crash", "read-tier",
                                         "pre-execution")),
        ScenarioSpec("crashpoint-exec-post-apply",
                     scenario_crashpoint_exec_post_apply,
                     "inproc", 60, tags=("crashpoint", "recovery")),
        ScenarioSpec("crashpoint-vc-persist",
                     scenario_crashpoint_vc_persist,
                     "inproc", 90, tags=("crashpoint", "view-change",
                                         "recovery")),
        ScenarioSpec("group-commit-crash", scenario_group_commit_crash,
                     "inproc", 60, tags=("crashpoint", "durability",
                                         "recovery")),
        ScenarioSpec("agg-tree-node-kill", scenario_agg_tree_node_kill,
                     "inproc", 90, tags=("aggregation", "crash",
                                         "fallback")),
        ScenarioSpec("agg-wan-latency", scenario_agg_wan_latency,
                     "inproc", 120, tags=("aggregation", "wan",
                                          "large-n")),
    ]


# ----------------------------------------------------------------------
# full matrix (real replica subprocesses; bench_chaos.py without --smoke)
# ----------------------------------------------------------------------


def _net(ctx: ScenarioContext, **kw):
    from tpubft.testing.network import BftTestNetwork
    base_port = ctx.randint("base_port", 210, 479) * 100
    kw.setdefault("view_change_timeout_ms", 2500)
    return BftTestNetwork(f=1, base_port=base_port,
                          db_dir=ctx.tmpdir,
                          seed=ctx.cluster_seed().decode(), **kw)


def _commit(kv, key: bytes, value: bytes, timeout_ms: int = 10000,
            tries: int = 6) -> bool:
    for _ in range(tries):
        try:
            if kv.write([(key, value)], timeout_ms=timeout_ms).success:
                return True
        except Exception:  # noqa: BLE001 — retried
            pass
    return False


def _views(net, replicas) -> dict:
    return {r: net.current_view(r) or 0 for r in replicas}


def proc_crash_primary_mid_viewchange(ctx: ScenarioContext) -> dict:
    """The old primary is isolated, then HARD-CRASHES halfway through
    the view-change window and restarts: the cluster must still
    complete the change, and the restarted ex-primary must rejoin the
    new view with its ledger intact."""
    with _net(ctx) as net:
        kv = net.skvbc_client(0)
        assert _commit(kv, b"pre", b"1"), "baseline write failed"
        ctx.event("isolate", replica=0)
        net.isolate_replica(0)
        # crash the old primary mid-window (half the VC timeout in)
        time.sleep(net.view_change_timeout_ms / 2e3)
        ctx.event("kill", replica=0)
        net.kill_replica(0)
        t0 = time.monotonic()
        assert _commit(kv, b"during", b"2", timeout_ms=15000, tries=8), \
            "cluster never recovered from the crashed primary"
        views = _views(net, (1, 2, 3))
        assert all(v >= 1 for v in views.values()), views
        ctx.event("restart", replica=0)
        net.start_replica(0)
        net.wait_for_replicas_up(replicas=[0])
        net.wait_for(lambda: (net.current_view(0) or 0) >= 1, timeout=60)
        assert _commit(kv, b"post", b"3", timeout_ms=15000)
        recovery = time.monotonic() - t0
        assert kv.read([b"pre", b"during", b"post"]) == {
            b"pre": b"1", b"during": b"2", b"post": b"3"}, \
            "ledger divergence after the mid-view-change crash"
    return {"recovery_s": round(recovery, 3)}


def proc_asymmetric_partition_heal(ctx: ScenarioContext) -> dict:
    """A deaf backup (sends, hears nothing) must not cost liveness;
    after heal it re-converges from retransmissions/state transfer."""
    victim = ctx.choice("victim", (2, 3))
    with _net(ctx) as net:
        kv = net.skvbc_client(0)
        assert _commit(kv, b"a", b"1")
        ctx.event("deafen", replica=victim)
        net.deafen_replica(victim)
        for i in range(3):
            assert _commit(kv, b"k%d" % i, b"v", timeout_ms=15000), \
                "liveness lost to a single deaf backup"
        ctx.event("heal", replica=victim)
        net.heal(victim)
        t0 = time.monotonic()
        target = net.last_executed(0) or 0
        net.wait_for(lambda: (net.last_executed(victim) or 0) >= target,
                     timeout=60)
        recovery = time.monotonic() - t0
        assert _commit(kv, b"b", b"2")
    return {"recovery_s": round(recovery, 3)}


def proc_equivocating_primary(ctx: ScenarioContext) -> dict:
    """Process-grade equivocation: replica 0 runs with the equivocate
    strategy (validly signed forks). The honest quorum must view-change
    away and commit."""
    net = _net(ctx)
    ctx.event("byzantine", replica=0, strategy="equivocate")
    try:
        for r in range(net.n):
            net.start_replica(r, extra_args=(
                ["--strategy", "equivocate"] if r == 0 else None))
        net.wait_for_replicas_up()
        kv = net.skvbc_client(0)
        t0 = time.monotonic()
        assert _commit(kv, b"x", b"1", timeout_ms=15000, tries=10), \
            "honest quorum never committed under an equivocating primary"
        recovery = time.monotonic() - t0
        views = _views(net, (1, 2, 3))
        assert all(v >= 1 for v in views.values()), views
        assert _commit(kv, b"y", b"2", timeout_ms=15000)
        assert kv.read([b"x", b"y"]) == {b"x": b"1", b"y": b"2"}
    finally:
        net.stop_all()
    return {"recovery_s": round(recovery, 3)}


def proc_f_crash_restart_st_catchup(ctx: ScenarioContext) -> dict:
    """f replicas crash simultaneously and restart far behind: they must
    catch back up (state transfer once the window is gone) and the
    cluster re-converges."""
    victim = ctx.choice("victim", (1, 2, 3))
    with _net(ctx, checkpoint_window=10, work_window=20) as net:
        kv = net.skvbc_client(0)
        assert _commit(kv, b"seed", b"1")
        ctx.event("kill", replica=victim)
        net.kill_replica(victim)
        n_writes = 30               # > work_window: forces ST catch-up
        ctx.event("writes_behind", count=n_writes)
        for i in range(n_writes):
            assert _commit(kv, b"w%03d" % i, b"v", timeout_ms=15000), i
        ctx.event("restart", replica=victim)
        net.start_replica(victim)
        net.wait_for_replicas_up(replicas=[victim])
        t0 = time.monotonic()
        target = net.last_executed(0) or 0
        # a lagging replica's ST anchor comes from live CheckpointMsgs
        # beyond its window (reference: ST triggers off checkpoint
        # certificates riding ordering) — an idle cluster gives it no
        # signal to transfer from, so keep traffic flowing while it
        # catches up
        deadline = time.monotonic() + 240
        i = 0
        while time.monotonic() < deadline \
                and (net.last_executed(victim) or 0) < target:
            _commit(kv, b"t%03d" % i, b"v", timeout_ms=10000, tries=2)
            i += 1
            time.sleep(0.2)
        assert (net.last_executed(victim) or 0) >= target, \
            "victim never caught up via state transfer"
        recovery = time.monotonic() - t0
        assert _commit(kv, b"tail", b"2")
    return {"recovery_s": round(recovery, 3), "writes_behind": n_writes}


def proc_crashpoint_exec_drill(ctx: ScenarioContext) -> dict:
    """Process crashpoint drill: a replica restarted with
    TPUBFT_CRASHPOINT=exec.post_apply dies AT the seam (exit code 173,
    proving it was the seam and not a stray fault), restarts clean, and
    must replay exactly once — reads stay consistent clusterwide."""
    from tpubft.testing.crashpoints import CRASH_EXIT_CODE, ENV_VAR
    victim = 2
    with _net(ctx) as net:
        kv = net.skvbc_client(0)
        assert _commit(kv, b"pre", b"1")
        ctx.event("restart_with_crashpoint", replica=victim,
                  point="exec.post_apply")
        net.restart_replica(victim,
                            extra_env={ENV_VAR: "exec.post_apply"})
        net.wait_for_replicas_up(replicas=[victim])
        # the victim dies on its first applied run (recovery replay of
        # the committed suffix counts — it IS a durable apply)
        assert _commit(kv, b"boom", b"2", timeout_ms=15000)
        code = net.wait_exit(victim, timeout=60)
        assert code == CRASH_EXIT_CODE, \
            f"victim exited {code}, not at the crashpoint seam"
        ctx.event("crashed", replica=victim, point="exec.post_apply")
        ctx.event("restart", replica=victim)
        t0 = time.monotonic()
        net.start_replica(victim)           # clean env: no crashpoint
        net.wait_for_replicas_up(replicas=[victim])
        assert _commit(kv, b"post", b"3", timeout_ms=15000)
        target = net.last_executed(0) or 0
        net.wait_for(lambda: (net.last_executed(victim) or 0) >= target,
                     timeout=60)
        recovery = time.monotonic() - t0
        assert kv.read([b"pre", b"boom", b"post"]) == {
            b"pre": b"1", b"boom": b"2", b"post": b"3"}, \
            "ledger divergence after the exec-seam crash"
    return {"recovery_s": round(recovery, 3), "exit_code": code}


def proc_crashpoint_dur_drill(ctx: ScenarioContext) -> dict:
    """Process crashpoint drill (ISSUE 15): a replica restarted with
    TPUBFT_CRASHPOINT=dur.group_fsync dies AT the durability seam —
    group applied, fsync never issued, watermark never published (exit
    code 173 proves it was the seam). A clean restart must replay the
    committed suffix exactly once: reads stay consistent clusterwide
    and the recovered replica catches back up to the quorum's
    watermark, digest-identical."""
    from tpubft.testing.crashpoints import CRASH_EXIT_CODE, ENV_VAR
    victim = ctx.choice("victim", (1, 2, 3))
    with _net(ctx) as net:
        kv = net.skvbc_client(0)
        assert _commit(kv, b"pre", b"1")
        ctx.event("restart_with_crashpoint", replica=victim,
                  point="dur.group_fsync")
        net.restart_replica(victim,
                            extra_env={ENV_VAR: "dur.group_fsync"})
        net.wait_for_replicas_up(replicas=[victim])
        # the victim dies on its first group commit after the restart
        assert _commit(kv, b"boom", b"2", timeout_ms=15000)
        code = net.wait_exit(victim, timeout=60)
        assert code == CRASH_EXIT_CODE, \
            f"victim exited {code}, not at the dur.group_fsync seam"
        ctx.event("crashed", replica=victim, point="dur.group_fsync")
        ctx.event("restart", replica=victim)
        t0 = time.monotonic()
        net.start_replica(victim)           # clean env: no crashpoint
        net.wait_for_replicas_up(replicas=[victim])
        assert _commit(kv, b"post", b"3", timeout_ms=15000)
        target = net.last_executed(0) or 0
        net.wait_for(lambda: (net.last_executed(victim) or 0) >= target,
                     timeout=60)
        recovery = time.monotonic() - t0
        assert kv.read([b"pre", b"boom", b"post"]) == {
            b"pre": b"1", b"boom": b"2", b"post": b"3"}, \
            "ledger divergence after the group-fsync crash"
    return {"recovery_s": round(recovery, 3), "exit_code": code}


def proc_crashpoint_vc_drill(ctx: ScenarioContext) -> dict:
    """Process crashpoint drill: a backup dies at vc.persist while the
    old primary is isolated — after a clean restart it must RESUME the
    persisted view change and retransmit its ViewChangeMsg so the
    quorum completes."""
    from tpubft.testing.crashpoints import CRASH_EXIT_CODE, ENV_VAR
    victim = ctx.choice("victim", (2, 3))
    with _net(ctx) as net:
        kv = net.skvbc_client(0)
        assert _commit(kv, b"pre", b"1")
        ctx.event("restart_with_crashpoint", replica=victim,
                  point="vc.persist")
        net.restart_replica(victim, extra_env={ENV_VAR: "vc.persist"})
        net.wait_for_replicas_up(replicas=[victim])
        ctx.event("isolate", replica=0)
        net.isolate_replica(0)
        # complaints (and the view change the victim dies inside) only
        # fire while work is in flight: drive a write from a background
        # thread. It cannot complete before the victim recovers — the
        # view-change quorum (2f+1 = 3) needs all three survivors and
        # the victim crashes before broadcasting its ViewChangeMsg.
        box: dict = {}

        def drive() -> None:
            box["ok"] = _commit(kv, b"during", b"2", timeout_ms=15000,
                                tries=20)

        th = threading.Thread(target=drive, daemon=True)
        th.start()
        code = net.wait_exit(victim, timeout=90)
        assert code == CRASH_EXIT_CODE, \
            f"victim exited {code}, not at the vc.persist seam"
        ctx.event("crashed", replica=victim, point="vc.persist")
        ctx.event("restart", replica=victim)
        t0 = time.monotonic()
        net.start_replica(victim)           # clean env
        net.wait_for_replicas_up(replicas=[victim])
        th.join(120)
        recovery = time.monotonic() - t0
        assert not th.is_alive() and box.get("ok"), \
            "view change never completed after the vc.persist crash"
        views = _views(net, [r for r in (1, 2, 3)])
        assert all(v >= 1 for v in views.values()), views
        net.heal(0)
        assert _commit(kv, b"post", b"3", timeout_ms=15000)
        assert kv.read([b"pre", b"during", b"post"]) == {
            b"pre": b"1", b"during": b"2", b"post": b"3"}
    return {"recovery_s": round(recovery, 3), "exit_code": code}


def proc_breaker_trip_mid_viewchange(ctx: ScenarioContext) -> dict:
    """COMPOUND at process scale: every replica's device breaker is
    tripped through the fault-control plane, then the primary is
    isolated — the view change and subsequent ordering run entirely
    degraded."""
    from tpubft.testing.faults import fault_command
    with _net(ctx) as net:
        kv = net.skvbc_client(0)
        assert _commit(kv, b"pre", b"1")
        ctx.event("breaker_trip", replicas=list(range(1, net.n)))
        for r in range(1, net.n):
            res = fault_command(net.fault_base + r, cmd="breaker",
                                action="trip")
            assert res and "breaker" in res, f"breaker trip failed on {r}"
        ctx.event("isolate", replica=0)
        net.isolate_replica(0)
        t0 = time.monotonic()
        assert _commit(kv, b"during", b"2", timeout_ms=15000, tries=10), \
            "degraded cluster never completed the view change"
        recovery = time.monotonic() - t0
        views = _views(net, (1, 2, 3))
        assert all(v >= 1 for v in views.values()), views
        snap = fault_command(net.fault_base + 1, cmd="breaker",
                             action="get")
        trips = (snap or {}).get("breaker", {}).get("trips", 0)
        assert trips >= 1, "breaker snapshot lost the injected trip"
        net.heal(0)
        assert _commit(kv, b"post", b"3", timeout_ms=15000)
    return {"recovery_s": round(recovery, 3), "degraded": True,
            "breaker_trips": trips,
            "probe_error": "device breaker tripped via fault-control "
                           "plane during view change"}


def full_matrix() -> List[ScenarioSpec]:
    return smoke_matrix() + [
        ScenarioSpec("proc-crash-primary-mid-viewchange",
                     proc_crash_primary_mid_viewchange, "process", 300,
                     tags=("crash", "view-change")),
        ScenarioSpec("proc-asymmetric-partition-heal",
                     proc_asymmetric_partition_heal, "process", 300,
                     tags=("partition",)),
        ScenarioSpec("proc-equivocating-primary",
                     proc_equivocating_primary, "process", 300,
                     tags=("byzantine", "view-change")),
        ScenarioSpec("proc-f-crash-restart-st-catchup",
                     proc_f_crash_restart_st_catchup, "process", 420,
                     tags=("crash", "state-transfer")),
        ScenarioSpec("proc-crashpoint-exec-drill",
                     proc_crashpoint_exec_drill, "process", 300,
                     tags=("crashpoint", "recovery")),
        ScenarioSpec("proc-crashpoint-vc-drill",
                     proc_crashpoint_vc_drill, "process", 300,
                     tags=("crashpoint", "view-change", "recovery")),
        ScenarioSpec("proc-crashpoint-dur-drill",
                     proc_crashpoint_dur_drill, "process", 300,
                     tags=("crashpoint", "durability", "recovery")),
        ScenarioSpec("proc-breaker-trip-mid-viewchange",
                     proc_breaker_trip_mid_viewchange, "process", 300,
                     tags=("compound", "degraded", "view-change")),
    ]


def matrix_by_name() -> Dict[str, ScenarioSpec]:
    return {s.name: s for s in full_matrix()}
