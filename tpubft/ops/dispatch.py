"""Device dispatch gate — one execution stream to the accelerator,
guarded by the device circuit breaker.

A TPU chip executes one XLA program at a time per core: concurrent
host threads submitting programs don't overlap on the device, they
queue. Modeling that queue explicitly with a process-wide lock keeps
the host sane too — without it, every verification worker (admission
batcher, PrePrepare background verify, collector combine jobs, cert
batcher) materializes its own sharded program simultaneously, and on
the CPU-mesh test backend (8 virtual devices × N worker threads) the
oversubscription collapses throughput far below the serial rate.

Hold the gate for submit→materialize of one batch; never while doing
host-side crypto or holding protocol locks.

Every kernel call site enters through `device_section(kind)`, which
wraps the gate in the process-wide device breaker
(tpubft/utils/breaker.py): device exceptions and latency-SLO breaches
count against the failure budget, a tripped breaker fast-fails callers
into their scalar/host fallbacks with `BreakerOpen` instead of queueing
work behind a dead accelerator transport, and half-open probe batches
re-admit the device once it recovers. `device_dispatch()` (the raw
gate) exists ONLY for this module — tools/check_device_seam.py rejects
any other call site, so no future kernel call can bypass degradation
handling.
"""
from __future__ import annotations

import threading
import time

from tpubft.utils import flight
from tpubft.utils.breaker import BreakerOpen, get_breaker  # noqa: F401
# re-exported: callers catching the fast-fail import it from here so the
# ops layer stays the only crypto↔breaker coupling point

# RLock: a gated section may call another gated helper (e.g. a combine
# that internally runs a gated MSM)
_gate = threading.RLock()

# ONE breaker for the whole device: the accelerator is a single shared
# resource — if the transport wedges under the ed25519 kernel, the
# sha256 batch is just as dead. Per-seam attribution rides the `kind`
# tag (failures_by_kind in the snapshot).
_breaker = get_breaker("device")


def device_breaker():
    """The process-wide device circuit breaker (health plane + replica
    config wiring read/configure it here)."""
    return _breaker


def device_dispatch():
    """Raw context manager serializing device program execution. Only
    this module may use it — kernels go through `device_section`."""
    return _gate


# per-thread seam state: `.tier` is the OUTERMOST open device_tier on
# this thread (nested tiers pass through, as their breaker attempts
# do), `.sections` the depth of open sections (the gate is re-entrant)
_tl = threading.local()


class _Section:
    """`with device_section(kind, batch):` — breaker
    admission/classification around the serialized device gate, plus
    flight-recorder/kernel-profiler annotation (kind, batch size,
    breaker state, and the call's three intervals: `prep` inside the
    enclosing `device_tier`, `gate_wait`, and `device` — the gate held:
    transfer, launch, read-back, on the host's clock). Where a profile
    is being taken the gate-held interval is also the host span
    `tpubft:dev:<kind>` round the launch's device events. Raises
    BreakerOpen without touching the device when tripped."""

    __slots__ = ("_attempt", "_kind", "_batch", "_kid", "_t0", "_rec",
                 "_shards", "_wait_ns", "_prep_ns", "_tier", "_ann")

    def __init__(self, kind: str, batch: int, shards: int = 1) -> None:
        self._attempt = _breaker.attempt(kind)
        self._kind = kind
        self._batch = batch
        self._shards = max(1, shards)
        # the TPUBFT_FLIGHT=0 off switch covers the kernel profiler
        # too: a disabled recorder must cost this seam nothing beyond
        # the enabled() check (decided once per section — consistent
        # even if the test hook flips mid-call)
        self._rec = flight.enabled()
        self._kid = flight.kernel_profiler().kind_id(kind) \
            if self._rec else 0
        self._t0 = 0

    def __enter__(self):
        self._attempt.__enter__()
        # breaker admission happens BEFORE the gate (a tripped breaker
        # must fast-fail without queueing behind a wedged dispatch that
        # still holds the gate), so the gate wait lands inside the
        # attempt's clock — credit it back: queueing behind other
        # healthy threads' batches is contention, not device slowness
        t_req = time.monotonic_ns()
        _gate.acquire()
        t_got = time.monotonic_ns()
        _breaker.exclude_wait((t_got - t_req) / 1e9)
        if self._rec:
            self._wait_ns = t_got - t_req
            depth = getattr(_tl, "sections", 0)
            _tl.sections = depth + 1
            # a section nested under the re-entrant gate runs inside
            # its parent's `device` interval: it leaves the tier's prep
            # accounting alone
            tier = self._tier = None if depth else getattr(_tl, "tier",
                                                           None)
            self._prep_ns = (t_req - tier.cursor) if tier is not None \
                else 0
            flight.record(flight.EV_DEV_ENTER, view=self._kid,
                          arg=self._batch)
            self._ann = flight.annotation("dev:" + self._kind)
            if self._ann is not None:
                self._ann.__enter__()
            self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t_rel = time.monotonic_ns() if self._rec else 0
        if self._rec and self._ann is not None:
            self._ann.__exit__(*exc)
        _gate.release()
        suppressed = bool(self._attempt.__exit__(*exc))
        if self._rec:
            elapsed_ns = t_rel - self._t0
            _tl.sections -= 1
            # profile AFTER the breaker's verdict so the recorded state
            # is the post-call one (a call that just tripped the
            # breaker shows up as such in the kernel profile)
            flight.record(flight.EV_DEV_EXIT, view=self._kid,
                          arg=int(elapsed_ns // 1000))
            prof = flight.kernel_profiler()
            row = prof.record(self._kind, self._batch, elapsed_ns,
                              _breaker.state, self._wait_ns,
                              self._prep_ns, self._t0)
            if self._tier is not None:
                self._tier.cursor, self._tier.last = t_rel, row
            if self._shards > 1:
                # per-shard view of the same launch: the shards run in
                # lockstep, so wall time is shared and the per-shard
                # batch is the rebalanced slice — this is the profile
                # the `crypto_shard_count` tuning policy (and an
                # operator reading `status get kernels`) compares
                # against the unsharded kind
                prof.record(f"{self._kind}.shard",
                            max(1, -(-self._batch // self._shards)),
                            elapsed_ns, _breaker.state, row=False)
        return suppressed


def device_section(kind: str, batch: int = 0, shards: int = 1) -> _Section:
    """Guarded device seam. `batch` annotates the kernel profile /
    flight ring with the call's batch size (0 = not reported);
    `shards > 1` marks a mesh launch and adds a `<kind>.shard` profile
    row with the per-shard batch size."""
    return _Section(kind, batch, shards)


class _Tier:
    """`with device_tier(kind):` — the breaker attempt round a whole
    device tier, recorded: the outermost tier on a thread is the
    interval its sections' `prep` is measured in (tier enter -> gate
    requested, gate released -> the next request or the tier's exit),
    and the host span `tpubft:tier:<kind>` in a profile."""

    __slots__ = ("_attempt", "_kind", "_ann", "_mine", "cursor", "last")

    def __init__(self, kind: str) -> None:
        self._attempt = _breaker.attempt(kind)
        self._kind = kind
        self._mine = False
        self.last = None

    def __enter__(self) -> "_Tier":
        self._attempt.__enter__()       # BreakerOpen: nothing opened yet
        if flight.enabled() and getattr(_tl, "tier", None) is None:
            self._mine = True
            _tl.tier = self
            self._ann = flight.annotation("tier:" + self._kind)
            if self._ann is not None:
                self._ann.__enter__()
            self.cursor = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> bool:
        if self._mine:
            _tl.tier = None
            if self.last is not None:
                flight.kernel_profiler().add_prep(
                    self.last, time.monotonic_ns() - self.cursor)
            if self._ann is not None:
                self._ann.__exit__(*exc)
        return bool(self._attempt.__exit__(*exc))


def device_tier(kind: str) -> _Tier:
    """One breaker attempt around a WHOLE device tier — for callers
    that answer on the host when the tier raises (crypto/tpu.py,
    sparse_merkle, state transfer). Such an answer is only safe to read
    as "the device is fine" if it is on the breaker's books: the kernel
    seams (`device_section`) count what fails inside them, but host
    prep, mesh planning and kernel construction run outside. The
    outermost attempt owns the verdict (nested seams pass through), so
    a tier that falls to the host for ANY reason is one recorded
    failure, and an OPEN breaker is one recorded fast-fail."""
    return _Tier(kind)


# ---------------------------------------------------------------------
# mesh tier (ISSUE 16): multi-chip routing for the batched kernels
# ---------------------------------------------------------------------

def crypto_mesh():
    """The process-wide CryptoMesh control plane (health plane, chaos
    tooling and the `crypto_shard_count` knob actuator reach it here —
    ops modules only use `mesh_plan`/`mesh_launch` below)."""
    from tpubft.parallel.sharding import mesh_manager
    return mesh_manager()


def mesh_plan():
    """Current routing decision (probes cooled-down chips for
    re-admission as a side effect). `plan.mesh is None` on single-chip
    hosts — callers take their unsharded kernel path."""
    return crypto_mesh().plan()


def mesh_shards() -> int:
    """Shard count the next mesh launch would use (1 = no mesh)."""
    return crypto_mesh().plan().n


def mesh_launch(kind: str, launch):
    """Run one sharded launch with per-chip fault isolation:
    `launch(plan)` is called with the current MeshPlan; if it raises,
    every chip in the plan is probed and any chip failing its probe is
    EVICTED (its `device.chip<N>` breaker trips), the mesh is rebuilt
    over the survivors, and the launch retries there — so a single sick
    chip degrades the plane to the surviving shards, never to scalar.
    Only when no chip can be blamed (or none are left) does the error
    propagate to the caller's fallback tier. The launch callable must
    handle `plan.mesh is None` (run its unsharded kernel) so the
    retry loop stays total.

    BreakerOpen passes straight through: the GLOBAL device breaker
    tripping means the whole plane is degraded — that is the caller's
    scalar-fallback signal, not a rebalancing opportunity."""
    mgr = crypto_mesh()
    while True:
        plan = mgr.plan()
        try:
            mgr.raise_if_faulted(plan)
            return launch(plan)
        except BreakerOpen:
            raise
        except Exception:
            if not mgr.on_launch_failure(plan, kind):
                raise


# ---------------------------------------------------------------------
# offload tier (ISSUE 20): rented, untrusted, verified helpers
# ---------------------------------------------------------------------

def offload_pool():
    """The process-wide verified crypto-offload HelperPool (replica
    wiring configures it from ReplicaConfig; the health plane and the
    `offload_route` knob actuator reach it here). Like the mesh, the
    pool is just another backend tier behind the crypto call sites:
    kernels keep their device/mesh/host paths and consult the pool's
    verified API first — a failed or evicted lease re-runs on the local
    tiers inside the same flush."""
    from tpubft.offload.pool import get_offload_pool
    return get_offload_pool()
