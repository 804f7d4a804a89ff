"""Sharded crypto kernels over a jax.sharding.Mesh — the production
multi-chip dispatch plane (ISSUE 16).

Kernel patterns, all ICI-friendly:
  * data-parallel batch verify / digest — batch axis sharded, no
    cross-device traffic (the common PrePrepare/client-sig flood case,
    and the sha256 window digests);
  * sharded MSM — points sharded across devices, each device ladders and
    tree-reduces its shard locally, then one all_gather of the tiny partial
    sums (4*NL ints each) and a local log2(D) combine. This is the n=1000
    threshold-share accumulation at scale (reference: fastMultExp over all
    shares on one CPU thread, FastMultExp.cpp:27);
  * sharded ECDSA RLC — the aggregate fold is mesh-friendly: each shard
    folds its own weighted residual sum to width 1 and emits one verdict
    bit, so the only cross-device traffic is the out-spec gather of D
    booleans, and a failing aggregate names the guilty SHARD — bisection
    re-launches only inside it (tpubft/ops/ecdsa.rlc_verify_batch).

`CryptoMesh` is the mesh's control plane: it owns the healthy-device
set, one breaker CHILD per chip under the process-wide registry
(`device.chip<N>` — a single sick chip is evicted from the mesh and the
work rebalances over the survivors instead of tripping the whole plane
to scalar), cooldown re-admission probes, the autotuner's
`crypto_shard_count` cap, and the per-mesh compiled-kernel cache. Ops
modules never touch it directly — they go through the mesh tier in
tpubft/ops/dispatch.py (`mesh_plan`/`mesh_launch`), the same seam
discipline as `device_section` (and the tpulint device-seam pass keeps
`shard_map` call sites confined to these two modules).
"""
from __future__ import annotations

import functools
import math
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpubft.utils.breaker import BreakerOpen, CircuitBreaker, get_breaker
from tpubft.utils.racecheck import make_lock

AXIS = "shard"


def _shard_map(f, mesh: Mesh, in_specs, out_specs):
    """`jax.shard_map` with the varying-manual-axes check off: the
    ladders' initial carry is an unvarying constant (identity point)
    which the checker rejects."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"mesh of {n} devices asked for, "
                         f"{len(devs)} present")
    return Mesh(np.array(devs[:n]), (AXIS,))


def sharded_msm_kernel(mesh: Mesh):
    """Builds a jitted sharded MSM: (bits, px, py, inf) sharded on the batch
    axis -> replicated projective sum (NL, 1) per coordinate."""
    from tpubft.ops.bls12_381 import g1_curve
    cv = g1_curve()

    def local_msm(bits, px, py, inf):
        pts = cv.from_affine(px, py)
        pts = cv.select(inf, cv.identity(px.shape[1:]), pts)
        acc = cv.scalar_mul_bits(bits, pts)
        part = cv.msm_reduce(acc)                       # (NL, 1) local partial
        # gather all partials (tiny: 3*NL ints per device) over ICI
        gx = jax.lax.all_gather(part.x, AXIS, axis=1, tiled=True)  # (NL, D)
        gy = jax.lax.all_gather(part.y, AXIS, axis=1, tiled=True)
        gz = jax.lax.all_gather(part.z, AXIS, axis=1, tiled=True)
        from tpubft.ops.weierstrass import WPoint
        total = cv.msm_reduce(WPoint(gx, gy, gz))       # log2(D) adds, local
        return total.x, total.y, total.z

    shard = P(None, AXIS)
    fn = _shard_map(local_msm, mesh,
                    in_specs=(shard, shard, shard, P(AXIS)),
                    out_specs=(P(None, None),) * 3)
    return jax.jit(fn)


def sharded_verify_ed25519(mesh: Mesh):
    """Data-parallel batched Ed25519 verify: every input sharded on
    batch. On a TPU each device runs the FUSED Pallas kernel on its
    shard (the fast single-chip path must not be lost by going
    multi-chip) — under shard_map, because the partitioner cannot split
    a Mosaic kernel; elsewhere the XLA formulation, partitioned by the
    jit shardings."""
    from tpubft.ops import ed25519 as ops

    batch_last, batch_only = P(None, AXIS), P(AXIS)
    specs = (batch_last, batch_last, batch_last, batch_only, batch_last,
             batch_only)
    if ops._use_pallas():
        from tpubft.ops import ed25519_pallas as pk
        return jax.jit(_shard_map(pk.verify_kernel, mesh, in_specs=specs,
                                  out_specs=batch_only))
    return jax.jit(ops.verify_kernel,
                   in_shardings=tuple(NamedSharding(mesh, s)
                                      for s in specs),
                   out_shardings=NamedSharding(mesh, batch_only))


def sharded_msm(points: Sequence, scalars: Sequence[int],
                mesh: Optional[Mesh] = None):
    """Host-facing sharded MSM over G1 affine int points. Pads the batch to
    a multiple of the mesh size (power of two) with identity slots."""
    from tpubft.crypto import bls12381 as ref
    from tpubft.ops.bls12_381 import (_bits_msb_batch, _pad_pow2,
                                      _to_affine_host, g1_curve)
    mesh = mesh or make_mesh()
    cv = g1_curve()
    n = len(points)
    if n == 0:
        return None
    d = mesh.devices.size
    # batch must split evenly over the mesh (non-power-of-two device
    # counts included)
    m = max(_pad_pow2(n), d)
    m = ((m + d - 1) // d) * d
    infinity = np.zeros(m, bool)
    pts, ks = [], []
    for i in range(m):
        if i < n and points[i] is not None:
            pts.append(points[i])
            ks.append(scalars[i] % ref.R)
        else:
            pts.append((0, 0))
            ks.append(0)
            infinity[i] = True
    px, py = cv.affine_to_device(pts)
    bits = _bits_msb_batch(ks)
    kern = _get_msm_kernel(mesh)
    x, y, z = kern(jnp.asarray(bits), jnp.asarray(px), jnp.asarray(py),
                   jnp.asarray(infinity))
    return _to_affine_host(np.asarray(x)[:, 0], np.asarray(y)[:, 0],
                           np.asarray(z)[:, 0])


_KERNEL_CACHE = {}


def _get_msm_kernel(mesh: Mesh):
    key = tuple(d.id for d in mesh.devices.flat)
    if key not in _KERNEL_CACHE:
        _KERNEL_CACHE[key] = sharded_msm_kernel(mesh)
    return _KERNEL_CACHE[key]


# ---------------------------------------------------------------------------
# data-parallel sha256 (window digests ride the mesh too)
# ---------------------------------------------------------------------------

def sharded_sha256_kernel(mesh: Mesh):
    """Uniform-block-count digest batch, batch axis sharded: words
    (B, nb, 16) -> digests (B, 8). Purely elementwise per lane, so the
    partitioner splits the batch with zero cross-device traffic and the
    per-lane values are bit-identical to the single-device kernel."""
    from tpubft.ops import sha256 as ops
    batch = NamedSharding(mesh, P(AXIS))
    return jax.jit(lambda w: ops.sha256_kernel(w),
                   in_shardings=batch, out_shardings=batch)


def sharded_sha256_masked_kernel(mesh: Mesh):
    """Mixed-size digest batch (per-lane freeze at its own block count):
    words (B, nb, 16) + nblocks (B,) sharded on the batch axis."""
    from tpubft.ops import sha256 as ops
    batch = NamedSharding(mesh, P(AXIS))
    return jax.jit(lambda w, nb: ops.sha256_kernel_masked(w, nb),
                   in_shardings=(batch, batch), out_shardings=batch)


# ---------------------------------------------------------------------------
# segmented multi-MSM (the fused combine plane's msm_batch at mesh scale)
# ---------------------------------------------------------------------------

def sharded_msm_batch_kernel(mesh: Mesh):
    """Segmented multi-MSM with the share axis K sharded: bits
    (255, S, K), px/py (NL, S, K), infinity (S, K) -> one projective
    point per segment (NL, S, 1). Each device ladders its K-shard and
    tree-reduces it locally; the cross-device traffic is one all_gather
    of the per-shard partials (3*NL ints per segment per device),
    combined with a local log2(D) reduce — same shape as the
    single-segment sharded MSM, vectorized over S."""
    from tpubft.ops.bls12_381 import g1_curve
    cv = g1_curve()

    def local(bits, px, py, inf):
        from tpubft.ops.weierstrass import WPoint
        pts = cv.from_affine(px, py)
        pts = cv.select(inf, cv.identity(px.shape[1:]), pts)
        acc = cv.scalar_mul_bits(bits, pts)
        part = cv.msm_reduce(acc)                     # (NL, S, 1) local
        gx = jax.lax.all_gather(part.x, AXIS, axis=2, tiled=True)
        gy = jax.lax.all_gather(part.y, AXIS, axis=2, tiled=True)
        gz = jax.lax.all_gather(part.z, AXIS, axis=2, tiled=True)
        total = cv.msm_reduce(WPoint(gx, gy, gz))     # (NL, S, 1)
        return total.x, total.y, total.z

    seg = P(None, None, AXIS)
    fn = _shard_map(local, mesh,
                    in_specs=(seg, seg, seg, P(None, AXIS)),
                    out_specs=(P(None, None, None),) * 3)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# sharded ECDSA RLC aggregate (per-shard verdict bits; ops/ecdsa bisects
# only inside a failing shard)
# ---------------------------------------------------------------------------

def sharded_rlc_kernel(curve_name: str, mesh: Mesh):
    """RLC aggregate with the batch axis sharded: every input column
    sharded, each shard folds its own weighted residual sum to width 1
    and emits ONE verdict bit — out-spec gather of D booleans is the
    only cross-device traffic. The aggregate passes iff every shard's
    partial sum is zero (strictly stronger than the global sum being
    zero, and sound by the same Fiat-Shamir argument bisection subtrees
    already rely on: the coefficients bind the FULL batch transcript)."""
    from tpubft.ops.ecdsa import get_curve, rlc_fold_body
    body = rlc_fold_body(get_curve(curve_name))

    def local(u1_bits, u2_bits, qx, qy, xr_m, xrpn_m, wrap_ok, active,
              a_m):
        return body(u1_bits, u2_bits, qx, qy, xr_m, xrpn_m, wrap_ok,
                    active, a_m).reshape(1)

    col = P(None, AXIS)
    fn = _shard_map(local, mesh,
                    in_specs=(col, col, col, col, col, col, P(AXIS),
                              P(AXIS), col),
                    out_specs=P(AXIS))
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# CryptoMesh — the mesh control plane (health, eviction, shard cap)
# ---------------------------------------------------------------------------

# test/chaos fault injection: device ids whose chips are "dead" — a
# launch over a mesh containing one raises (the XLA launch error a real
# sick chip produces) and its re-admission probes fail until cleared
_chip_faults: Set[int] = set()


def inject_chip_fault(device_id: int) -> None:
    """Mark one chip dead (bench_dispatch --device-fault style, but per
    chip): mesh launches touching it fail and its probes fail."""
    _chip_faults.add(device_id)


def clear_chip_faults() -> None:
    _chip_faults.clear()


@dataclass(frozen=True)
class MeshPlan:
    """One routing decision: the devices a launch may use. `mesh` is
    None on a single-chip (or chip-less) host — callers take their
    plain single-device kernel path, byte-identical to pre-mesh
    behavior."""
    epoch: int
    devices: Tuple
    mesh: Optional[Mesh]

    @property
    def n(self) -> int:
        """Shard count this plan routes across (1 = single-device)."""
        return len(self.devices) if self.mesh is not None else 1


def shard_rows(n: int, d: int, multiple: int = 1) -> int:
    """Per-shard row count for an n-item batch over d shards: padded to
    a power of two (and a multiple of the per-device kernel tile) so
    the jit cache holds O(log) shapes per mesh width, not one program
    per distinct batch size."""
    from tpubft.ops.field import pad_pow2
    rows = pad_pow2(max(1, math.ceil(n / max(1, d))))
    if multiple > 1:
        rows = ((rows + multiple - 1) // multiple) * multiple
    return rows


@functools.lru_cache(maxsize=1)
def _probe_fn():
    return jax.jit(lambda x: (x * x + 1).sum())


class CryptoMesh:
    """Process-wide mesh control plane. One breaker child per chip
    (`device.chip<N>`) under the existing registry: a chip whose probe
    fails after a mesh-launch failure trips its OWN breaker and is
    evicted — the mesh rebuilds over the survivors and the launch
    retries there, so the global `device` breaker (and the scalar
    fallback behind it) only sees a failure when NO healthy subset can
    run the work. Cooldown re-admission rides the breaker's HALF_OPEN
    probe protocol: `plan()` probes a cooled-down chip once, success
    closes the child and the chip rejoins (epoch bump -> fresh mesh).

    A chip-eviction probe failure counts ONCE (threshold 1, vs the
    global breaker's 3): the probe is targeted evidence — it ran on
    that chip alone right after a launch over it failed — and a false
    eviction costs little (the chip re-admits itself on cooldown)
    while each extra confirmation round is another failed flood batch.

    An OPEN chip breaker makes `utils.breaker.any_degraded()` true, so
    the health plane reports the plane degraded and the autotuner's
    degraded rule resets every unpinned knob — including
    `crypto_shard_count` — exactly the ISSUE 16 eviction contract.
    """

    CHIP_PREFIX = "device.chip"

    def __init__(self) -> None:
        self._mu = make_lock("crypto_mesh", reentrant=True)
        self._devices: Optional[Tuple] = None
        self._breakers: Dict[int, CircuitBreaker] = {}
        self._cap = 0                   # 0 = use every healthy chip
        self._epoch = 0
        self._meshes: Dict[Tuple[int, ...], Mesh] = {}
        self._kernels: Dict[Tuple, object] = {}
        # telemetry (read by health/status/bench; plain ints under _mu)
        self.evictions = 0
        self.readmits = 0
        self.last_rebalance_ms = 0.0

    # -- inventory ----------------------------------------------------
    def _inventory(self) -> Tuple:
        with self._mu:                    # reentrant: plan() re-enters
            if self._devices is None:
                # no backend is an error, not an empty pool: a process
                # that asked for the device plane and cannot reach it
                # must not carry on chip-less
                self._devices = tuple(jax.devices())
                for dev in self._devices:
                    if len(self._devices) > 1:
                        self._breakers[dev.id] = get_breaker(
                            f"{self.CHIP_PREFIX}{dev.id}",
                            failure_threshold=1, cooldown_s=2.0,
                            max_cooldown_s=30.0)
            return self._devices

    def device_count(self) -> int:
        return len(self._inventory())

    def chip_breaker(self, device_id: int) -> Optional[CircuitBreaker]:
        self._inventory()
        return self._breakers.get(device_id)

    # -- knob actuator (tuning/wiring.py: crypto_shard_count) ---------
    def set_shard_count(self, v: int) -> None:
        """Cap the shard fan-out (autotuner actuator). 0 or >= device
        count means "all healthy chips"; an evicted chip resets the
        knob via the controller's degraded rule, not here."""
        v = max(0, int(v))
        with self._mu:
            if v != self._cap:
                self._cap = v
                self._epoch += 1

    def shard_count_cap(self) -> int:
        with self._mu:
            return self._cap

    # -- probes -------------------------------------------------------
    def _probe(self, dev) -> None:
        """Tiny computation pinned to ONE chip — enough to catch a dead
        transport/runtime without the cost of a crypto kernel. Runs
        OUTSIDE device_section on purpose: probes must work while the
        global breaker is OPEN (re-admission is how it closes), and a
        per-chip probe must never be attributed to the shared device."""
        if dev.id in _chip_faults:
            raise RuntimeError(f"injected chip fault on device {dev.id}")
        x = jax.device_put(np.arange(16, dtype=np.int32), dev)
        np.asarray(_probe_fn()(x))

    # -- planning -----------------------------------------------------
    def plan(self) -> MeshPlan:
        """Current routing decision. Cooled-down evicted chips are
        probed for re-admission here (one probe per cooldown expiry —
        the breaker's HALF_OPEN slot accounting rate-limits it)."""
        devices = self._inventory()
        if len(devices) <= 1:
            return MeshPlan(0, devices, None)
        with self._mu:
            healthy: List = []
            for dev in devices:
                b = self._breakers[dev.id]
                state = b.state
                if state == "half_open":
                    try:
                        with b.attempt("mesh_probe"):
                            self._probe(dev)
                        state = b.state
                        if state == "closed":
                            self.readmits += 1
                            self._epoch += 1
                    except BreakerOpen:
                        continue        # probe slot taken / re-opened
                    except Exception:  # noqa: BLE001 — probe verdict
                        continue        # recorded by the attempt
                if state == "closed":
                    healthy.append(dev)
            if self._cap:
                healthy = healthy[:self._cap]
            if len(healthy) <= 1:
                return MeshPlan(self._epoch,
                                tuple(healthy) or devices[:1], None)
            key = tuple(d.id for d in healthy)
            mesh = self._meshes.get(key)
            if mesh is None:
                mesh = Mesh(np.array(healthy), (AXIS,))
                self._meshes[key] = mesh
            return MeshPlan(self._epoch, tuple(healthy), mesh)

    def raise_if_faulted(self, plan: MeshPlan) -> None:
        """Surface an injected chip fault as the launch failure a real
        dead chip produces (the XLA launch raises when any participant
        is gone). Called by dispatch.mesh_launch inside the try."""
        if not _chip_faults:
            return
        bad = [d.id for d in plan.devices if d.id in _chip_faults]
        if bad:
            raise RuntimeError(
                f"injected chip fault: device(s) {bad} in the mesh")

    # -- failure handling --------------------------------------------
    def on_launch_failure(self, plan: MeshPlan, kind: str) -> bool:
        """A sharded launch raised: probe every chip it used, record
        each probe's verdict on that chip's breaker (a failed probe
        evicts — threshold 1), and rebuild the plan. Returns True when
        the healthy set changed (the caller rebalances and retries on
        the survivors); False means no chip could be blamed — the error
        is not a sick chip, re-raise it into the global breaker."""
        if plan.mesh is None:
            return False
        t0 = time.perf_counter()
        evicted = 0
        for dev in plan.devices:
            b = self._breakers.get(dev.id)
            if b is None:
                continue
            before = b.state
            try:
                with b.attempt(kind or "mesh"):
                    self._probe(dev)
            except BreakerOpen:
                continue
            except Exception:  # noqa: BLE001 — the verdict is recorded
                pass
            if before == "closed" and b.state != "closed":
                evicted += 1
        if not evicted:
            return False
        with self._mu:
            self._epoch += 1
            self.evictions += evicted
        self.plan()     # rebuild eagerly so the rebalance time includes
        # the survivor mesh construction, not just the bookkeeping
        with self._mu:
            self.last_rebalance_ms = (time.perf_counter() - t0) * 1e3
        return True

    # -- per-mesh compiled-kernel cache ------------------------------
    def cached_kernel(self, name: str, plan: MeshPlan,
                      builder: Callable[[Mesh], object]) -> object:
        key = (name,) + tuple(d.id for d in plan.devices)
        kern = self._kernels.get(key)
        if kern is None:
            kern = builder(plan.mesh)
            self._kernels[key] = kern
        return kern

    # -- visibility / test isolation ---------------------------------
    def snapshot(self) -> Dict:
        devices = self._inventory()
        with self._mu:
            evicted = sorted(d.id for d in devices
                             if d.id in self._breakers
                             and self._breakers[d.id].state != "closed")
            return {"devices": len(devices),
                    "healthy": len(devices) - len(evicted),
                    "evicted": evicted,
                    "shard_count_cap": self._cap,
                    "epoch": self._epoch,
                    "evictions": self.evictions,
                    "readmits": self.readmits,
                    "last_rebalance_ms": round(self.last_rebalance_ms,
                                               3)}

    def reset(self) -> None:
        """Test isolation: close every chip breaker, drop the cap."""
        with self._mu:
            for b in self._breakers.values():
                b.reset()
            self._cap = 0
            self._epoch += 1


_MESH_MGR: Optional[CryptoMesh] = None
_mesh_mgr_mu = make_lock("crypto_mesh_init")


def mesh_manager() -> CryptoMesh:
    """The process-wide CryptoMesh (all replicas of one process share
    one device pool, same rule as the device breaker). Kernel call
    sites route through tpubft/ops/dispatch.py's mesh tier, never
    here."""
    global _MESH_MGR
    if _MESH_MGR is None:
        with _mesh_mgr_mu:
            if _MESH_MGR is None:
                _MESH_MGR = CryptoMesh()
    return _MESH_MGR
