"""chip_smoke.py — the quickest proof that tpu-bft still starts on the chip.

One process, one chip, nothing spawned that touches JAX:

  python chip_smoke.py          # one chip: `served`, then `crypto_plane`
  python chip_smoke.py --mesh   # four chips: the mesh tier and what it
                                # is compared with, and nothing else

`served` is upstream's simpleTest/simpleKVBC shape (BASELINE.json config
1): n=4 f=1 c=0, ed25519 client and replica signatures, multisig-ed25519
certificates, the SKVBC handler over the merkle KeyValueBlockchain on the
native kvlog engine, crypto_backend="tpu" at production defaults, driven
through InProcessCluster / bftclient / SkvbcClient. The same seeded
traffic then runs against a crypto_backend="cpu" cluster with host
hashing; block count, state root and every read must be identical.
`crypto_plane` puts each kernel kind through its public `ops.*` entry at
the widths the large deployments form, forged, truncated and duplicate
items mixed in, verdicts compared elementwise with the host engines.

Everything printed is one JSON object per line. The timings are smoke
timings (how long this script took), not metrics. The last line is the
contract's `{"ok": true, "device": {...}}`; any phase that fails raises,
and nothing is caught and carried past.

The phases are functions of their sizes so tests/test_chip_smoke.py can
rehearse them on XLA-CPU at a tiny size; `main()` is the only place that
insists on a TPU.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

KINDS = ("ed25519", "sha256", "ecdsa", "bls_msm")


def say(**row) -> None:
    print(json.dumps(row), flush=True)


# ---------------------------------------------------------------------
# compile accounting and warm-up
# ---------------------------------------------------------------------

class CompileLog:
    """Every XLA compile this process makes, and whether JAX's
    persistent cache served it, from jax.monitoring events: rows of
    (jitted function, seconds in compile-or-load, cache hit). The hit
    event fires inside the timed span on the compiling thread, so the
    pairing is per thread."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax
        self.rows = []
        self._tl = threading.local()
        self._mu = threading.Lock()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)

    def _event(self, event, **kw) -> None:
        if event == self._HIT:
            self._tl.hit = True

    def _duration(self, event, secs, **kw) -> None:
        if event != self._COMPILE:
            return
        row = (kw.get("fun_name", ""), round(secs, 3),
               getattr(self._tl, "hit", False))
        self._tl.hit = False
        self._tl.last = row
        with self._mu:
            self.rows.append(row)

    def take_last(self):
        """The calling thread's newest row (None if it compiled nothing
        since the last take)."""
        row, self._tl.last = getattr(self._tl, "last", None), None
        return row

    def cold_since(self, mark: int, floor_s: float = 1.0):
        """Compiles after row `mark` that the cache did not serve and
        that took long enough to be a kernel, not a helper op."""
        return [r for r in self.rows[mark:] if not r[2] and r[1] >= floor_s]


def _s(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype)


def _ed25519_args(m: int):
    import jax.numpy as jnp
    from tpubft.ops import f25519
    i32 = jnp.int32
    return [_s((64, m), i32), _s((64, m), i32), _s((f25519.NL, m), i32),
            _s((m,), i32), _s((f25519.NL, m), i32), _s((m,), i32)]


def _rlc_args(curve: str, m: int):
    import jax.numpy as jnp
    from tpubft.ops import ecdsa
    nl = ecdsa.get_curve(curve).f.nl
    col = lambda rows: _s((rows, m), jnp.int32)  # noqa: E731
    return [col(256), col(256), col(nl), col(nl), col(nl), col(nl),
            _s((m,), jnp.bool_), _s((m,), jnp.bool_), col(nl)]


def _msm_args(m: int):
    import jax.numpy as jnp
    from tpubft.ops import bls12_381
    nl = bls12_381.g1_curve().f.nl
    return [_s((bls12_381.SCALAR_BITS, m), jnp.int32),
            _s((nl, m), jnp.int32), _s((nl, m), jnp.int32),
            _s((m,), jnp.bool_)]


def _sha_args(m: int, nb: int, masked: bool):
    import jax.numpy as jnp
    words = _s((m, nb, 16), jnp.uint32)
    return [words, _s((m,), jnp.uint32)] if masked else [words]


def ed25519_padded(n: int) -> int:
    """Lanes the single-device ed25519 tier pads an n-item batch to
    (ops/ed25519._single_device_verify's rule)."""
    from tpubft.ops import ed25519
    m = ed25519._pad_to_class(n)
    if ed25519._use_pallas():
        from tpubft.ops import ed25519_pallas
        tile = ed25519_pallas.TILE
        m = (max(m, tile) + tile - 1) // tile * tile
    return m


def _pow2_ladder(n: int):
    """Every power of two from pad_pow2(n) down to 1: the shapes an RLC
    aggregate and its bisection re-launches take."""
    from tpubft.ops.field import pad_pow2
    m, out = pad_pow2(n), []
    while m >= 1:
        out.append(m)
        m //= 2
    return out


def single_device_programs(*, ed25519_batches=(), rlc=(), msm_points=(),
                           sha_uniform=(), sha_masked=()):
    """(label, jitted kernel, argument shapes) of every single-device
    program the phases run at these sizes, longest compile first.
    tests/test_tpu_compile.py asks the v5e compiler for the same table.

    ed25519_batches: item counts; rlc: (curve, item count) pairs, each
    expanded to its bisection ladder; msm_points: point counts;
    sha_uniform / sha_masked: (messages, blocks) pairs."""
    from tpubft.ops import bls12_381, ecdsa, ed25519, sha256
    from tpubft.ops.field import pad_pow2
    if ed25519._use_pallas():
        from tpubft.ops import ed25519_pallas
        ed_kernel = ed25519_pallas.verify_kernel
    else:
        ed_kernel = ed25519.verify_kernel
    out = []
    for m in sorted({ed25519_padded(n) for n in ed25519_batches}):
        out.append((f"ed25519@{m}", ed_kernel, _ed25519_args(m)))
    for m in sorted({pad_pow2(n) for n in msm_points}):
        out.append((f"bls_msm@{m}", bls12_381.msm_kernel, _msm_args(m)))
    for curve, n in rlc:
        kernel = ecdsa.rlc_kernel(curve)
        for m in _pow2_ladder(n):
            out.append((f"ecdsa_rlc.{curve}@{m}", kernel,
                        _rlc_args(curve, m)))
    for n, nb in sha_uniform:
        out.append((f"sha256@{pad_pow2(n)}x{nb}", sha256.sha256_kernel,
                    _sha_args(pad_pow2(n), nb, False)))
    for n, nb in sha_masked:
        out.append((f"sha256.masked@{pad_pow2(n)}x{nb}",
                    sha256.sha256_kernel_masked,
                    _sha_args(pad_pow2(n), nb, True)))
    return out


def mesh_programs(plan, *, ed25519_batches, sha_masked, rlc, msm_points):
    """The sharded counterparts under MeshPlan `plan`: the mesh tier's
    own cached kernels, at the shapes it pads these batches to."""
    from tpubft.ops import ed25519
    from tpubft.parallel import sharding
    d = plan.n
    kernel = sharding.mesh_manager().cached_kernel
    per_dev = 1
    if ed25519._use_pallas():
        from tpubft.ops import ed25519_pallas
        per_dev = ed25519_pallas.TILE
    verify = kernel("ed25519", plan, sharding.sharded_verify_ed25519)
    out = []
    for m in sorted({max(sharding.shard_rows(n, d, per_dev), 8) * d
                     for n in ed25519_batches}):
        out.append((f"ed25519.shard@{m}", verify, _ed25519_args(m)))
    m = sharding.shard_rows(msm_points, d) * d
    out.append((f"bls_msm.shard@{m}",
                kernel("bls_msm", plan, sharding.sharded_msm_kernel),
                _msm_args(m)))
    curve, n = rlc
    m = sharding.shard_rows(n, d) * d
    out.append((f"ecdsa_rlc.{curve}.shard@{m}",
                kernel(f"ecdsa_rlc.{curve}", plan,
                       lambda mesh: sharding.sharded_rlc_kernel(curve, mesh)),
                _rlc_args(curve, m)))
    n, nb = sha_masked
    m = sharding.shard_rows(n, d) * d
    out.append((f"sha256.masked.shard@{m}x{nb}",
                kernel("sha256.masked", plan,
                       sharding.sharded_sha256_masked_kernel),
                _sha_args(m, nb, True)))
    return out


def warm(programs, log: CompileLog):
    """Compile every program before anything waits on it, ahead of time
    and into the persistent cache: the phases' first calls then find
    their executables instead of stalling four dispatchers on a
    minute-long compile. Tracing holds the interpreter lock, so the
    programs are lowered one after another, longest compile first; each
    backend compile (which does not hold it) starts on a worker thread
    as soon as its program is lowered. Prints one row per program
    (seconds, and whether the cache already had it); returns
    {label: compiled}."""
    def compile_(label, lowered, trace_s):
        log.take_last()
        t0 = time.monotonic()
        compiled = lowered.compile()
        mine = log.take_last()
        row = dict(kernel=label, trace_s=trace_s,
                   compile_s=round(time.monotonic() - t0, 2),
                   cache=("in-process" if mine is None
                          else "hit" if mine[2] else "cold"))
        return row, compiled

    t0 = time.monotonic()
    with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
        jobs = []
        for label, kernel, shapes in programs:
            t1 = time.monotonic()
            lowered = kernel.lower(*shapes)
            jobs.append(pool.submit(compile_, label, lowered,
                                    round(time.monotonic() - t1, 2)))
        done = [job.result() for job in jobs]
    for row, _ in done:
        say(phase="warm", **row)
    say(phase="warm", programs=len(done),
        wall_s=round(time.monotonic() - t0, 2),
        trace_s=round(sum(row["trace_s"] for row, _ in done), 2),
        cold=sum(row["cache"] == "cold" for row, _ in done),
        cache_hits=sum(row["cache"] == "hit" for row, _ in done))
    return {row["kernel"]: compiled for row, compiled in done}


# ---------------------------------------------------------------------
# device evidence
# ---------------------------------------------------------------------

def kernel_calls() -> dict:
    from tpubft.utils import flight
    return {kind: st["calls"]
            for kind, st in flight.kernel_profiler().snapshot().items()}


def kernel_batches() -> dict:
    """{kind: [smallest, mean, largest batch]} the device was given."""
    from tpubft.utils import flight
    return {kind: [st["batch_min"], st["batch_avg"], st["batch_max"]]
            for kind, st in flight.kernel_profiler().snapshot().items()}


def breaker_snapshot() -> dict:
    from tpubft.ops.dispatch import device_breaker
    return device_breaker().snapshot()


def assert_breaker_clean(since: dict) -> dict:
    """The host tiers behind the kernels are a production safety net;
    here they must not have been used. Every one of them is on the
    breaker's books (ops/dispatch.device_tier), so a breaker that
    recorded nothing since the phase began (`since`, its snapshot then)
    means the device answered every call it was given."""
    snap = breaker_snapshot()
    assert snap["state"] == "closed", snap
    for counter in ("failures", "trips", "fast_fails"):
        assert snap[counter] == since[counter], (counter, snap, since)
    assert snap["failures_by_kind"] == since["failures_by_kind"], snap
    return snap


# ---------------------------------------------------------------------
# phase: served
# ---------------------------------------------------------------------

KV_LEN = 21      # Apollo's skvbc.py key and value length


def _traffic(seed: int, clients: int, msgs_per_client: int, batch: int,
             bulk_writes: int, bulk_keys: int):
    """plan[client][message] = list of writesets. Keys are distinct
    across the whole run, so the final state does not depend on the
    order concurrent clients are served in. The first write of each of
    the first `bulk_writes` clients is a bulk load of `bulk_keys` pairs
    (the width at which sparse_merkle levels ride ops/sha256); every
    other write is one pair."""
    rng = random.Random(seed)
    plan = []
    for c in range(clients):
        msgs = []
        for m in range(msgs_per_client):
            writes = []
            for w in range(batch):
                pairs = (bulk_keys if m == 0 and w == 0 and c < bulk_writes
                         else 1)
                writes.append([
                    (hashlib.sha256(b"%d/%d/%d/%d/%d" % (seed, c, m, w, j))
                     .digest()[:KV_LEN], rng.randbytes(KV_LEN))
                    for j in range(pairs)])
            msgs.append(writes)
        plan.append(msgs)
    return plan


def served(backend: str, *, workdir: str, seed: int, clients: int,
           msgs_per_client: int, batch: int, bulk_writes: int,
           bulk_keys: int, cfg_overrides=None,
           op_timeout_ms: int = 300_000) -> dict:
    """Order, execute and answer the seeded traffic on an n=4 cluster
    with this crypto backend; read every key back. Returns what the two
    backends are compared on, plus the evidence counters."""
    from tpubft.apps.skvbc import SkvbcClient, SkvbcHandler
    from tpubft.kvbc import KeyValueBlockchain
    from tpubft.kvbc.replica import open_db
    from tpubft.storage.metadata import (CONSENSUS_META_FAMILIES,
                                         DBPersistentStorage)
    from tpubft.testing import InProcessCluster

    plan = _traffic(seed, clients, msgs_per_client, batch, bulk_writes,
                    bulk_keys)
    expected = {k: v for msgs in plan for writes in msgs
                for ws in writes for k, v in ws}
    n_writes = clients * msgs_per_client * batch
    dbs = {}

    def handler_factory(r):
        # what KvbcReplica builds for a deployment: the native kvlog
        # engine at the config's durability defaults, the merkle SKVBC
        # layout, device hashing exactly when the backend is the device
        dbs[r] = open_db(
            os.path.join(workdir, backend, f"replica-{r}.kvlog"),
            sync_writes=False, sync_families=CONSENSUS_META_FAMILIES)
        return SkvbcHandler(
            KeyValueBlockchain(dbs[r],
                               use_device_hashing=(backend == "tpu")),
            merkle=True)

    cluster = InProcessCluster(
        f=1, c=0, num_clients=clients, handler_factory=handler_factory,
        storage_factory=lambda r: DBPersistentStorage(dbs[r]),
        cfg_overrides=dict(crypto_backend=backend,
                           threshold_scheme="multisig-ed25519",
                           client_sig_scheme="ed25519",
                           **(cfg_overrides or {})),
        seed=b"chip-smoke-%d" % seed)
    kvs = [SkvbcClient(cluster.client(c)) for c in range(clients)]
    acked = [0] * clients
    errors = []

    def drive(c: int) -> None:
        try:
            for writes in plan[c]:
                replies = kvs[c].write_batch(writes,
                                             timeout_ms=op_timeout_ms)
                assert all(r.success for r in replies), replies
                acked[c] += len(replies)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    t0 = time.monotonic()
    with cluster:
        threads = [threading.Thread(target=drive, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        write_s = time.monotonic() - t0
        assert sum(acked) == n_writes, (acked, n_writes)

        # a reply quorum is 2f+1: give the last replica time to apply
        chains = [cluster.handlers[r].blockchain for r in range(cluster.n)]
        deadline = time.monotonic() + 120
        while (any(bc.last_block_id != n_writes for bc in chains)
               and time.monotonic() < deadline):
            time.sleep(0.05)
        blocks = {bc.last_block_id for bc in chains}
        heads = {bc.state_digest() for bc in chains}
        roots = {bc.merkle_root("kv") for bc in chains}
        assert blocks == {n_writes}, blocks
        assert len(heads) == 1 and len(roots) == 1, (heads, roots)

        keys = sorted(expected)
        reads = {}
        for i in range(0, len(keys), 256):
            reads.update(kvs[0].read(keys[i:i + 256],
                                     timeout_ms=op_timeout_ms))
        assert reads == expected, "a read did not return the written value"

        def total(name):
            return sum(cluster.metric(r, "counters", name,
                                      component="signature_manager")
                       for r in range(cluster.n))
        counters = {name: total(name) for name in (
            "sigs_device_dispatched", "batched_verifies",
            "scalar_fallbacks", "degraded_verifies")}
        views = [cluster.metric(r, "gauges", "view")
                 for r in range(cluster.n)]
    for db in dbs.values():
        db.close()
    h = hashlib.sha256()
    for k in keys:
        h.update(k + reads[k])
    return {"backend": backend, "writes_acked": sum(acked),
            "blocks": n_writes, "state_root": roots.pop().hex(),
            "keys_read": len(reads), "reads_sha256": h.hexdigest(),
            "views": views, "write_s": round(write_s, 2),
            "wall_s": round(time.monotonic() - t0, 2), **counters}


def served_vs_reference(*, seed: int, clients: int, msgs_per_client: int,
                        batch: int, bulk_writes: int, bulk_keys: int,
                        cfg_overrides=None):
    """The device-backed cluster, then the same traffic on the cpu
    backend with host hashing; the two must agree, the device must have
    done the device's part, and the reference must not have touched it."""
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    sizes = dict(seed=seed, clients=clients, msgs_per_client=msgs_per_client,
                 batch=batch, bulk_writes=bulk_writes, bulk_keys=bulk_keys,
                 cfg_overrides=cfg_overrides, workdir=workdir)
    try:
        before, breaker = kernel_calls(), breaker_snapshot()
        tpu = served("tpu", **sizes)
        during = kernel_calls()
        say(phase="served", **tpu, kernel_calls=during,
            kernel_batches=kernel_batches())
        for kind in ("ed25519", "sha256"):
            assert during.get(kind, 0) > before.get(kind, 0), (kind, during)
        assert tpu["sigs_device_dispatched"] > 0, tpu
        assert tpu["degraded_verifies"] == 0, tpu
        say(phase="served", breaker=assert_breaker_clean(breaker))

        cpu = served("cpu", **sizes)
        say(phase="served", **cpu)
        assert kernel_calls() == during, "the cpu reference used the device"
        for field in ("writes_acked", "blocks", "state_root", "keys_read",
                      "reads_sha256"):
            assert tpu[field] == cpu[field], (field, tpu[field], cpu[field])
        say(phase="served", identical_to_cpu_backend=True,
            writes_acked=tpu["writes_acked"], blocks=tpu["blocks"],
            state_root=tpu["state_root"])
        return tpu, cpu
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


# ---------------------------------------------------------------------
# corpora: seeded, with forged, truncated and duplicate items mixed in
# ---------------------------------------------------------------------

def _spoil(items, rng, forge, truncate, duplicate):
    """items are (msg, sig, pk). Forged: signature over another
    message. Truncated: a short signature. Duplicate: a copy of an
    earlier item."""
    items = list(items)
    idx = rng.sample(range(1, len(items)), forge + truncate + duplicate)
    for i in idx[:forge]:
        msg, sig, pk = items[i]
        items[i] = (msg + b"!", sig, pk)
    for i in idx[forge:forge + truncate]:
        msg, sig, pk = items[i]
        items[i] = (msg, sig[:40], pk)
    for i in idx[forge + truncate:]:
        items[i] = items[i - 1]
    return items


def ed25519_corpus(n: int, principals: int, rng):
    from tpubft.crypto import cpu
    signers = [cpu.Ed25519Signer.generate(seed=rng.randbytes(16))
               for _ in range(principals)]
    items = []
    for i in range(n):
        s = signers[i % principals]
        msg = b"ed25519-%d-" % i + rng.randbytes(24)
        items.append((msg, s.sign(msg), s.public_bytes()))
    k = max(1, n // 300)
    items = _spoil(items, rng, k, k, k) if n > 3 else items
    want = [cpu.Ed25519Verifier(pk).verify(m, s) for m, s, pk in items]
    return items, want


def ecdsa_corpus(curve: str, n: int, rng):
    from tpubft.crypto import cpu
    signers = [cpu.EcdsaSigner.generate(curve, seed=rng.randbytes(16))
               for _ in range(min(n, 32))]
    items = []
    for i in range(n):
        s = signers[i % len(signers)]
        msg = b"ecdsa-%d-" % i + rng.randbytes(24)
        items.append((msg, s.sign(msg), s.public_bytes()))
    items = _spoil(items, rng, 1, 1, 1) if n > 3 else items
    want = [cpu.EcdsaVerifier(pk, curve).verify(m, s) for m, s, pk in items]
    return items, want


def sha256_corpus(n: int, rng, max_len: int = 200):
    msgs = [rng.randbytes(rng.randrange(1, max_len)) for _ in range(n)]
    msgs[0] = b""                                   # truncated to nothing
    for i in rng.sample(range(2, n), max(1, n // 100)):
        msgs[i] = msgs[i - 1]                       # duplicates
    msgs[1] = rng.randbytes(max_len - 1)            # pins the block count
    return msgs, [hashlib.sha256(m).digest() for m in msgs]


def msm_corpus(k: int, n: int, rng):
    """k Lagrange-weighted shares of an n-share threshold signature
    (config 4: k=667 of n=1000) — one identity slot and one repeated
    point among them. The reference is the native host MSM."""
    from tpubft.crypto import bls12381 as ref
    from tpubft.crypto import bls_native
    assert bls_native.available()
    h = ref.hash_to_g1(b"chip-smoke")
    ids = sorted(rng.sample(range(1, n + 1), k))
    points = [bls_native.g1_mul(h, rng.randrange(1, ref.R)) for _ in ids]
    if k > 3:
        points[1] = None
        points[3] = points[2]
    coeffs = ref.lagrange_coeffs_at_zero(ids)
    return points, coeffs, bls_native.g1_msm(points, coeffs)


def _same(kind: str, n: int, got, want) -> None:
    got, want = list(got), list(want)
    assert len(got) == len(want) == n, (kind, len(got), len(want), n)
    bad = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
    assert not bad, f"{kind}@{n}: device and host disagree at {bad[:8]}"


# ---------------------------------------------------------------------
# phase: crypto_plane
# ---------------------------------------------------------------------

def crypto_plane(*, seed: int, ed25519_sizes, ecdsa_n: int, sha_n: int,
                 msm_k: int, msm_n: int, log: CompileLog,
                 curves=("secp256k1", "secp256r1")) -> None:
    """Each kernel kind through its public ops entry (so through
    device_section), verdicts compared elementwise with the host."""
    from tpubft.ops import bls12_381, ecdsa, ed25519, sha256
    rng = random.Random(seed)
    mark, breaker = len(log.rows), breaker_snapshot()
    for n, principals in ed25519_sizes:
        items, want = ed25519_corpus(n, principals, rng)
        t0 = time.monotonic()
        got = ed25519.verify_batch(items)
        dt = time.monotonic() - t0
        _same("ed25519", n, (bool(x) for x in got), want)
        say(phase="crypto_plane", kind="ed25519", n=n,
            principals=principals, accepted=sum(want),
            rejected=n - sum(want), call_s=round(dt, 3))
    for curve in curves:
        items, want = ecdsa_corpus(curve, ecdsa_n, rng)
        t0 = time.monotonic()
        got = ecdsa.rlc_verify_batch(curve, items)
        dt = time.monotonic() - t0
        _same(f"ecdsa.{curve}", ecdsa_n, (bool(x) for x in got), want)
        say(phase="crypto_plane", kind="ecdsa", curve=curve, n=ecdsa_n,
            accepted=sum(want), rejected=ecdsa_n - sum(want),
            call_s=round(dt, 3))
    msgs, want = sha256_corpus(sha_n, rng)
    t0 = time.monotonic()
    got = sha256.sha256_batch_mixed(msgs)
    dt = time.monotonic() - t0
    _same("sha256", sha_n, got, want)
    say(phase="crypto_plane", kind="sha256", n=sha_n, call_s=round(dt, 3))
    points, coeffs, want = msm_corpus(msm_k, msm_n, rng)
    t0 = time.monotonic()
    got = bls12_381.msm(points, coeffs)
    dt = time.monotonic() - t0
    assert got == want, "bls_msm: device and native host MSM disagree"
    say(phase="crypto_plane", kind="bls_msm", k=msm_k, n=msm_n,
        call_s=round(dt, 3))
    calls = kernel_calls()
    for kind in KINDS:
        assert calls.get(kind, 0) > 0, (kind, calls)
    cold = log.cold_since(mark)
    say(phase="crypto_plane", kernel_calls=calls,
        breaker=assert_breaker_clean(breaker), compiled_in_phase=cold)
    assert not cold, f"a kernel compiled inside the phase: {cold}"


# ---------------------------------------------------------------------
# phase: mesh (behind --mesh; four chips)
# ---------------------------------------------------------------------

def mesh_plane(*, seed: int, chips: int, ed25519_n: int, flood_n: int,
               sha_n: int, rlc_n: int, msm_k: int, msm_n: int,
               log: CompileLog, curve: str = "secp256k1") -> None:
    """The mesh tier (ops/dispatch.mesh_launch, parallel/sharding.py)
    against the single-device kernels: each kind runs through its public
    ops entry with the CryptoMesh capped at one chip and then at full
    width; the two answers and the host's must be identical."""
    import jax
    from tpubft.ops import bls12_381, dispatch, ecdsa, ed25519, sha256
    from tpubft.utils import flight
    rng = random.Random(seed)
    breaker = breaker_snapshot()
    mgr = dispatch.crypto_mesh()
    evictions = mgr.snapshot()["evictions"]
    plan = dispatch.mesh_plan()
    assert plan.mesh is not None and plan.n == chips, (plan.n, chips)

    sha_msgs, sha_want = sha256_corpus(sha_n, rng)
    sha_nb = 1 << (max(sha256.blocks_needed(len(m)) for m in sha_msgs)
                   - 1).bit_length()
    compiled = warm(
        mesh_programs(plan, ed25519_batches=[ed25519_n, flood_n],
                      sha_masked=(sha_n, sha_nb), rlc=(curve, rlc_n),
                      msm_points=msm_k)
        + single_device_programs(
            ed25519_batches=[ed25519_n], rlc=[(curve, rlc_n)],
            msm_points=[msm_k], sha_masked=[(sha_n, sha_nb)]), log)
    # the sharded programs really are partitioned over every chip
    for label, exe in compiled.items():
        if ".shard@" in label:
            text = exe.as_text()
            assert f"num_partitions={chips}" in text, label
            if label.startswith("ed25519") and ed25519._use_pallas():
                assert "tpu_custom_call" in text, label
    mark = len(log.rows)

    def both_widths(run):
        t = {}
        mgr.set_shard_count(1)
        try:
            t0 = time.monotonic()
            single = run()
            t["single_s"] = round(time.monotonic() - t0, 3)
        finally:
            mgr.set_shard_count(0)
        t0 = time.monotonic()
        sharded = run()
        t["sharded_s"] = round(time.monotonic() - t0, 3)
        return single, sharded, t

    items, want = ed25519_corpus(ed25519_n, 1000, rng)
    single, sharded, t = both_widths(
        lambda: [bool(x) for x in ed25519.verify_batch(items)])
    _same("ed25519 single", ed25519_n, single, want)
    _same("ed25519 sharded", ed25519_n, sharded, want)
    say(phase="mesh", kind="ed25519", n=ed25519_n, identical=True, **t)

    single, sharded, t = both_widths(
        lambda: sha256.sha256_batch_mixed(sha_msgs))
    _same("sha256 single", sha_n, single, sha_want)
    _same("sha256 sharded", sha_n, sharded, sha_want)
    say(phase="mesh", kind="sha256", n=sha_n, identical=True, **t)

    items, want = ecdsa_corpus(curve, rlc_n, rng)
    single, sharded, t = both_widths(
        lambda: [bool(x) for x in ecdsa.rlc_verify_batch(curve, items)])
    _same("ecdsa single", rlc_n, single, want)
    _same("ecdsa sharded", rlc_n, sharded, want)
    say(phase="mesh", kind="ecdsa", curve=curve, n=rlc_n, identical=True,
        **t)

    points, coeffs, want = msm_corpus(msm_k, msm_n, rng)
    single, sharded, t = both_widths(
        lambda: bls12_381.msm(points, coeffs))
    assert single == sharded == want, "bls_msm: mesh, chip and host differ"
    say(phase="mesh", kind="bls_msm", k=msm_k, identical=True, **t)

    # the product's own verification entry counts its mesh rides
    sharded_verifies = _sig_manager_on_mesh(rng, flood_n)

    snap = mgr.snapshot()
    assert snap["devices"] == snap["healthy"] == chips, snap
    assert not snap["evicted"] and snap["evictions"] == evictions, snap
    profile = flight.kernel_profiler().snapshot()
    for kind in KINDS:
        assert profile.get(f"{kind}.shard", {}).get("calls", 0) > 0, \
            (kind, sorted(profile))
    # ... and left something on every chip, not only the first
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    if None not in peaks:
        assert all(p > 0 for p in peaks), peaks
    cold = log.cold_since(mark)
    say(phase="mesh", mesh=snap, mesh_sharded_verifies=sharded_verifies,
        shard_rows={k: v["calls"] for k, v in profile.items()
                    if k.endswith(".shard")},
        peak_bytes_per_device=peaks,
        breaker=assert_breaker_clean(breaker),
        compiled_in_phase=cold)
    assert not cold, f"a kernel compiled inside the phase: {cold}"


def _sig_manager_on_mesh(rng, flood: int, clients: int = 64) -> int:
    """A SigManager with the device batch backend verifies a flood of
    client signatures; returns its `mesh_sharded_verifies`."""
    per_client = flood // clients
    from tpubft.consensus.keys import ClusterKeys
    from tpubft.consensus.sig_manager import SigManager
    from tpubft.crypto.tpu import verify_batch_mixed
    from tpubft.utils.config import ReplicaConfig
    cfg = ReplicaConfig(num_of_client_proxies=clients)
    keys = ClusterKeys.generate(cfg, clients, seed=rng.randbytes(16))
    items = []
    for c in range(cfg.n_val, cfg.n_val + clients):
        signer = keys.for_node(c).my_signer()
        for j in range(per_client):
            msg = b"req-%d-%d" % (c, j)
            items.append((c, msg, signer.sign(msg)))
    sm = SigManager(keys.for_node(0), batch_fn=verify_batch_mixed,
                    device_min_batch=cfg.device_min_verify_batch)
    assert all(sm.verify_batch(items))
    assert sm.degraded_verifies.value == 0
    assert sm.mesh_sharded_verifies.value == len(items)
    return sm.mesh_sharded_verifies.value


# ---------------------------------------------------------------------
# main: the chip, at full size
# ---------------------------------------------------------------------

# every shape the one-chip phases form at main()'s sizes: admission
# drains of client batches pad to the 1024-lane tile (4096 if a
# retransmission storm piles up) and crypto_plane adds 16,384; a 256-key
# bulk write rehashes 256-wide merkle levels of 65-byte nodes (2
# blocks); sha256_corpus messages run to 4 blocks
ONE_CHIP_SHAPES = dict(
    ed25519_batches=[1000, 4096, 16384],
    rlc=[("secp256k1", 256), ("secp256r1", 256)],
    msm_points=[667], sha_uniform=[(256, 2)], sha_masked=[(1024, 4)])

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: only the mesh tier and its "
                         "single-device comparison")
    ap.add_argument("--seed", type=int, default=21)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU: the default JAX "
                         f"platform here is {dev.platform!r}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    want = 4 if args.mesh else 1
    if device["count"] != want:
        raise SystemExit(f"chip_smoke.py{' --mesh' if args.mesh else ''} "
                         f"runs on {want} chip(s), found {device['count']}")

    from tpubft.utils.jaxcache import setup_cache
    cache_dir = setup_cache()
    # the smoke reports cache hit or miss for EVERY kernel, so even the
    # sub-second sha256 programs are persisted here
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log = CompileLog()
    from tpubft.ops import ed25519
    assert ed25519._use_pallas(), "the fused Pallas kernel was not selected"
    say(phase="start", device=device, compile_cache=cache_dir,
        cache_entries=len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0,
        jax=jax.__version__, ed25519_kernel="pallas-fused")
    t0 = time.monotonic()

    if args.mesh:
        mesh_plane(seed=args.seed, chips=4, ed25519_n=16384, flood_n=512,
                   sha_n=4096, rlc_n=1024, msm_k=667, msm_n=1000, log=log)
    else:
        compiled = warm(single_device_programs(**ONE_CHIP_SHAPES), log)
        # compiled by Mosaic, not interpreted
        assert "tpu_custom_call" in compiled["ed25519@1024"].as_text()
        # four replicas share this process (and its interpreter lock):
        # a 256-key merkle write costs the co-located cluster ~10 s, well
        # past the 4 s view-change timer sized for a replica per host.
        # The timer is liveness tuning, not a guarantee; quorums,
        # durability and device_min_verify_batch stay at their defaults.
        served_vs_reference(seed=args.seed, clients=8, msgs_per_client=4,
                            batch=64, bulk_writes=1, bulk_keys=256,
                            cfg_overrides={"view_change_timer_ms": 60000})
        crypto_plane(seed=args.seed,
                     ed25519_sizes=[(1000, 1000), (16384, 1000)],
                     ecdsa_n=256, sha_n=1024, msm_k=667, msm_n=1000,
                     log=log)
    say(phase="done", wall_s=round(time.monotonic() - t0, 1),
        compiles=len(log.rows),
        compile_or_load_s=round(sum(r[1] for r in log.rows), 1),
        cache_hits=sum(1 for r in log.rows if r[2]))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
