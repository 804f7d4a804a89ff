"""TPU-backed crypto plugin implementations.

This is the backend the whole project exists for: the reference verifies
every signature one-at-a-time on CPU threads behind its plugin boundaries
(`IVerifier` — util/include/crypto_utils.hpp:41-55, consumed by
SigManager.cpp:197; `IThresholdVerifier`/`IThresholdAccumulator` —
threshsign/include/threshsign/IThresholdVerifier.h:23,
IThresholdAccumulator.h:22). Here the same boundaries are implemented by
batched JAX kernels:

  * TpuEd25519Verifier       — per-principal IVerifier over the windowed
                               batch kernel (tpubft/ops/ed25519.py);
  * verify_batch_items       — cross-principal one-kernel-call batch used
                               by SigManager.verify_batch (the PrePrepare
                               client-sig flood path);
  * TpuMultisigEd25519Verifier — combined-multisig verification as ONE
                               device batch instead of k sequential share
                               verifies;
  * TpuBlsThresholdVerifier  — BLS threshold accumulator whose combine
                               runs the Lagrange+MSM on device
                               (tpubft/ops/bls12_381.py), the counterpart
                               of fastMultExp (FastMultExp.cpp:27).

Selected via ReplicaConfig.crypto_backend == "tpu"; everything constructs
through the same factories as the CPU backend, so consensus code never
branches on the backend.
"""
from __future__ import annotations

import struct
from typing import Dict, List, Optional, Sequence, Tuple

from tpubft.crypto import bls12381 as bls
from tpubft.crypto.interfaces import IVerifier
from tpubft.crypto.systems import (BlsMultisigVerifier,
                                   BlsThresholdAccumulator,
                                   BlsThresholdVerifier,
                                   MultisigEd25519Verifier)
from tpubft.ops.dispatch import device_tier


def verify_batch_items(items: Sequence[Tuple[bytes, bytes, bytes]]
                       ) -> List[bool]:
    """One kernel call over ed25519 (pubkey, data, sig) triples —
    principals may all differ. Used by the multisig share paths (replica
    shares are always the replica scheme)."""
    from tpubft.ops import ed25519 as ops
    return [bool(x) for x in
            ops.verify_batch([(d, s, pk) for pk, d, s in items])]


import functools


@functools.lru_cache(maxsize=1)
def _platform_default_crossover() -> int:
    """Platform half of the crossover default — the expensive
    jax.devices() probe cannot change after process start, so it
    resolves once."""
    import jax
    return 1 if jax.devices()[0].platform != "cpu" else 1 << 30


# runtime override of the ECDSA device/host crossover — the autotuner's
# actuator (tpubft/tuning/wiring.py drives it from measured `ecdsa`
# kernel batch stats vs the batched-host timing counters). Process-wide
# like the device itself: all replicas of one process share one
# accelerator, so the last-configured value wins (same doctrine as the
# breaker's configure()). None = fall through to the env knob/platform
# default below.
_crossover_override: Optional[int] = None


def set_ecdsa_crossover(b: Optional[int]) -> None:
    """Set (or with None, clear) the runtime ECDSA device/host
    crossover. Takes precedence over TPUBFT_ECDSA_CROSSOVER_B."""
    global _crossover_override
    _crossover_override = None if b is None else max(1, int(b))


def ecdsa_crossover() -> int:
    """The effective crossover (override > env > platform default) —
    the autotuner seeds its knob default from this. The static tiers
    (env/platform) scale DOWN by the healthy mesh width: d chips
    amortize the RLC launch at ~1/d the batch, so the device tier wins
    sooner. The autotuner override is exempt — its policy already
    measures the mesh-backed per-item cost, so dividing again would
    double-count the mesh."""
    base = _ecdsa_device_crossover()
    if _crossover_override is not None or base <= 1:
        return base
    from tpubft.ops import dispatch
    return max(1, base // max(1, dispatch.mesh_shards()))


def _ecdsa_device_crossover() -> int:
    """Minimum ECDSA sub-batch size that rides the device RLC kernel;
    smaller groups verify through the batched host engine
    (crypto/scalar.ecdsa_verify_batch). The runtime override (autotuner)
    wins, then TPUBFT_ECDSA_CROSSOVER_B as exported by
    `benchmarks/bench_msm_crossover.py --ecdsa` (env read stays
    per-call: tests flip it at runtime); unset, the default prefers the
    device on an accelerator and the batched host on XLA-CPU (where the
    kernel is ~100x slower than the comb walk — BENCH_r05.json, a
    CPU-host row: 30-34/s)."""
    import os
    if _crossover_override is not None:
        return _crossover_override
    v = os.environ.get("TPUBFT_ECDSA_CROSSOVER_B")
    if v is not None:
        try:
            return int(v)
        except ValueError:
            # a malformed knob must not poison every verify batch (the
            # caller's degrade-never-fail wrapper would reroute forever
            # with only a cryptic per-batch traceback)
            import logging
            logging.getLogger("tpubft.crypto").warning(
                "ignoring non-integer TPUBFT_ECDSA_CROSSOVER_B=%r", v)
    return _platform_default_crossover()


def verify_batch_mixed(items: Sequence[Tuple[str, bytes, bytes, bytes]]
                       ) -> List[bool]:
    """SigManager's cross-principal batch entry: (scheme, pubkey, data,
    sig) tuples, one device dispatch per scheme present. This is how the
    secp256k1/P-256 client-auth mix of BASELINE configs 3/5 rides the
    device: EdDSA through the windowed ed25519 kernel, ECDSA through the
    RLC batch kernel (tpubft/ops/ecdsa.rlc_verify_batch — one MSM-shaped
    launch per flush, the batched counterpart of the reference's
    per-message ECDSAVerifier, crypto_utils.hpp:57-73). ECDSA groups
    below the measured device crossover verify on the batched host
    engine instead of paying a losing kernel dispatch."""
    groups = {}
    for i, (scheme, pk, data, sig) in enumerate(items):
        groups.setdefault(scheme, []).append(i)
    out = [False] * len(items)
    for scheme, idxs in groups.items():
        sub = [items[i] for i in idxs]
        if scheme == "ed25519":
            verdicts = verify_batch_items([(pk, d, s)
                                           for _, pk, d, s in sub])
        elif scheme in ("ecdsa-secp256k1", "secp256k1",
                        "ecdsa-secp256r1", "secp256r1", "ecdsa-p256"):
            curve = ("secp256k1" if "k1" in scheme else "secp256r1")
            if len(sub) >= ecdsa_crossover():
                from tpubft.ops import ecdsa as ops_ecdsa
                rlc_items = [(d, s, pk) for _, pk, d, s in sub]

                def _local_rlc(items=rlc_items, curve=curve):
                    return [bool(x) for x in
                            ops_ecdsa.rlc_verify_batch(curve, items)]
                # offload tier first: a helper eats the verdict storm,
                # the replica pays ONE re-fold launch instead of the
                # bisection descent; None = no lease (pool inactive /
                # at capacity / helpers down) -> local path unchanged
                from tpubft.offload import pool as offload
                verdicts = offload.ecdsa_via_offload(curve, rlc_items,
                                                     _local_rlc)
                if verdicts is None:
                    verdicts = _local_rlc()
            else:
                from tpubft.crypto import scalar as _scalar
                verdicts = _scalar.ecdsa_verify_batch(
                    [(pk, d, s) for _, pk, d, s in sub], curve)
        else:                       # unknown scheme: CPU fallback
            from tpubft.crypto.cpu import make_verifier
            verdicts = []
            for _, pk, d, s in sub:
                try:
                    verdicts.append(make_verifier(scheme, pk).verify(d, s))
                except Exception:
                    verdicts.append(False)
        for i, ok in zip(idxs, verdicts):
            out[i] = ok
    return out


class TpuEd25519Verifier(IVerifier):
    """IVerifier bound to one public key, batch-first. Single verify() is
    a batch of one (pays one device dispatch — callers on the hot path go
    through SigManager.verify_batch / BatchVerifier instead)."""

    def __init__(self, public_key_bytes: bytes):
        self.public_key_bytes = public_key_bytes

    def verify(self, data: bytes, sig: bytes) -> bool:
        return self.verify_batch([(data, sig)])[0]

    def verify_batch(self, items: Sequence[Tuple[bytes, bytes]]
                     ) -> List[bool]:
        try:
            with device_tier("ed25519"):
                from tpubft.ops import ed25519 as ops
                return [bool(x) for x in ops.verify_batch(
                    [(d, s, self.public_key_bytes) for d, s in items])]
        except Exception:  # noqa: BLE001 — device loss (or an OPEN
            # breaker fast-fail) degrades to the host verifier; the
            # breaker recorded it (_device_tier)
            from tpubft.crypto.cpu import make_verifier
            v = make_verifier("ed25519", self.public_key_bytes)
            return [v.verify(d, s) for d, s in items]

    @property
    def signature_length(self) -> int:
        return 64


class TpuMultisigEd25519Verifier(MultisigEd25519Verifier):
    """Multisig verifier whose combined-signature check and bad-share
    identification run as one device batch (k shares -> one dispatch).
    Below `min_device_batch` shares the check stays on the CPU verifiers:
    a k=3 certificate is latency-critical and too small to amortize a
    device dispatch."""

    def __init__(self, threshold: int, total: int,
                 share_public_keys: Sequence[bytes],
                 min_device_batch: int = 1):
        super().__init__(threshold, total, share_public_keys)
        self._share_pk_bytes = list(share_public_keys)
        self.min_device_batch = min_device_batch

    def verify(self, data: bytes, sig: bytes) -> bool:
        if self.threshold < self.min_device_batch:
            return super().verify(data, sig)
        entries = self._parse_vector(data, sig)
        if entries is None:
            return False
        try:
            with device_tier("ed25519"):
                return all(verify_batch_items(entries))
        except Exception:  # noqa: BLE001 — device loss: the host
            # multisig check is byte-identical, just serial
            return super().verify(data, sig)

    def verify_share_batch(self, items: Sequence[Tuple[int, bytes, bytes]]
                           ) -> List[bool]:
        """[(share_id, data, share)] -> verdicts, one device dispatch."""
        if len(items) < self.min_device_batch:
            return [self.verify_share(i, d, s) for i, d, s in items]
        entries = []
        ok_shape = []
        for share_id, data, share in items:
            if 1 <= share_id <= self.total_signers:
                entries.append((self._share_pk_bytes[share_id - 1], data,
                                share))
                ok_shape.append(True)
            else:
                ok_shape.append(False)
        try:
            with device_tier("ed25519"):
                verdicts = iter(verify_batch_items(entries))
        except Exception:  # noqa: BLE001 — degrade to per-share host
            return [self.verify_share(i, d, s) for i, d, s in items]
        return [next(verdicts) if shaped else False for shaped in ok_shape]

    def verify_batch_certs(self, items) -> List[bool]:
        """Cross-cert batching for the multisig vector: every cert's
        share signatures across the whole flush verify in ONE ed25519
        device batch (k_1+...+k_m sigs, one dispatch) instead of m
        sequential k-verify loops. Presence of this override routes
        multisig certs through the replica's CertBatchVerifier."""
        parsed: List[Optional[List[Tuple[bytes, bytes, bytes]]]] = []
        entries: List[Tuple[bytes, bytes, bytes]] = []
        for data, sig in items:
            one = self._parse_vector(data, sig)
            parsed.append(one)
            if one is not None:
                entries.extend(one)
        if not entries:
            return [False] * len(items)
        if len(entries) < self.min_device_batch:
            # a near-empty flush is latency-critical and too small to
            # amortize a dispatch: host loop (same doctrine as verify)
            return [self.verify(d, s) for d, s in items]
        try:
            with device_tier("ed25519"):
                verdicts = iter(verify_batch_items(entries))
        except Exception:  # noqa: BLE001 — device loss: serial host check
            return [self.verify(d, s) for d, s in items]
        out = []
        for one in parsed:
            if one is None:
                out.append(False)
            else:
                # materialize BEFORE all(): a short-circuit would leave
                # this cert's unconsumed verdicts on the shared iterator
                # and misattribute them to every later cert in the flush
                vs = [next(verdicts) for _ in one]
                out.append(all(vs))
        return out

    def _parse_vector(self, data: bytes, sig: bytes
                      ) -> Optional[List[Tuple[bytes, bytes, bytes]]]:
        """Structural multisig-vector checks (threshold met, unique
        in-range signers, exact length) -> (pk, data, share) entries,
        or None when the vector can't be valid. Mirrors
        MultisigEd25519Verifier.verify's parse exactly."""
        try:
            (k,) = struct.unpack_from("<H", sig, 0)
            if k < self.threshold:
                return None
            off = 2
            entries = []
            seen = set()
            for _ in range(k):
                (i,) = struct.unpack_from("<H", sig, off)
                off += 2
                share = sig[off:off + 64]
                off += 64
                if i in seen or not 1 <= i <= self.total_signers:
                    return None
                seen.add(i)
                entries.append((self._share_pk_bytes[i - 1], data, share))
            if off != len(sig):
                return None
            return entries
        except (struct.error, IndexError):
            return None

    def combine_batch(self, jobs) -> List[Tuple[bool, bytes, List[int]]]:
        """Fused cross-slot combine for the multisig vector: combining
        is concatenation (host, trivial) — the cost is verification, so
        every job's shares across the flush ride ONE ed25519 device
        batch. Verdicts (including bad-share identification and its
        dict-order listing) are identical to the per-job loop."""
        entries = []
        index = []                     # (job, sid) per entry
        for j, (digest, shares) in enumerate(jobs):
            for sid in shares:         # dict order, like the accumulator
                if 1 <= sid <= self.total_signers:
                    entries.append((self._share_pk_bytes[sid - 1], digest,
                                    shares[sid]))
                    index.append((j, sid))
        if len(entries) < self.min_device_batch:
            return super().combine_batch(jobs)   # host loop (see verify)
        try:
            with device_tier("ed25519"):
                flat = verify_batch_items(entries) if entries else []
        except Exception:  # noqa: BLE001 — device loss: per-job host loop
            return super().combine_batch(jobs)
        ok_by_job: List[Dict[int, bool]] = [{} for _ in jobs]
        for (j, sid), good in zip(index, flat):
            ok_by_job[j][sid] = bool(good)
        out: List[Tuple[bool, bytes, List[int]]] = []
        for j, (digest, shares) in enumerate(jobs):
            verdicts = ok_by_job[j]
            chosen = sorted(shares)[: self.threshold]
            ok = (len(chosen) >= self.threshold
                  and all(verdicts.get(sid, False) for sid in chosen))
            if ok:
                from tpubft.crypto.systems import pack_multisig_vector
                out.append((True, pack_multisig_vector(chosen, shares),
                            []))
            else:
                out.append((False, b"", [sid for sid in shares
                                         if not verdicts.get(sid, False)]))
        return out


class TpuBlsThresholdAccumulator(BlsThresholdAccumulator):
    """BLS accumulator combining on device: Lagrange coefficients on host
    (tiny), the [λ_i]·share_i MSM on the TPU (ops/bls12_381.msm) — the
    role of fastMultExp in BlsThresholdAccumulator.cpp:42-56.

    Combine-path selection is by quorum size: below the measured
    crossover (TPUBFT_MSM_CROSSOVER_K, benchmarks/bench_msm_crossover.py)
    the host Pippenger MSM beats a device dispatch, so small quorums stay
    on the CPU path even under the tpu backend."""

    def get_full_signed_data(self) -> bytes:
        import os
        k = self._verifier.threshold
        crossover = int(os.environ.get("TPUBFT_MSM_CROSSOVER_K", "128"))
        shares = self._shares           # decodes what `add` kept
        if len(shares) < crossover and k < crossover:
            return super().get_full_signed_data()
        self._flush_decompress_span()
        try:
            with device_tier("bls_msm"):
                from tpubft.ops import bls12_381 as dev
                ids = sorted(shares)[:k]
                # shares are affine (x, y) int tuples — the device MSM's
                # native input
                combined = dev.combine_shares(
                    ids, [shares[i] for i in ids])
                return bls.g1_compress(combined)
        except Exception:  # noqa: BLE001 — device loss: the host
            # Pippenger combine produces the identical signature
            return super().get_full_signed_data()


class TpuBlsThresholdVerifier(BlsThresholdVerifier):
    def new_accumulator(self, with_share_verification: bool
                        ) -> TpuBlsThresholdAccumulator:
        return TpuBlsThresholdAccumulator(self, with_share_verification)

    def _combine_segments(self, segments, digests=None) -> List:
        """Fused-combine with backend tiering: offload (leased to a
        verified helper, ISSUE 20) -> device -> host. A lease only
        happens when the pool is active AND the caller supplied the
        slot digests the soundness check binds to; any failed/evicted
        lease re-runs on the local tiers inside this same call, so the
        returned points are byte-identical with offload on or off."""
        if digests is not None:
            from tpubft.offload import pool as offload
            leased = offload.combine_via_offload(
                segments, digests, self._master_pk,
                lambda: self._combine_segments_local(segments))
            if leased is not None:
                return leased
        return self._combine_segments_local(segments)

    def _combine_segments_local(self, segments) -> List:
        """Device path: every slot's Lagrange-weighted MSM in ONE
        segmented `msm_batch_kernel` launch (combine_batch's whole
        flush pays one `bls_msm` dispatch instead of one per slot).
        Below the measured crossover the host Pippenger path wins even
        fused — same knob as the per-slot accumulator."""
        import os
        total = sum(len(ids) for ids, _ in segments)
        crossover = int(os.environ.get("TPUBFT_MSM_CROSSOVER_K", "128"))
        # a fused flush amortizes the dispatch across all segments, so
        # it clears the crossover on the SUM of shares, not per slot
        if total < crossover or not any(ids for ids, _ in segments):
            return super()._combine_segments(segments)
        try:
            with device_tier("bls_msm"):
                from tpubft.ops import bls12_381 as dev
                return dev.combine_shares_batch(
                    [(ids, pts) for ids, pts in segments])
        except Exception:  # noqa: BLE001 — device loss: the host
            # per-segment combine produces identical signatures
            return super()._combine_segments(segments)


class TpuBlsMultisigVerifier(BlsMultisigVerifier):
    """Multisig-BLS with the unweighted sums on device: every segment's
    Σ share_i rides the SAME segmented multi-MSM kernel the threshold
    scheme's Lagrange combine uses (`ops/bls12_381.msm_batch` under
    `device_section("bls_msm")`), with all-ones scalars — a new call
    shape, not a new kernel. Serves both the fused `combine_batch` flush
    (root of the aggregation overlay) and `aggregate_partials` (interior
    nodes), so one flush is one launch in both roles."""

    def _sum_segments(self, segments, meta=None) -> List:
        if meta is not None and any(m is not None for m in meta):
            from tpubft.offload import pool as offload
            leased = offload.sum_via_offload(
                segments, meta, self,
                lambda: self._sum_segments_local(segments))
            if leased is not None:
                return leased
        return self._sum_segments_local(segments)

    def _sum_segments_local(self, segments) -> List:
        import os
        total = sum(len(pts) for pts in segments)
        crossover = int(os.environ.get("TPUBFT_MSM_CROSSOVER_K", "128"))
        # fused flush: clear the crossover on the SUM across segments
        if total < crossover or not any(segments):
            return super()._sum_segments(segments)
        try:
            live = [i for i, pts in enumerate(segments) if pts]
            with device_tier("bls_msm"):
                from tpubft.ops import bls12_381 as dev
                sums = dev.msm_batch(
                    [(segments[i], [1] * len(segments[i]))
                     for i in live])
            out = [None] * len(segments)
            for i, pt in zip(live, sums):
                out[i] = pt
            return out
        except Exception:  # noqa: BLE001 — device loss: the host
            # sequential sums produce identical points
            return super()._sum_segments(segments)


def make_threshold_verifier(type_name: str, threshold: int, total: int,
                            public_key, share_public_keys,
                            min_device_batch: int = 1):
    """TPU-flavored counterpart of Cryptosystem.create_threshold_verifier
    (ThresholdSignaturesTypes.cpp:183): same key material, device-backed
    verification."""
    if type_name == "multisig-ed25519":
        return TpuMultisigEd25519Verifier(threshold, total,
                                          share_public_keys,
                                          min_device_batch)
    if type_name == "threshold-bls":
        return TpuBlsThresholdVerifier(threshold, total, public_key,
                                       share_public_keys)
    if type_name == "multisig-bls":
        return TpuBlsMultisigVerifier(threshold, total, share_public_keys)
    raise ValueError(f"no TPU backend for cryptosystem {type_name!r}")
