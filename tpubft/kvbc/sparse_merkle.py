"""Sparse Merkle tree over a 256-bit key space.

Rebuild of the reference's sparse_merkle::Tree
(/root/reference/kvbc/src/sparse_merkle/tree.cpp, internal_node.cpp) with a
TPU-first update path: instead of nibble-batched internal nodes walked one
at a time, updates are applied as a *batch per level* — all changed nodes
of a level are rehashed in one call, which routes through the batched
SHA-256 kernel (tpubft/ops/sha256.py) once the level is wide enough to
amortize device dispatch.

Layout: key -> path = SHA-256(key), 256 levels. Only non-default nodes are
persisted (family `smt`); empty subtrees hash to precomputed defaults.
Leaf hash = H(0x00 || path || value_hash); inner = H(0x01 || l || r).

Versioning (reference tree.cpp is versioned; internal_node.cpp tracks
stale nodes): the LATEST state mutates in place — the hot path reads and
writes exactly one row per node, no version walk. Every node change is
additionally appended to an archive family keyed `node_key || version`
(version = block id), so `prove_at(key, version)` can rebuild the audit
path of any retained block by taking, per node, the newest archive row
at or below that version (absence = default subtree — any older change
would have been archived). `prune_versions(before)` is the stale-node
GC: it drops archive rows superseded before the retention point, exactly
the role of the reference's stale-node index.

Reads: a node that hashes to its depth's default is deleted, never
stored, so where a changed leaf's path has no stored node the subtree
under it is empty and `update_batch` takes every sibling below that
depth as the default without asking the engine. This is no cache: the
depth is found anew on every call through the tree's own read view (so
a run's staged rows and the pending store are seen as ever), and
nothing is remembered between calls.

Writes: a batch too narrow for the device tier (fewer changed leaves
than `_DEVICE_THRESHOLD`) is walked by one native call
(tpubft/native/smtwalk.cpp) that returns its rows already in the
engine's wire encoding; the write batch carries them to the log as they
are. The rows, their order and their bytes are the level loop's.
"""
from __future__ import annotations

import ctypes
import hashlib
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from tpubft.native.build import load
from tpubft.storage.interfaces import (EncodedRows, IDBClient, WriteBatch,
                                       family_prefix)
from tpubft.utils.metrics import Component

DEPTH = 256
_EMPTY = b"\x00" * 32

# default (empty-subtree) hash per depth: _DEFAULTS[256] = empty leaf,
# _DEFAULTS[d] = H(0x01 || _DEFAULTS[d+1] || _DEFAULTS[d+1])
_DEFAULTS: List[bytes] = [b""] * (DEPTH + 1)
_DEFAULTS[DEPTH] = _EMPTY
for _d in range(DEPTH - 1, -1, -1):
    _DEFAULTS[_d] = hashlib.sha256(
        b"\x01" + _DEFAULTS[_d + 1] + _DEFAULTS[_d + 1]).digest()

# below this many nodes in a level, hashlib beats device dispatch: a
# batch of fewer changed leaves has no level that wide, and its walk is
# the native one (tpubft/native/smtwalk.cpp)
_DEVICE_THRESHOLD = 192

# what update_batch asked of the engine, process-wide (every tree of every
# ledger in the process): leaves changed, how many of them the native walk
# carried, how many of them were already stored (an overwrite or delete:
# the empty depth is DEPTH + 1 and every sibling is read), node reads
# issued (the probes for the empty depth and the sibling reads), how many
# of those went to a store whose point reads keep the interpreter lock
# (`IDBClient.point_reads_keep_lock`), and sibling lookups the empty
# depth answered with no read. Totals only — nothing here is read back
# by the tree.
METRICS = Component("kvbc")
_M_KEYS = METRICS.register_counter("smt_keys_updated")
_M_NATIVE = METRICS.register_counter("smt_keys_native")
_M_OVERWRITTEN = METRICS.register_counter("smt_keys_overwritten")
_M_ENGINE_READS = METRICS.register_counter("smt_engine_reads")
_M_READS_LOCK_KEPT = METRICS.register_counter("smt_engine_reads_lock_kept")
_M_BOUNDED = METRICS.register_counter("smt_siblings_bounded")

_U8P = ctypes.POINTER(ctypes.c_uint8)
_U32P = ctypes.POINTER(ctypes.c_uint32)


def _lib():
    lib = load("smtwalk")
    if getattr(lib, "_smtwalk_typed", False):
        return lib
    lib.smt_walk.restype = ctypes.c_int
    lib.smt_walk.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int32),
        ctypes.c_uint32,                                  # the leaves
        ctypes.c_char_p, ctypes.c_uint32, ctypes.c_char_p,
        ctypes.c_uint32,                                  # the siblings
        ctypes.c_uint64, ctypes.c_char_p, _U32P,          # version, families
        ctypes.c_char_p, _U32P,                           # root, defaults
        ctypes.POINTER(_U8P), _U32P, ctypes.POINTER(_U32P), _U32P]
    # free() is over before another thread could use the interpreter
    # lock: called through a handle that keeps it
    lib.free = ctypes.PyDLL(lib._name).smt_free
    lib.free.argtypes = [ctypes.c_void_p]
    lib.free.restype = None
    lib._smtwalk_typed = True
    return lib


def _hash_level(messages: Sequence[bytes], use_device: bool) -> List[bytes]:
    if use_device and len(messages) >= _DEVICE_THRESHOLD:
        try:
            from tpubft.ops.dispatch import device_tier
            from tpubft.ops.sha256 import sha256_batch
            with device_tier("sha256"):
                return sha256_batch(messages)
        except Exception:  # noqa: BLE001 — device loss (or an OPEN
            # circuit breaker fast-fail) degrades to hashlib: digests
            # are byte-identical, a Merkle update must never die with
            # the accelerator
            pass
    return [hashlib.sha256(m).digest() for m in messages]


def _leaf_hash(path: bytes, value_hash: bytes) -> bytes:
    return hashlib.sha256(b"\x00" + path + value_hash).digest()


def _node_key(depth: int, path_bits: int) -> bytes:
    """Physical key: depth (2B big-endian) + the leading `depth` bits."""
    nbytes = (depth + 7) // 8
    return depth.to_bytes(2, "big") + (
        (path_bits << (nbytes * 8 - depth)).to_bytes(nbytes, "big")
        if depth else b"")


@dataclass
class Proof:
    """Audit path, compressed: bitmap marks levels whose sibling is
    non-default; `siblings` lists only those, bottom (depth 256) first."""
    bitmap: bytes                    # 32 bytes, bit i = level DEPTH - i
    siblings: List[bytes]


class SparseMerkleTree:
    def __init__(self, db: IDBClient, family: bytes = b"smt",
                 use_device: bool = True) -> None:
        self._db = db
        self._family = family
        self._leaf_family = family + b".leaf"
        self._arch_family = family + b".arch"        # node_key+ver8 -> hash
        self._leaf_arch_family = family + b".leafarch"  # path+ver8 -> vh
        self._use_device = use_device
        # physical-key prefixes of the four families: update_batch
        # composes its rows' keys itself and stages them in one call
        self._pre, self._leaf_pre, self._arch_pre, self._leaf_arch_pre = (
            family_prefix(f) for f in (self._family, self._leaf_family,
                                       self._arch_family,
                                       self._leaf_arch_family))
        # the native walk (built here if this checkout has not yet:
        # NativeBuildError now, not at the first write), and the
        # prefixes as it takes them
        self._lib = _lib()
        prefixes = (self._pre, self._leaf_pre, self._arch_pre,
                    self._leaf_arch_pre)
        self._prefix_blob = b"".join(prefixes)
        self._prefix_lens = (ctypes.c_uint32 * 4)(*map(len, prefixes))

    def _row_families(self, version: int) -> Tuple[bytes, ...]:
        """Family prefixes of the rows a walk stages at `version`."""
        live = (self._pre, self._leaf_pre)
        return live + (self._arch_pre, self._leaf_arch_pre) \
            if version > 0 else live

    # ---- reads ----
    # Reads go straight to the DB (no node cache): staged-but-uncommitted
    # updates must never be observable, and an aborted block must leave no
    # residue — the DB's batch atomicity is the single source of truth.
    def _node(self, depth: int, path_bits: int) -> bytes:
        v = self._db.get(_node_key(depth, path_bits), self._family)
        return v if v is not None else _DEFAULTS[depth]

    def root(self) -> bytes:
        return self._node(0, 0)

    def get_value_hash(self, key: bytes) -> Optional[bytes]:
        path = hashlib.sha256(key).digest()
        return self._db.get(path, self._leaf_family)

    # ---- batch update ----
    def update_batch(self, updates: Dict[bytes, Optional[bytes]],
                     batch: Optional[WriteBatch] = None,
                     version: int = 0) -> bytes:
        """Apply {key: value_hash or None(delete)}; returns the new root.
        If `batch` is given, node writes are staged into it (caller
        commits atomically with the block); otherwise committed here.
        `version` (the block id) > 0 additionally archives every changed
        node so `prove_at` can serve this version later.

        The walk is chosen from the batch alone: fewer changed leaves
        than the device tier's narrowest level take the native walk,
        which returns the rows already encoded; a wider batch takes the
        level loop, whose levels `_hash_level` may send to the device.
        Both read the same nodes and stage the same rows in the same
        order."""
        if not updates:
            return self.root()
        own_batch = batch is None
        wb = WriteBatch() if own_batch else batch
        walk = (self._walk_native if len(updates) < _DEVICE_THRESHOLD
                else self._walk_levels)
        root = walk(updates, wb, version)
        _M_KEYS.inc(len(updates))
        if own_batch:
            self._db.write(wb)
        return root

    def _walk_native(self, updates: Dict[bytes, Optional[bytes]],
                     wb: WriteBatch, version: int) -> bytes:
        """The walk for a narrow batch. What only the tree can do stays
        here: hash each key to its path, find the path's empty depth and
        read, through the tree's own read view, the siblings at or above
        it (below it every sibling is the default, unread). One native
        call then hashes the changed nodes up to the root and returns
        the walk's rows in the engine's wire encoding."""
        paths: List[bytes] = []
        lens: List[int] = []
        on_path = set()               # (depth, bits) that may be stored
        reads = stored = 0
        for key, vh in updates.items():
            path = hashlib.sha256(key).digest()
            bits = int.from_bytes(path, "big")
            empty, probes = self._empty_depth(bits)
            reads += probes
            stored += empty > DEPTH
            paths.append(path)
            lens.append(-1 if vh is None else len(vh))
            for depth in range(1, min(empty, DEPTH) + 1):
                on_path.add((depth, bits >> (DEPTH - depth)))
        # a changed node's sibling is read unless it is changed too; two
        # leaves that share a node share its path from there up, so
        # their empty depths agree wherever either still decides
        # anything. Deepest level first, ascending within a level: the
        # order in which the walk meets them
        siblings = sorted({(-depth, bits ^ 1) for depth, bits in on_path
                           if (depth, bits ^ 1) not in on_path})
        get, family = self._db.get, self._family
        sib_keys: List[bytes] = []
        sib_vals: List[bytes] = []
        for neg_depth, bits in siblings:
            k = _node_key(-neg_depth, bits)
            v = get(k, family)
            sib_keys.append(k)
            sib_vals.append(v if v is not None else _DEFAULTS[-neg_depth])
        reads += len(siblings)

        lib = self._lib
        n = len(paths)
        keys_blob = b"".join(sib_keys)
        root = ctypes.create_string_buffer(32)
        defaulted, payload_len, n_rows = (ctypes.c_uint32(),
                                          ctypes.c_uint32(),
                                          ctypes.c_uint32())
        payload, index = _U8P(), _U32P()
        rc = lib.smt_walk(
            b"".join(paths), b"".join(v for v in updates.values() if v),
            (ctypes.c_int32 * n)(*lens), n,
            keys_blob, len(keys_blob), b"".join(sib_vals), len(siblings),
            max(version, 0), self._prefix_blob, self._prefix_lens,
            root, ctypes.byref(defaulted),
            ctypes.byref(payload), ctypes.byref(payload_len),
            ctypes.byref(index), ctypes.byref(n_rows))
        if rc != 0:
            raise RuntimeError(f"smt_walk rc={rc}")
        try:
            rows = EncodedRows(
                ctypes.string_at(payload, payload_len.value),
                ctypes.string_at(index, 16 * n_rows.value),
                self._row_families(version))
        finally:
            lib.free(payload)
            lib.free(index)
        wb.extend_encoded(rows)
        _M_NATIVE.inc(n)
        _M_OVERWRITTEN.inc(stored)
        self._count_reads(reads)
        _M_BOUNDED.inc(defaulted.value)
        return root.raw

    def _walk_levels(self, updates: Dict[bytes, Optional[bytes]],
                     wb: WriteBatch, version: int) -> bytes:
        """The walk for a batch wide enough for the device tier: one
        `_hash_level` call a level."""
        ver = version.to_bytes(8, "big") if version > 0 else None

        # leaf level. Each leaf's empty depth is taken here, before any
        # node row is staged: `wb` may mirror into the read view, and
        # the walk stages a row for every node of the path
        changed: Dict[int, bytes] = {}
        bound: Dict[int, int] = {}
        reads = bounded = stored = 0
        # every row of the walk, in the order the engine gets them
        rows: List[Tuple[bytes, Optional[bytes]]] = []
        for key, vh in updates.items():
            path = hashlib.sha256(key).digest()
            bits = int.from_bytes(path, "big")
            bound[bits], probes = self._empty_depth(bits)
            reads += probes
            stored += bound[bits] > DEPTH
            changed[bits] = _EMPTY if vh is None else _leaf_hash(path, vh)
            rows.append((self._leaf_pre + path, vh))
            if ver is not None:
                rows.append((self._leaf_arch_pre + path + ver,
                             vh if vh is not None else b""))
        self._level_rows(rows, DEPTH, changed, ver)

        # ascend, rehashing all changed nodes of each level in one batch.
        # A sibling that is not changed hangs from a changed node's path:
        # below that path's empty depth it is the default, unread
        for depth in range(DEPTH, 0, -1):
            parents = sorted({bits >> 1 for bits in changed})
            msgs = []
            up: Dict[int, int] = {}
            for pb in parents:
                lb, rb = pb << 1, (pb << 1) | 1
                left, right = changed.get(lb), changed.get(rb)
                if left is not None and right is not None:
                    # two changed children share their path from the
                    # parent up, so their bounds agree wherever either
                    # still decides anything
                    empty = min(bound[lb], bound[rb])
                else:
                    empty = bound[rb if left is None else lb]
                    if depth > empty:
                        sibling = _DEFAULTS[depth]
                        bounded += 1
                    else:
                        sibling = self._node(depth,
                                             lb if left is None else rb)
                        reads += 1
                    if left is None:
                        left = sibling
                    else:
                        right = sibling
                up[pb] = empty
                msgs.append(b"\x01" + left + right)
            hashes = _hash_level(msgs, self._use_device)
            changed = dict(zip(parents, hashes))
            bound = up
            self._level_rows(rows, depth - 1, changed, ver)
        wb.extend(rows, self._row_families(version))
        _M_OVERWRITTEN.inc(stored)
        self._count_reads(reads)
        _M_BOUNDED.inc(bounded)
        return changed[0]

    def _count_reads(self, reads: int) -> None:
        """One walk's node reads, once a walk."""
        _M_ENGINE_READS.inc(reads)
        if self._db.point_reads_keep_lock:
            _M_READS_LOCK_KEPT.inc(reads)

    def _empty_depth(self, bits: int) -> Tuple[int, int]:
        """-> (the least depth at which the path of leaf `bits` has no
        stored node, DEPTH + 1 if the leaf itself is stored; node reads
        made). Only non-default nodes are stored, so the subtree under
        an absent path node is empty and every path node below it is
        absent too: one read of the leaf, then a bisection."""
        get, family = self._db.get, self._family
        if get(_node_key(DEPTH, bits), family) is not None:
            return DEPTH + 1, 1
        lo, hi, reads = 1, DEPTH, 1        # the answer is in [lo, hi]
        while lo < hi:
            mid = (lo + hi) // 2
            reads += 1
            if get(_node_key(mid, bits >> (DEPTH - mid)), family) is None:
                hi = mid
            else:
                lo = mid + 1
        return lo, reads

    # ---- multi-block batch update ----
    def update_batches(self, updates_list: Sequence[Dict[bytes,
                                                         Optional[bytes]]],
                       batch: Optional[WriteBatch] = None,
                       first_version: int = 0) -> List[bytes]:
        """Apply N consecutive blocks' updates in one level-synchronous
        walk: block i gets version `first_version + i` (0 = unversioned,
        like update_batch). Returns the root AFTER each block, exactly as
        N sequential update_batch calls would, and stages byte-identical
        rows (final node/leaf values + one archive row per changed node
        per version).

        The win over per-block calls is hash batching: at every level,
        the changed nodes of ALL blocks hash in ONE _hash_level call (one
        ops/sha256 device dispatch per level once wide enough) instead of
        one host loop per block per level. Cross-block dependencies are
        handled by tracking, per node, the ordered list of
        (block index, hash) versions: block i's parent hash reads the
        newest child value at or below i, falling back to the DB for
        nodes untouched by the whole batch."""
        if not updates_list:
            return []
        nblocks = len(updates_list)
        if not any(updates_list):
            return [self.root()] * nblocks
        if nblocks == 1:
            # degenerate: the sequential path is the batched path
            return [self.update_batch(dict(updates_list[0]), batch=batch,
                                      version=first_version)]
        own_batch = batch is None
        wb = WriteBatch() if own_batch else batch
        vers = [(first_version + i).to_bytes(8, "big")
                if first_version > 0 else None for i in range(nblocks)]

        # leaf level: per path, ordered (block, hash) versions
        changed: Dict[int, List[Tuple[int, bytes]]] = {}
        final_leaf: Dict[bytes, Optional[bytes]] = {}
        for i, updates in enumerate(updates_list):
            for key, vh in updates.items():
                path = hashlib.sha256(key).digest()
                bits = int.from_bytes(path, "big")
                h = _EMPTY if vh is None else _leaf_hash(path, vh)
                changed.setdefault(bits, []).append((i, h))
                final_leaf[path] = vh
                if vers[i] is not None:
                    wb.put(path + vers[i],
                           vh if vh is not None else b"",
                           self._leaf_arch_family)
        for path, vh in final_leaf.items():
            if vh is None:
                wb.delete(path, self._leaf_family)
            else:
                wb.put(path, vh, self._leaf_family)
        # pre-batch values of this level's changed nodes, captured BEFORE
        # staging them: `wb` may be a read-your-writes mirrored batch (the
        # bulk add_blocks path), where a post-staging read of a node whose
        # first change is at a LATER block would see that final value
        # instead of the pre-batch one — corrupting earlier blocks' roots
        pre: Dict[int, bytes] = {b: self._node(DEPTH, b) for b in changed}
        self._stage_level_multi(wb, DEPTH, changed, vers)

        for depth in range(DEPTH, 0, -1):
            def value_at(bits: int, i: int) -> bytes:
                """Newest value of (depth, bits) at or below block i:
                the node's newest in-batch version ≤ i, its pre-batch
                value if its first change is later, or the DB (which the
                batch never touched for this node)."""
                versions = changed.get(bits)
                if versions is None:
                    return self._node(depth, bits)
                best = None
                for j, h in versions:          # ascending block order
                    if j > i:
                        break
                    best = h
                return best if best is not None else pre[bits]

            # (parent_bits, block) pairs needing a hash, in stable order
            pairs: List[Tuple[int, int]] = []
            seen = set()
            for bits, versions in changed.items():
                pb = bits >> 1
                for i, _ in versions:
                    if (pb, i) not in seen:
                        seen.add((pb, i))
                        pairs.append((pb, i))
            pairs.sort()
            msgs = [b"\x01" + value_at(pb << 1, i)
                    + value_at((pb << 1) | 1, i)
                    for pb, i in pairs]
            hashes = _hash_level(msgs, self._use_device)
            parents: Dict[int, List[Tuple[int, bytes]]] = {}
            for (pb, i), h in zip(pairs, hashes):
                parents.setdefault(pb, []).append((i, h))
            changed = parents                  # pairs sorted → ascending i
            pre = {b: self._node(depth - 1, b) for b in changed}
            self._stage_level_multi(wb, depth - 1, changed, vers)

        if own_batch:
            self._db.write(wb)
        root_versions = changed[0]
        roots, cur = [], pre[0]               # pre-batch root
        it = iter(root_versions)
        nxt = next(it, None)
        for i in range(nblocks):
            while nxt is not None and nxt[0] <= i:
                cur = nxt[1]
                nxt = next(it, None)
            roots.append(cur)
        return roots

    def _stage_level_multi(self, wb: WriteBatch, depth: int,
                           nodes: Dict[int, List[Tuple[int, bytes]]],
                           vers: List[Optional[bytes]]) -> None:
        """Stage a level's multi-version nodes: final value to the live
        family, one archive row per (node, block) change."""
        default = _DEFAULTS[depth]
        for bits, versions in nodes.items():
            k = _node_key(depth, bits)
            final = versions[-1][1]
            if final == default:
                wb.delete(k, self._family)
            else:
                wb.put(k, final, self._family)
            for i, h in versions:
                if vers[i] is not None:
                    wb.put(k + vers[i], b"" if h == default else h,
                           self._arch_family)

    def _level_rows(self, rows: List[Tuple[bytes, Optional[bytes]]],
                    depth: int, nodes: Dict[int, bytes],
                    ver: Optional[bytes]) -> None:
        """Append a level's rows: a node that hashes to its depth's
        default is deleted, so only non-default nodes are stored."""
        default = _DEFAULTS[depth]
        for bits, h in nodes.items():
            k = _node_key(depth, bits)
            rows.append((self._pre + k, None if h == default else h))
            if ver is not None:
                # archive row; default is stored as empty so a historical
                # walk can tell "reverted to default at ver" from "never
                # touched" (the latter = default since genesis)
                rows.append((self._arch_pre + k + ver,
                             b"" if h == default else h))

    # ---- versioned reads ----
    def _newest_row_at(self, family: bytes, prefix: bytes,
                       version: int) -> Optional[bytes]:
        """Newest archive row for `prefix` at or below `version`, or None
        if the node was never written by then. Rows of one node share a
        fixed-length prefix, so the range scan is exact."""
        row = self._db.last_in_range(
            family, start=prefix,
            end=prefix + (version + 1).to_bytes(8, "big"))
        return row[1] if row else None

    def _node_at(self, depth: int, path_bits: int, version: int) -> bytes:
        row = self._newest_row_at(self._arch_family,
                                  _node_key(depth, path_bits), version)
        if row is None or row == b"":
            return _DEFAULTS[depth]
        return row

    def root_at(self, version: int) -> bytes:
        return self._node_at(0, 0, version)

    def get_value_hash_at(self, key: bytes,
                          version: int) -> Optional[bytes]:
        path = hashlib.sha256(key).digest()
        row = self._newest_row_at(self._leaf_arch_family, path, version)
        return row if row else None        # b"" = deleted at that version

    def prove_at(self, key: bytes, version: int) -> Proof:
        """Audit path as of `version` (a retained block id). Costs one
        archive range-scan per level — proof serving, not the hot path."""
        return self._prove_with(
            key, lambda depth, bits: self._node_at(depth, bits, version))

    def prune_versions(self, before_version: int) -> int:
        """Stale-node GC (reference stale-node index role): drop archive
        rows SUPERSEDED at or below `before_version` — for each node,
        every row older than its newest row ≤ before stays unreachable
        from any retained root ≥ before. Returns rows deleted.

        Cost: one pass over the archive family (O(retained history), a
        maintenance operation like the reference's stale-node sweep, not
        the ordering hot path). A per-write stale index would make this
        O(deleted) at the price of one extra read per node on every
        block commit — wrong trade while prune frequency << block rate."""
        wb = WriteBatch()
        deleted = 0
        for fam in (self._arch_family, self._leaf_arch_family):
            prev_key: Optional[bytes] = None   # candidate superseded row
            for k, _v in self._db.range_iter(fam):
                prefix, ver = k[:-8], int.from_bytes(k[-8:], "big")
                if (prev_key is not None and prev_key[:-8] == prefix
                        and ver <= before_version):
                    wb.delete(prev_key, fam)   # newer row ≤ before exists
                    deleted += 1
                prev_key = k if ver <= before_version else None
        if deleted:
            self._db.write(wb)
        return deleted

    # ---- proofs ----
    def prove(self, key: bytes) -> Proof:
        return self._prove_with(key, self._node)

    def _prove_with(self, key: bytes, node) -> Proof:
        """One audit-path walk for both latest and versioned proofs —
        the bitmap compression must never diverge between the two."""
        path = hashlib.sha256(key).digest()
        bits = int.from_bytes(path, "big")
        bitmap = bytearray(32)
        siblings: List[bytes] = []
        node_bits = bits
        for depth in range(DEPTH, 0, -1):
            sib = node(depth, node_bits ^ 1)
            if sib != _DEFAULTS[depth]:
                i = DEPTH - depth
                bitmap[i // 8] |= 1 << (i % 8)
                siblings.append(sib)
            node_bits >>= 1
        return Proof(bytes(bitmap), siblings)

    @staticmethod
    def verify(root: bytes, key: bytes, value_hash: Optional[bytes],
               proof: Proof) -> bool:
        """Checks membership (value_hash given) or non-membership (None)."""
        if len(proof.bitmap) != 32:
            return False
        path = hashlib.sha256(key).digest()
        bits = int.from_bytes(path, "big")
        acc = _EMPTY if value_hash is None else _leaf_hash(path, value_hash)
        sib_iter = iter(proof.siblings)
        node_bits = bits
        try:
            for depth in range(DEPTH, 0, -1):
                i = DEPTH - depth
                if proof.bitmap[i // 8] >> (i % 8) & 1:
                    sib = next(sib_iter)
                else:
                    sib = _DEFAULTS[depth]
                if node_bits & 1:
                    acc = hashlib.sha256(b"\x01" + sib + acc).digest()
                else:
                    acc = hashlib.sha256(b"\x01" + acc + sib).digest()
                node_bits >>= 1
        except StopIteration:
            return False
        return acc == root
