"""`SparseMerkleTree.update_batch` reads the engine only where the tree is
not provably empty. The golden model is the walk it replaced, kept here:
the same ascent with every sibling read. Roots after every block and the
full set of stored rows (live nodes, leaves, both archive families) must
be equal — on a bare tree, and through `KeyValueBlockchain` inside plain
and aborted accumulations, on the thread that built the ledger and on
another (the lane's), where the bound is taken through the staged view
(block N+1 of a run sees block N's path).

A batch of fewer than 192 changed leaves takes the native walk, whose rows
arrive encoded: its payload is held, byte for byte, to the golden walk's
rows put one at a time and encoded by the plain encoder kept here — on a
bare tree, and as the engine receives it from a ledger's runs."""
import hashlib
import math
import random
import struct
import threading
import types

import pytest

from tpubft.kvbc import BLOCK_MERKLE, BlockUpdates, KeyValueBlockchain
from tpubft.kvbc import sparse_merkle
from tpubft.kvbc.sparse_merkle import (DEPTH, _EMPTY, SparseMerkleTree,
                                       _leaf_hash)
from tpubft.durability import PendingStore
from tpubft.storage.interfaces import IDBClient, WriteBatch
from tpubft.storage.memorydb import MemoryDB
from tpubft.storage.native import NativeDB


def node_key(depth: int, path_bits: int) -> bytes:
    nbytes = (depth + 7) // 8
    return depth.to_bytes(2, "big") + (
        (path_bits << (nbytes * 8 - depth)).to_bytes(nbytes, "big")
        if depth else b"")


class FullReadTree(SparseMerkleTree):
    """The plain walk, whole: every sibling of every changed node is
    read, and every row is put one at a time."""

    def read(self, depth, bits):
        v = self._db.get(node_key(depth, bits), self._family)
        return v if v is not None else sparse_merkle._DEFAULTS[depth]

    def stage_level(self, wb, depth, nodes, ver):
        default = sparse_merkle._DEFAULTS[depth]
        for bits, h in nodes.items():
            k = node_key(depth, bits)
            if h == default:
                wb.delete(k, self._family)
            else:
                wb.put(k, h, self._family)
            if ver is not None:
                wb.put(k + ver, b"" if h == default else h,
                       self._arch_family)

    def update_batch(self, updates, batch=None, version=0):
        if not updates:
            return self.root()
        own_batch = batch is None
        wb = WriteBatch() if own_batch else batch
        ver = version.to_bytes(8, "big") if version > 0 else None
        changed = {}
        for key, vh in updates.items():
            path = hashlib.sha256(key).digest()
            bits = int.from_bytes(path, "big")
            if vh is None:
                changed[bits] = _EMPTY
                wb.delete(path, self._leaf_family)
            else:
                changed[bits] = _leaf_hash(path, vh)
                wb.put(path, vh, self._leaf_family)
            if ver is not None:
                wb.put(path + ver, vh if vh is not None else b"",
                       self._leaf_arch_family)
        self.stage_level(wb, DEPTH, changed, ver)
        for depth in range(DEPTH, 0, -1):
            parents = sorted({bits >> 1 for bits in changed})
            msgs = []
            for pb in parents:
                left = changed.get(pb << 1)
                if left is None:
                    left = self.read(depth, pb << 1)
                right = changed.get((pb << 1) | 1)
                if right is None:
                    right = self.read(depth, (pb << 1) | 1)
                msgs.append(b"\x01" + left + right)
            changed = dict(zip(parents, (
                hashlib.sha256(m).digest() for m in msgs)))
            self.stage_level(wb, depth - 1, changed, ver)
        if own_batch:
            self._db.write(wb)
        return changed[0]


def plain_payload(ops) -> bytes:
    """The engine's wire encoding, one row at a time:
    u8 op(1=put, 2=del) | u32le klen | key | [u32le vlen | val]."""
    out = []
    for k, v in ops:
        out += [struct.pack("<BI", 2 if v is None else 1, len(k)), k]
        if v is not None:
            out += [struct.pack("<I", len(v)), v]
    return b"".join(out)


class FullReadLedger(KeyValueBlockchain):
    def _tree(self, category):
        t = self._trees.get(category)
        if t is None:
            t = self._trees[category] = FullReadTree(
                self._db, family=f"smt.{category}".encode(),
                use_device=False)
        return t


def vh(i) -> bytes:
    return hashlib.sha256(b"value-%d" % i).digest()


def blocks_fresh_keys(rng):
    return [{b"k%d" % i: vh(rng.random())} for i in range(24)]


def blocks_overwrites(rng):
    keys = [b"k%d" % i for i in range(8)]
    out = [{k: vh(0)} for k in keys]
    out += [{rng.choice(keys): vh(rng.random())} for _ in range(16)]
    return out


def blocks_delete_to_empty(rng):
    keys = [b"k%d" % i for i in range(6)]
    out = [{k: vh(1)} for k in keys]
    out += [{k: None} for k in rng.sample(keys, len(keys))]
    out += [{b"again": vh(2)}, {b"again": None}]
    return out


def blocks_multi_key(rng):
    """Blocks of many keys: some pairs of paths share a long prefix, and
    deletes, overwrites and fresh keys ride one block."""
    out, live = [], []
    for i in range(10):
        ups = {b"m%d.%d" % (i, j): vh(rng.random()) for j in range(12)}
        for k in rng.sample(live, min(len(live), 5)):
            ups[k] = None if rng.random() < 0.5 else vh(rng.random())
        out.append(ups)
        live = [k for k in set(live) | set(ups) if ups.get(k, b"") is not None]
    return out


def blocks_random_mix(rng):
    out, live = [], []
    for i in range(60):
        ups = {}
        for _ in range(rng.choice((1, 1, 1, 2, 5))):
            r = rng.random()
            if live and r < 0.25:
                ups[rng.choice(live)] = None
            elif live and r < 0.5:
                ups[rng.choice(live)] = vh(rng.random())
            else:
                ups[b"r%d.%d" % (i, len(ups))] = vh(rng.random())
        out.append(ups)
        live = sorted((set(live) | set(ups))
                      - {k for k, v in ups.items() if v is None})
    return out


SEQUENCES = {"fresh_keys": blocks_fresh_keys,
             "overwrites": blocks_overwrites,
             "delete_to_empty": blocks_delete_to_empty,
             "multi_key_shared_prefix": blocks_multi_key,
             "random_mix": blocks_random_mix}


def dump(db):
    return sorted(db.scan_all())


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("name", sorted(SEQUENCES))
def test_bare_tree_matches_the_full_read_walk(name, seed):
    blocks = SEQUENCES[name](random.Random(seed))
    gold_db, db = MemoryDB(), MemoryDB()
    gold = FullReadTree(gold_db, use_device=False)
    tree = SparseMerkleTree(db, use_device=False)
    for i, ups in enumerate(blocks):
        # every other sequence unversioned for its first blocks: no
        # archive rows there
        ver = 0 if (seed == 2 and i < 4) else 100 + i
        assert tree.update_batch(dict(ups), version=ver) \
            == gold.update_batch(dict(ups), version=ver), (name, i)
        assert dump(db) == dump(gold_db), (name, i)
    if name == "delete_to_empty":
        assert tree.root() == sparse_merkle._DEFAULTS[0]
        assert not list(db.range_iter(b"smt"))
    # the rows serve the same proofs, latest and historical
    for key in list(blocks[0]) + list(blocks[-1]):
        assert tree.prove(key) == gold.prove(key)
        assert tree.prove_at(key, 100 + len(blocks) // 2) \
            == gold.prove_at(key, 100 + len(blocks) // 2)


def test_siblings_at_depth_256(monkeypatch):
    """Two leaves under one depth-255 parent and a third that leaves
    them at depth 254, placed by hand: alone, one after the other,
    together, and one deleted beside the other."""
    real = hashlib.sha256

    class HandPaths:
        """sha256, but a 32-byte key that starts 0xfe is its own path."""
        def __init__(self, data=b""):
            self._data = data

        def digest(self):
            d = self._data
            return d if len(d) == 32 and d[:1] == b"\xfe" \
                else real(d).digest()

    shim = types.SimpleNamespace(sha256=HandPaths)
    monkeypatch.setattr(sparse_merkle, "hashlib", shim)
    monkeypatch.setitem(globals(), "hashlib", shim)
    a = b"\xfe" + b"\x5a" * 30 + b"\x10"
    b = b"\xfe" + b"\x5a" * 30 + b"\x11"
    near = b"\xfe" + b"\x5a" * 30 + b"\x13"      # shares 254 bits with both
    blocks = [{a: vh(1)}, {b: vh(2)}, {near: vh(3)}, {a: None},
              {a: vh(4), b: None}, {a: None, near: None},
              {a: vh(5), b: vh(6), near: vh(7)}, {b: vh(8)},
              {a: None, b: None, near: None}]
    gold_db, db = MemoryDB(), MemoryDB()
    gold = FullReadTree(gold_db, use_device=False)
    tree = SparseMerkleTree(db, use_device=False)
    for i, ups in enumerate(blocks):
        wb, gold_wb = WriteBatch(), WriteBatch()
        assert tree.update_batch(dict(ups), batch=wb, version=1 + i) \
            == gold.update_batch(dict(ups), batch=gold_wb,
                                 version=1 + i), i
        assert wb.encode() == plain_payload(gold_wb.ops), i
        db.write(wb)
        gold_db.write(gold_wb)
        assert dump(db) == dump(gold_db), i
    assert not list(db.range_iter(b"smt"))


def blocks_of(leaves, rng):
    """At most `leaves` changed leaves a block, the first two blocks
    exactly: fresh keys, then overwrites and deletes beside fresh keys,
    then the first block's rest deleted, then one key back."""
    keys = [b"n%d" % i for i in range(leaves)]
    some = keys[:max(1, leaves // 2)]
    mixed = {k: (None if i % 3 == 0 else vh(rng.random()))
             for i, k in enumerate(some)}
    mixed.update((b"late%d" % i, vh(i)) for i in range(leaves - len(some)))
    gone = {k for k, v in mixed.items() if v is None}
    return [{k: vh(i) for i, k in enumerate(keys)}, mixed,
            {k: None for k in keys if k not in gone}, {keys[0]: vh(-1)}]


@pytest.mark.parametrize("store", ["memory", "native"])
@pytest.mark.parametrize("version", [0, 7])
@pytest.mark.parametrize("leaves", [1, 2, 7, 191])
def test_native_walk_payload_is_the_full_read_walk_s(leaves, version, store,
                                                     tmp_path):
    def open_db(name):
        return MemoryDB() if store == "memory" else NativeDB(
            str(tmp_path / name), sync_writes=False)
    gold_db, db = open_db("gold.kvlog"), open_db("tree.kvlog")
    gold = FullReadTree(gold_db, use_device=False)
    tree = SparseMerkleTree(db, use_device=False)
    for i, ups in enumerate(blocks_of(leaves, random.Random(leaves))):
        assert len(ups) == leaves if i < 2 else len(ups) <= leaves
        ver = version + i if version else 0
        c0 = counters()
        wb, gold_wb = WriteBatch(), WriteBatch()
        assert tree.update_batch(dict(ups), batch=wb, version=ver) \
            == gold.update_batch(dict(ups), batch=gold_wb, version=ver), i
        assert wb.encode() == plain_payload(gold_wb.ops), i
        assert wb.ops == gold_wb.ops and len(wb) == len(gold_wb), i
        assert wb.families == gold_wb.families, i
        assert counters()["smt_keys_native"] - c0["smt_keys_native"] \
            == len(ups), i
        db.write(wb)
        gold_db.write(gold_wb)
        assert dump(db) == dump(gold_db), i
    assert tree.root() == gold.root()


def test_192_leaves_take_the_level_loop():
    gold_db, db = MemoryDB(), MemoryDB()
    gold = FullReadTree(gold_db, use_device=False)
    tree = SparseMerkleTree(db, use_device=False)
    ups = {b"w%d" % i: vh(i) for i in range(sparse_merkle._DEVICE_THRESHOLD)}
    c0 = counters()
    wb, gold_wb = WriteBatch(), WriteBatch()
    assert tree.update_batch(dict(ups), batch=wb, version=3) \
        == gold.update_batch(dict(ups), batch=gold_wb, version=3)
    c1 = counters()
    assert c1["smt_keys_native"] == c0["smt_keys_native"]
    assert c1["smt_keys_updated"] - c0["smt_keys_updated"] == len(ups)
    assert wb.encode() == plain_payload(gold_wb.ops)
    # one leaf fewer is the native walk's
    ups.popitem()
    tree.update_batch(ups, batch=WriteBatch(), version=4)
    assert counters()["smt_keys_native"] - c1["smt_keys_native"] == len(ups)


def merkle_block(ups) -> BlockUpdates:
    bu = BlockUpdates()
    for k, v in ups.items():
        if v is None:
            bu.delete("kv", k, cat_type=BLOCK_MERKLE)
        else:
            bu.put("kv", k, v, cat_type=BLOCK_MERKLE)
    return bu


def ledger_blocks(rng, n):
    """Runs' worth of blocks as the served cell writes them — one fresh
    key a block — with overwrites, deletes and a multi-key block mixed
    in, so that a block's path meets its run's earlier paths."""
    out, live = [], []
    for i in range(n):
        r = rng.random()
        if live and r < 0.15:
            ups = {rng.choice(live): None}
        elif live and r < 0.3:
            ups = {rng.choice(live): b"over-%d" % i}
        elif r < 0.4:
            ups = {b"multi-%d-%d" % (i, j): b"v" for j in range(4)}
        else:
            ups = {b"key-%d" % i: b"val-%d" % i}
        out.append(ups)
        live = sorted((set(live) | set(ups))
                      - {k for k, v in ups.items() if v is None})
    return out


def in_thread(fn):
    err = []

    def run():
        try:
            fn()
        except BaseException as e:   # noqa: BLE001 — re-raised below
            err.append(e)
    th = threading.Thread(target=run, daemon=True)
    th.start()
    th.join(60)
    assert not th.is_alive()
    if err:
        raise err[0]


def run_plain(bc, blocks):
    for ups in blocks:
        bc.add_block(merkle_block(ups))


def run_accumulated(bc, blocks):
    bc.begin_accumulation()
    run_plain(bc, blocks)
    bc.end_accumulation()


def run_aborted_then_accumulated(bc, blocks):
    """A run that aborts after staging other keys leaves nothing the
    next run's bounds could trip on."""
    bc.begin_accumulation()
    run_plain(bc, [{b"doomed-%d" % bc.last_block_id: b"x"},
                   dict(blocks[0])])
    bc.abort_accumulation()
    run_accumulated(bc, blocks)


def run_on_the_lane_s_thread(bc, blocks):
    """As a replica runs it: the ledger is built on one thread and its
    runs are staged and sealed on another."""
    in_thread(lambda: run_accumulated(bc, blocks))


def run_aborted_on_the_lane_then_replayed(bc, blocks):
    """A run that fails on the lane's thread after staging the blocks
    in another order, then the same blocks one at a time on this one, as
    the restore replay appends them."""
    def doomed():
        bc.begin_accumulation()
        run_plain(bc, blocks[::-1])
        bc.abort_accumulation()
    in_thread(doomed)
    run_plain(bc, blocks)


MODES = {"plain": run_plain, "accumulated": run_accumulated,
         "aborted_then_accumulated": run_aborted_then_accumulated,
         "on_the_lane_s_thread": run_on_the_lane_s_thread,
         "aborted_on_the_lane_then_replayed":
             run_aborted_on_the_lane_then_replayed}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_ledger_rows_match_through_staged_views(mode):
    rng = random.Random(7)
    blocks = ledger_blocks(rng, 48)
    gold_db, db = MemoryDB(), MemoryDB()
    gold = FullReadLedger(gold_db, use_device_hashing=False)
    bc = KeyValueBlockchain(db, use_device_hashing=False)
    for at in range(0, len(blocks), 8):          # runs of 8 blocks
        run = blocks[at:at + 8]
        run_plain(gold, run)
        MODES[mode](bc, run)
        assert bc.last_block_id == gold.last_block_id
        assert bc.merkle_root("kv") == gold.merkle_root("kv"), at
        assert bc.state_digest() == gold.state_digest(), at
        assert dump(db) == dump(gold_db), at
    for b in range(1, bc.last_block_id + 1):
        assert bc.get_raw_block(b) == gold.get_raw_block(b)


def run_deferred(bc, blocks):
    """As the execution lane seals a run: the overlay goes to the pending
    store, the batch to the engine as a group of one, later."""
    bc.begin_accumulation()
    run_plain(bc, blocks)
    bc.end_accumulation(defer=True)
    return bc.take_deferred()


def run_deferred_applied(bc, blocks, store):
    run_no, batch, base = run_deferred(bc, blocks)
    base.write_group([batch])
    store.mark_applied(run_no)


def run_deferred_two_pending(bc, blocks, store):
    """Two runs sealed before either is applied: the second run's walks
    read the first's rows from the pending store."""
    half = len(blocks) // 2
    sealed = [run_deferred(bc, blocks[:half]),
              run_deferred(bc, blocks[half:])]
    sealed[0][2].write_group([batch for _no, batch, _db in sealed])
    for run_no, _batch, _db in sealed:
        store.mark_applied(run_no)


def run_sealed_on_the_lane_applied_here(bc, blocks, store):
    """The lane's thread seals the run, another (the io thread's part)
    hands the batch to the engine."""
    sealed = []
    in_thread(lambda: sealed.append(run_deferred(bc, blocks)))
    run_no, batch, base = sealed[0]
    base.write_group([batch])
    store.mark_applied(run_no)


ENGINE_MODES = {
    "accumulated": lambda bc, blocks, store: run_accumulated(bc, blocks),
    "on_the_lane_s_thread":
        lambda bc, blocks, store: run_on_the_lane_s_thread(bc, blocks),
    "deferred": run_deferred_applied,
    "deferred_two_pending": run_deferred_two_pending,
    "sealed_on_the_lane_applied_here": run_sealed_on_the_lane_applied_here}


@pytest.mark.parametrize("mode", sorted(ENGINE_MODES))
def test_the_engine_receives_the_full_read_walk_s_payload(mode, tmp_path,
                                                          monkeypatch):
    """What `kvlog_apply` is handed for a ledger's runs — under a staged
    accumulation and a pending store, on one thread and across two — is
    the payload of the golden ledger's rows, put one at a time."""
    applied = {}
    real = NativeDB._apply

    def recording(self, payload, families):
        applied.setdefault(id(self), []).append(bytes(payload))
        return real(self, payload, families)
    monkeypatch.setattr(NativeDB, "_apply", recording)
    gold_db = NativeDB(str(tmp_path / "gold.kvlog"), sync_writes=False)
    db = NativeDB(str(tmp_path / "bc.kvlog"), sync_writes=False)
    gold = FullReadLedger(gold_db, use_device_hashing=False)
    bc = KeyValueBlockchain(db, use_device_hashing=False)
    store = PendingStore("t")
    bc.attach_durability(store)
    blocks = ledger_blocks(random.Random(11), 40)
    c0 = counters()
    for at in range(0, len(blocks), 8):
        run = blocks[at:at + 8]
        gold.begin_accumulation()
        for ups in run:
            gold.add_block(merkle_block(ups))
        gold_rows = plain_payload(gold._accum.master.ops)
        gold.end_accumulation()
        ENGINE_MODES[mode](bc, run, store)
        assert b"".join(applied[id(db)]) \
            == b"".join(applied[id(gold_db)]), at
        assert applied[id(gold_db)][-1] == gold_rows
        assert store.empty
        assert bc.state_digest() == gold.state_digest(), at
    c1 = counters()
    assert c1["smt_keys_native"] - c0["smt_keys_native"] \
        == sum(map(len, blocks))
    db.close()
    gold_db.close()
    # the log replays to the same store
    assert dump(NativeDB(str(tmp_path / "bc.kvlog"))) \
        == dump(NativeDB(str(tmp_path / "gold.kvlog")))


class CountingDB(IDBClient):
    """Counts point reads by family; everything else passes through."""

    def __init__(self, base):
        self.base, self.gets = base, {}

    def get(self, key, family=b"default"):
        self.gets[family] = self.gets.get(family, 0) + 1
        return self.base.get(key, family)

    def write(self, batch):
        self.base.write(batch)

    def range_iter(self, family=b"default", start=None, end=None):
        return self.base.range_iter(family, start, end)

    def close(self):
        self.base.close()


def counters():
    return dict(sparse_merkle.METRICS.snapshot()["counters"])


def test_a_fresh_key_reads_about_twice_log2_of_the_keys(tmp_path):
    db = CountingDB(NativeDB(str(tmp_path / "tree.kvlog"),
                             sync_writes=False))
    tree = SparseMerkleTree(db, use_device=False)
    tree.update_batch({b"k%d" % i: vh(i) for i in range(1000)})
    limit = 2 * (math.log2(1000) + 6)

    for i in range(20):
        db.gets.clear()
        c0 = counters()
        tree.update_batch({b"fresh-%d" % i: vh(i)}, version=1 + i)
        assert set(db.gets) == {b"smt"}
        assert db.gets[b"smt"] <= limit, (i, db.gets)
        # the totals say the same: every read counted, and the bound
        # answered what the 256 sibling lookups of the key did not read
        c1 = counters()
        assert c1["smt_keys_updated"] - c0["smt_keys_updated"] == 1
        assert c1["smt_engine_reads"] - c0["smt_engine_reads"] \
            == db.gets[b"smt"]
        siblings_read = DEPTH - (c1["smt_siblings_bounded"]
                                 - c0["smt_siblings_bounded"])
        assert 0 < siblings_read < db.gets[b"smt"]

    # an overwrite and a delete find their leaf stored: one probe, then
    # every sibling read, as the full walk reads them
    for ups in ({b"k7": vh(-1)}, {b"k8": None}):
        db.gets.clear()
        c0 = counters()
        tree.update_batch(ups, version=50)
        assert db.gets == {b"smt": DEPTH + 1}
        assert counters()["smt_siblings_bounded"] \
            == c0["smt_siblings_bounded"]

    # the golden walk on the same tree: 256 reads for a fresh key
    gold = FullReadTree(db, use_device=False)
    db.gets.clear()
    gold.update_batch({b"fresh-gold": vh(0)}, version=60)
    assert db.gets == {b"smt": DEPTH}


def test_an_empty_tree_reads_its_root_s_children_only():
    db = CountingDB(MemoryDB())
    SparseMerkleTree(db, use_device=False).update_batch({b"first": vh(0)})
    # the leaf probe, 8 bisection probes, the sibling at depth 1
    assert db.gets == {b"smt": 10}
