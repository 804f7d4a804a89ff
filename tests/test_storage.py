"""Storage layer tests: IDBClient semantics across backends, native engine
crash recovery, metadata transactions (reference test model:
storage/test/, kvbc memorydb-backed unit tests)."""
import ctypes
import os
import random
import threading
import time

import pytest

from tpubft.storage import MemoryDB, WriteBatch
from tpubft.storage.interfaces import family_upper_bound, fkey, split_fkey
from tpubft.storage.metadata import DBPersistentStorage, MetadataStorage
from tpubft.storage.native import NativeDB


def test_fkey_roundtrip_and_bounds():
    assert split_fkey(fkey(b"fam", b"key")) == (b"fam", b"key")
    ub = family_upper_bound(b"fam")
    assert fkey(b"fam", b"\xff" * 50) < ub
    assert fkey(b"famz", b"") > ub  # sibling family sorts outside
    assert family_upper_bound(b"\xff" * 255) is None


@pytest.mark.parametrize("kind", ["memory", "native"])
def test_basic_ops(tmp_path, kind):
    db = (MemoryDB() if kind == "memory"
          else NativeDB(str(tmp_path / "db.kvlog")))
    assert db.get(b"a") is None
    db.put(b"a", b"1")
    db.put(b"b", b"2", family=b"other")
    assert db.get(b"a") == b"1"
    assert db.get(b"a", family=b"other") is None
    assert db.get(b"b", family=b"other") == b"2"
    db.delete(b"a")
    assert db.get(b"a") is None
    assert db.multi_get([b"b", b"c"], family=b"other") == [b"2", None]
    db.close()


@pytest.mark.parametrize("kind", ["memory", "native"])
def test_range_iter_ordered(tmp_path, kind):
    db = (MemoryDB() if kind == "memory"
          else NativeDB(str(tmp_path / "db.kvlog")))
    batch = WriteBatch()
    for i in [5, 1, 9, 3, 7]:
        batch.put(bytes([i]), str(i).encode())
    batch.put(b"zzz", b"x", family=b"other")
    db.write(batch)
    assert [k for k, _ in db.range_iter()] == [bytes([i])
                                               for i in [1, 3, 5, 7, 9]]
    assert [k for k, _ in db.range_iter(start=bytes([3]), end=bytes([8]))] \
        == [bytes([3]), bytes([5]), bytes([7])]
    assert db.last_in_range() == (bytes([9]), b"9")
    db.close()


def test_batch_atomicity_overwrite(tmp_path):
    db = NativeDB(str(tmp_path / "db.kvlog"))
    db.write(WriteBatch().put(b"k", b"v1").put(b"k", b"v2").delete(b"gone")
             .put(b"x", b"y"))
    assert db.get(b"k") == b"v2"
    assert db.get(b"x") == b"y"
    db.close()


def test_native_persistence_and_recovery(tmp_path):
    path = str(tmp_path / "db.kvlog")
    db = NativeDB(path)
    for i in range(100):
        db.put(f"key-{i:03d}".encode(), f"val-{i}".encode())
    db.close()

    db = NativeDB(path)
    assert db.count() == 100
    assert db.get(b"key-050") == b"val-50"

    # Torn tail: append garbage — recovery must stop at last good record.
    db.close()
    with open(path, "ab") as fh:
        fh.write(b"\x47\x4c\x56\x4btorn-partial-record")
    db = NativeDB(path)
    assert db.count() == 100
    db.put(b"after-recovery", b"ok")  # appends cleanly after truncation
    db.close()
    db = NativeDB(path)
    assert db.get(b"after-recovery") == b"ok"
    db.close()


def test_native_sync_families_carveout(tmp_path):
    """sync_writes=False + sync_families: batches touching a carved-out
    family (consensus metadata) fsync, everything else stays unsynced —
    and all data is durable across a clean close/reopen either way."""
    from tpubft.storage.interfaces import WriteBatch
    path = str(tmp_path / "db.kvlog")
    db = NativeDB(path, sync_writes=False,
                  sync_families=(b"metadata", b"metaseq"))
    # metadata batch -> hits the kvlog_sync path
    db.write(WriteBatch().put(b"\x00\x00\x00\x02", b"desc", b"metadata"))
    db.write(WriteBatch().put((5).to_bytes(8, "big"), b"row", b"metaseq"))
    # block-data batch -> no sync
    db.write(WriteBatch().put(b"blk1", b"payload", b"blk.blocks"))
    # a family whose name merely PREFIXES a sync family must not match
    # (prefix check runs on the length-prefixed physical key)
    db.write(WriteBatch().put(b"x", b"y", b"meta"))
    db.close()
    db = NativeDB(path)
    assert db.get(b"\x00\x00\x00\x02", b"metadata") == b"desc"
    assert db.get((5).to_bytes(8, "big"), b"metaseq") == b"row"
    assert db.get(b"blk1", b"blk.blocks") == b"payload"
    assert db.get(b"x", b"meta") == b"y"
    db.close()
    # sync_writes=True ignores the carve-out (everything already syncs)
    db = NativeDB(path, sync_writes=True, sync_families=(b"metadata",))
    assert not db._sync_prefixes
    db.close()


def _encoded(ops):
    """`ops` as the native walk hands rows over: payload and index."""
    import struct
    from tpubft.storage.interfaces import EncodedRows
    payload, index, families = b"", b"", set()
    for k, v in ops:
        payload += struct.pack("<BI", 2 if v is None else 1, len(k))
        k0 = len(payload)
        payload += k
        v0 = v1 = 0
        if v is not None:
            payload += struct.pack("<I", len(v))
            v0, v1 = len(payload), len(payload) + len(v)
            payload += v
        index += struct.pack("<4I", k0, k0 + len(k), v0, v1)
        families.add(k[:1 + k[0]])
    return EncodedRows(payload, index, tuple(families))


_SEGMENT = [(fkey(b"smt", b"\x01\x00node"), b"h" * 32),
            (fkey(b"smt", b"\x00\xffgone"), None),
            (fkey(b"smt.arch", b"\x00\xffgone" + b"\x00" * 8), b""),
            (fkey(b"smt.leaf", b"p" * 32), b"v" * 32)]


def _mixed_batches():
    """The same rows twice: put/delete round an encoded segment, and
    every row through put/delete."""
    mixed = WriteBatch().put(b"first", b"1", b"blk").delete(b"old", b"blk")
    mixed.extend_encoded(_encoded(_SEGMENT))
    mixed.put(b"last", b"", b"blk.misc").extend_encoded(
        _encoded(_SEGMENT[:1]))
    plain = WriteBatch().put(b"first", b"1", b"blk").delete(b"old", b"blk")
    plain.extend(_SEGMENT).put(b"last", b"", b"blk.misc")
    plain.extend(_SEGMENT[:1])
    return mixed, plain


def test_batch_with_encoded_rows_encodes_as_the_all_ops_batch():
    mixed, plain = _mixed_batches()
    assert mixed.encode() == plain.encode()
    assert mixed.ops == plain.ops and len(mixed) == len(plain) == 8
    assert mixed.families == plain.families == {
        b"\x03blk", b"\x08blk.misc", b"\x03smt", b"\x08smt.arch",
        b"\x08smt.leaf"}
    assert not len(WriteBatch()) and WriteBatch().encode() == b""
    # a store without the wire format reads the rows through `ops`
    a, b = MemoryDB(), MemoryDB()
    a.write(mixed)
    b.write(plain)
    assert sorted(a.scan_all()) == sorted(b.scan_all())
    assert a.get(b"\x00\xffgone" + b"\x00" * 8, b"smt.arch") == b""


@pytest.mark.parametrize("group", [False, True])
def test_encoded_rows_reopen_after_a_torn_tail_to_the_same_prefix(
        tmp_path, group):
    def write(db, batches):
        if group:
            db.write_group(batches)
        else:
            for b in batches:
                db.write(b)
    logs = []
    for name, batch in zip(("mixed", "plain"), _mixed_batches()):
        path = str(tmp_path / f"{name}.kvlog")
        db = NativeDB(path)
        db.put(b"before", b"0")
        write(db, [batch, WriteBatch().put(b"after", b"2")])
        db.close()
        logs.append(open(path, "rb").read())
    assert logs[0] == logs[1], "the log is the same bytes, CRCs included"
    # cut inside the last record (and, alone, inside the batch's own):
    # replay stops at the last whole record, the same one for both
    for cut in (3, 40):
        seen = []
        for name in ("mixed", "plain"):
            path = str(tmp_path / f"{name}.{cut}.kvlog")
            with open(path, "wb") as fh:
                fh.write(logs[0][:-cut])
            db = NativeDB(path)
            seen.append(sorted(db.scan_all()))
            db.close()
        assert seen[0] == seen[1]
        assert (b"default", b"before", b"0") in seen[0]
        assert (b"default", b"after", b"2") not in seen[0]
        whole = (b"smt.leaf", b"p" * 32, b"v" * 32) in seen[0]
        assert whole == (cut == 3 and not group)


class _CountingLib:
    """The engine's library, counting fsyncs."""

    def __init__(self, lib):
        self._lib, self.syncs = lib, 0

    def __getattr__(self, name):
        return getattr(self._lib, name)

    def kvlog_sync(self, handle):
        self.syncs += 1
        return self._lib.kvlog_sync(handle)


@pytest.mark.parametrize("group", [False, True])
def test_encoded_rows_of_a_sync_family_still_sync(tmp_path, group):
    db = NativeDB(str(tmp_path / "db.kvlog"), sync_writes=False,
                  sync_families=(b"smt.leaf", b"metadata"))
    db._lib = _CountingLib(db._lib)
    write = db.write_group if group else (lambda bs: [db.write(b)
                                                      for b in bs])
    ledger_rows = WriteBatch().put(b"blk1", b"x", b"blk.blocks")
    ledger_rows.extend_encoded(_encoded(_SEGMENT[:3]))   # smt, smt.arch
    write([ledger_rows, WriteBatch().put(b"k", b"v", b"smt.lea")])
    assert db._lib.syncs == 0
    touching = WriteBatch().put(b"blk2", b"x", b"blk.blocks")
    touching.extend_encoded(_encoded(_SEGMENT))           # smt.leaf too
    write([ledger_rows, touching])
    assert db._lib.syncs == 1
    write([WriteBatch().put(b"d", b"desc", b"metadata"), ledger_rows])
    assert db._lib.syncs == 2
    db.close()


def test_native_compaction(tmp_path):
    path = str(tmp_path / "db.kvlog")
    db = NativeDB(path, sync_writes=False)
    for i in range(200):
        db.put(b"hot", f"v{i}".encode())
    size_before = os.path.getsize(path)
    db.compact()
    assert os.path.getsize(path) < size_before
    assert db.get(b"hot") == b"v199"
    db.close()
    db = NativeDB(path)
    assert db.get(b"hot") == b"v199"
    db.close()


@pytest.mark.parametrize("growth,want", [
    # an append-only store outgrows the floor for good and holds no
    # garbage: rewriting it would reclaim nothing, on every write
    ("append_only", (0, 0)),
    # one hot key: the live set stays small, the floor alone decides
    ("overwrites", (8, 16)),
    # half of every write is garbage: the log is rewritten each time
    # it has doubled, not on every write past the floor
    ("half_and_half", (2, 6)),
])
def test_native_auto_compaction_waits_for_garbage(tmp_path, monkeypatch,
                                                  growth, want):
    path = str(tmp_path / "db.kvlog")
    db = NativeDB(path, sync_writes=False, compact_bytes=4096)
    compactions = []
    real = db.compact

    def counted():
        compactions.append(1)
        real()
    monkeypatch.setattr(db, "compact", counted)
    model = {}
    for i in range(400):            # ~48 KiB appended over a 4 KiB floor
        key = {"append_only": b"key-%04d" % i, "overwrites": b"hot",
               "half_and_half": b"key-%04d" % (i // 2)}[growth]
        model[key] = b"v" * 100 + b"%04d" % i
        db.put(key, model[key])
    assert want[0] <= len(compactions) <= want[1], len(compactions)
    # the engine's count of live bytes is what a compaction writes,
    # and a reopened log counts the same
    live = db._lib.kvlog_live_bytes(db._h)
    db.compact()
    assert os.path.getsize(path) == 12 + live
    db.delete(sorted(model)[0])
    del model[sorted(model)[0]]
    live = db._lib.kvlog_live_bytes(db._h)
    db.close()
    db = NativeDB(path)
    assert db._lib.kvlog_live_bytes(db._h) == live
    assert {k: db.get(k) for k in model} == model
    assert db.count() == len(model)
    db.close()


# the binding rule of `tpubft/storage/native.py`: a call keeps the
# interpreter lock iff its work is one lookup of the in-memory index
_ONE_LOOKUP = ("kvlog_get", "kvlog_free", "kvlog_count", "kvlog_wal_bytes",
               "kvlog_live_bytes")
_GROWS_OR_DISK = ("kvlog_open", "kvlog_close", "kvlog_apply", "kvlog_sync",
                  "kvlog_scan", "kvlog_compact", "kvlog_checkpoint")


@pytest.mark.parametrize("name,keeps", [(n, True) for n in _ONE_LOOKUP]
                         + [(n, False) for n in _GROWS_OR_DISK])
def test_an_engine_call_keeps_the_interpreter_lock_iff_it_is_one_lookup(
        name, keeps):
    from tpubft.storage.native import _lib
    fn = getattr(_lib(), name)
    assert bool(fn._flags_ & ctypes._FUNCFLAG_PYTHONAPI) == keeps


def _join_all(threads, timeout: float = 30.0) -> None:
    """Join each thread within `timeout`: a deadlock fails, never hangs."""
    deadline = time.monotonic() + timeout
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    assert not [t.name for t in threads if t.is_alive()]


def test_eight_threads_on_one_handle_end_as_a_memory_store_fed_the_same(
        tmp_path):
    """Gets, writes, group writes and syncs from eight threads at once on
    one handle; each thread owns its keys, so the order the handle took
    the threads in cannot change what a store fed the same batches
    holds."""
    path = str(tmp_path / "db.kvlog")
    db = NativeDB(path, sync_writes=False, sync_families=(b"meta",))
    fed = {t: [] for t in range(8)}          # each thread's batches
    errors = []
    stop_at = time.monotonic() + 2.0

    def run(t):
        rng = random.Random(t)
        mine = {}                            # (family, key) -> value
        i = 0

        def row(batch):
            fam = rng.choice((b"blk", b"meta"))
            key = b"t%d-%d" % (t, rng.randrange(48))
            if rng.random() < 0.25:
                batch.delete(key, fam)
                mine[fam, key] = None
            else:
                mine[fam, key] = b"v%d-%d" % (t, i)
                batch.put(key, mine[fam, key], fam)
            return batch
        try:
            while time.monotonic() < stop_at:
                i += 1
                op = i % 4
                if op == 0:
                    for (fam, key), value in rng.sample(
                            sorted(mine.items()), min(4, len(mine))):
                        assert db.get(key, fam) == value
                elif op == 1:
                    fed[t].append(row(row(WriteBatch())))
                    db.write(fed[t][-1])
                elif op == 2:
                    group = [row(WriteBatch()) for _ in range(3)]
                    fed[t].extend(group)
                    db.write_group(group)
                else:
                    db.sync()
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(t,), name=f"t{t}",
                                daemon=True) for t in range(8)]
    for t in threads:
        t.start()
    _join_all(threads)
    assert not errors, errors
    assert all(fed.values())
    memory = MemoryDB()
    for batches in fed.values():
        for b in batches:
            memory.write(b)
    want = sorted(memory.scan_all())
    assert sorted(db.scan_all()) == want
    db.close()
    db = NativeDB(path)
    assert sorted(db.scan_all()) == want
    db.close()


class _HeldApply:
    """The engine's library, its apply holding the handle lock until
    `release` is set."""

    def __init__(self, lib, entered, release):
        self._lib, self._entered, self._release = lib, entered, release

    def __getattr__(self, name):
        return getattr(self._lib, name)

    def kvlog_apply(self, handle, payload, n):
        self._entered.set()
        rc = self._lib.kvlog_apply(handle, payload, n)
        self._release.wait(10)
        return rc


def test_a_read_queued_behind_a_slow_apply_leaves_the_interpreter_free(
        tmp_path):
    db = NativeDB(str(tmp_path / "db.kvlog"), sync_writes=False)
    db.put(b"k", b"before")
    big = WriteBatch()
    for i in range(50_000):
        big.put(b"row-%07d" % i, b"x" * 200, b"blk")
    entered, release, stop = (threading.Event(), threading.Event(),
                              threading.Event())
    db._lib = _HeldApply(db._lib, entered, release)
    got, ticks = [], [0]

    def spin():
        while not stop.is_set():
            ticks[0] += 1
    writer = threading.Thread(target=db.write, args=(big,), daemon=True)
    reader = threading.Thread(target=lambda: got.append(db.get(b"k")),
                              daemon=True)
    spinner = threading.Thread(target=spin, daemon=True)
    try:
        writer.start()
        assert entered.wait(10)
        reader.start()
        spinner.start()
        time.sleep(0.2)
        assert reader.is_alive() and not got      # the read waits ...
        t0 = ticks[0]
        time.sleep(0.2)
        assert reader.is_alive() and ticks[0] > t0  # ... the rest runs
    finally:
        release.set()
        stop.set()
        _join_all([writer, reader, spinner])
    assert got == [b"before"]
    assert db.get(b"row-0049999", b"blk") == b"x" * 200
    db.close()


@pytest.mark.parametrize("store", ["memory", "native", "native_staged",
                                   "native_pending"])
def test_reads_counted_lock_kept_are_the_native_walks_reads(tmp_path, store):
    """One narrow walk (native) and one wide walk (levels) a block, on the
    store itself and through the read views that wrap it."""
    import hashlib

    from tpubft.durability import PendingStore
    from tpubft.kvbc import sparse_merkle
    from tpubft.kvbc.blockchain import _PendingView, _StagedReadView
    from tpubft.kvbc.sparse_merkle import SparseMerkleTree
    db = (MemoryDB() if store == "memory"
          else NativeDB(str(tmp_path / "db.kvlog")))
    view = {"native_staged": lambda: _StagedReadView(db, {}),
            "native_pending": lambda: _PendingView(db, PendingStore("t"))
            }.get(store, lambda: db)()
    assert view.point_reads_keep_lock == (store != "memory")
    tree = SparseMerkleTree(view, use_device=False)

    def counters():
        return dict(sparse_merkle.METRICS.snapshot()["counters"])
    c0 = counters()
    for block, width in enumerate((5, 200, 3)):
        wb = WriteBatch()
        tree.update_batch({b"k%d-%d" % (block, i):
                           hashlib.sha256(b"%d" % i).digest()
                           for i in range(width)}, batch=wb,
                          version=block + 1)
        db.write(wb)
    c1 = counters()
    reads = c1["smt_engine_reads"] - c0["smt_engine_reads"]
    kept = (c1["smt_engine_reads_lock_kept"]
            - c0["smt_engine_reads_lock_kept"])
    assert reads > 0
    assert kept == (0 if store == "memory" else reads)
    db.close()


def test_metadata_storage_transactions(tmp_path):
    db = NativeDB(str(tmp_path / "meta.kvlog"))
    ms = MetadataStorage(db)
    ms.write(1, b"one")
    assert ms.read(1) == b"one"
    ms.begin_atomic_write()
    ms.write(1, b"uno")
    ms.write(2, b"dos")
    assert ms.read(1) == b"uno"      # read-your-writes inside tran
    assert db.get((2).to_bytes(4, "big"), b"metadata") is None  # not yet
    ms.commit_atomic_write()
    assert ms.read(2) == b"dos"
    db.close()


def test_db_persistent_storage_roundtrip(tmp_path):
    db = NativeDB(str(tmp_path / "ps.kvlog"))
    ps = DBPersistentStorage(db)
    st = ps.begin_write_tran()
    st.last_view = 3
    st.last_executed_seq = 17
    st.seq(17).pre_prepare = b"\x01\x02"
    ps.end_write_tran()
    db.close()

    db = NativeDB(str(tmp_path / "ps.kvlog"))
    ps2 = DBPersistentStorage(db)
    st2 = ps2.load()
    assert st2.last_view == 3
    assert st2.last_executed_seq == 17
    assert st2.seq_states[17].pre_prepare == b"\x01\x02"
    db.close()


def test_db_persistent_storage_incremental(tmp_path):
    """Dirty/deleted seq tracking: window slide prunes rows from the DB,
    VC blobs and descriptors round-trip, and mutations via seq() on a
    pre-existing entry persist."""
    path = str(tmp_path / "ps.kvlog")
    db = NativeDB(path)
    ps = DBPersistentStorage(db)
    st = ps.begin_write_tran()
    for s in range(1, 6):
        st.seq(s).pre_prepare = b"pp%d" % s
    st.restrictions = [b"r1", b"r2"]
    st.carried_certs = [b"c1"]
    st.carried_bodies = [b"b1"]
    ps.end_write_tran()
    # second tran: mutate one entry, slide the window past seq 3
    st = ps.begin_write_tran()
    st.seq(4).commit_full = b"cf4"
    st.last_stable_seq = 3
    for s in [s for s in st.seq_states if s <= 3]:
        del st.seq_states[s]
    ps.end_write_tran()
    db.close()

    db = NativeDB(path)
    st2 = DBPersistentStorage(db).load()
    assert sorted(st2.seq_states) == [4, 5]
    assert st2.seq_states[4].pre_prepare == b"pp4"
    assert st2.seq_states[4].commit_full == b"cf4"
    assert st2.last_stable_seq == 3
    assert st2.restrictions == [b"r1", b"r2"]
    assert st2.carried_certs == [b"c1"]
    assert st2.carried_bodies == [b"b1"]
    # pruned rows are gone from the seq family on disk
    assert db.get((1).to_bytes(8, "big"), b"metaseq") is None
    db.close()


def test_db_persistent_storage_fresh_db_seq_only_commit(tmp_path):
    """A fresh DB whose first commits touch only seq rows (descriptor
    scalars still at defaults — the normal prepare-before-execute order)
    must still recover those rows: the desc row is the layout marker and
    has to ride any first write."""
    path = str(tmp_path / "ps.kvlog")
    db = NativeDB(path)
    ps = DBPersistentStorage(db)
    st = ps.begin_write_tran()
    st.seq(5).pre_prepare = b"\x05"
    ps.end_write_tran()
    db.close()
    db = NativeDB(path)
    st2 = DBPersistentStorage(db).load()
    assert st2.seq_states[5].pre_prepare == b"\x05"
    db.close()


def test_db_persistent_storage_legacy_json_migration(tmp_path):
    """A DB written by the old whole-state-JSON layout loads correctly."""
    import json as _json

    from tpubft.consensus.persistent import (FilePersistentStorage,
                                             PersistedState)
    path = str(tmp_path / "ps.kvlog")
    db = NativeDB(path)
    legacy = PersistedState(last_view=2, last_executed_seq=9,
                            last_stable_seq=0)
    legacy.seq(9).pre_prepare = b"\xaa"
    raw = _json.dumps(FilePersistentStorage._encode(legacy),
                      separators=(",", ":")).encode()
    db.put((1).to_bytes(4, "big"), raw, b"metadata")
    ps = DBPersistentStorage(db)
    st = ps.load()
    assert st.last_view == 2 and st.last_executed_seq == 9
    assert st.seq_states[9].pre_prepare == b"\xaa"
    # and the next commit writes the new layout
    st = ps.begin_write_tran()
    st.seq(9).commit_full = b"\xbb"
    ps.end_write_tran()
    db.close()
    db = NativeDB(path)
    st2 = DBPersistentStorage(db).load()
    assert st2.seq_states[9].commit_full == b"\xbb"
    db.close()
