"""Block accumulation + bulk add_blocks + the level-synchronous
multi-block sparse-merkle walk: every batched path must be byte-identical
to the sequential per-block path (roots, archive rows, block rows, full
DB state) — checkpoint digests depend on it."""
import pytest

from tpubft.kvbc import (BLOCK_MERKLE, IMMUTABLE, VERSIONED_KV,
                         BlockUpdates, KeyValueBlockchain)
from tpubft.kvbc.blockchain import BlockchainError
from tpubft.kvbc.sparse_merkle import SparseMerkleTree
from tpubft.storage.memorydb import MemoryDB


def _dump(db: MemoryDB):
    return sorted(db.scan_all())


def _mixed_updates(n):
    """n blocks touching merkle + versioned + immutable categories with
    overlapping keys (cross-block dependencies in the tree walk)."""
    out = []
    for i in range(n):
        bu = BlockUpdates()
        bu.put("mk", b"shared", b"v%d" % i, cat_type=BLOCK_MERKLE)
        bu.put("mk", b"k%d" % i, b"x%d" % i, cat_type=BLOCK_MERKLE)
        if i % 2:
            bu.delete("mk", b"k%d" % (i - 1), cat_type=BLOCK_MERKLE)
        bu.put("kv", b"a", b"%d" % i, cat_type=VERSIONED_KV)
        bu.put("imm", b"once%d" % i, b"w", cat_type=IMMUTABLE,
               tags=["t%d" % (i % 2)])
        out.append(bu)
    return out


# ---------------- sparse merkle: update_batches ----------------

def test_update_batches_matches_sequential():
    import hashlib
    seq_db, bat_db = MemoryDB(), MemoryDB()
    seq_tree = SparseMerkleTree(seq_db, use_device=False)
    bat_tree = SparseMerkleTree(bat_db, use_device=False)
    blocks = []
    for i in range(5):
        ups = {b"shared": hashlib.sha256(b"v%d" % i).digest(),
               b"k%d" % i: hashlib.sha256(b"x").digest()}
        if i == 3:
            ups[b"k1"] = None          # delete a key a prior block wrote
        if i == 4:
            ups = {}                   # empty block mid-batch
        blocks.append(ups)
    seq_roots = [seq_tree.update_batch(dict(u), version=10 + i)
                 for i, u in enumerate(blocks)]
    bat_roots = bat_tree.update_batches(blocks, first_version=10)
    assert seq_roots == bat_roots
    assert _dump(seq_db) == _dump(bat_db)
    # historical proofs built from the archive rows agree too
    for ver in (10, 12, 14):
        assert seq_tree.root_at(ver) == bat_tree.root_at(ver)
        p = bat_tree.prove_at(b"shared", ver)
        vh = bat_tree.get_value_hash_at(b"shared", ver)
        assert SparseMerkleTree.verify(bat_tree.root_at(ver), b"shared",
                                       vh, p)


def test_update_batches_empty_and_single():
    db = MemoryDB()
    t = SparseMerkleTree(db, use_device=False)
    assert t.update_batches([]) == []
    r = t.update_batches([{}, {}], first_version=1)
    assert r == [t.root(), t.root()]
    import hashlib
    one = t.update_batches([{b"k": hashlib.sha256(b"v").digest()}],
                           first_version=3)
    assert one == [t.root()]


# ---------------- add_blocks ----------------

def test_add_blocks_matches_sequential_add_block():
    ups = _mixed_updates(6)
    seq_db, bat_db = MemoryDB(), MemoryDB()
    seq_bc = KeyValueBlockchain(seq_db, use_device_hashing=False)
    bat_bc = KeyValueBlockchain(bat_db, use_device_hashing=False)
    for u in ups:
        seq_bc.add_block(u)
    assert bat_bc.add_blocks(ups) == 6
    assert bat_bc.last_block_id == seq_bc.last_block_id == 6
    assert _dump(seq_db) == _dump(bat_db)
    assert seq_bc.state_digest() == bat_bc.state_digest()
    for b in range(1, 7):
        assert seq_bc.block_digest(b) == bat_bc.block_digest(b)


def test_add_blocks_notifies_listeners_in_order():
    bc = KeyValueBlockchain(MemoryDB(), use_device_hashing=False)
    seen = []
    bc.add_listener(lambda bid, bu: seen.append(bid))
    bc.add_blocks(_mixed_updates(3))
    assert seen == [1, 2, 3]


def test_add_blocks_immutable_rewrite_across_batch_rejected():
    bc = KeyValueBlockchain(MemoryDB(), use_device_hashing=False)
    a = BlockUpdates()
    a.put("imm", b"k", b"v1", cat_type=IMMUTABLE)
    b = BlockUpdates()
    b.put("imm", b"k", b"v2", cat_type=IMMUTABLE)
    with pytest.raises(Exception):
        bc.add_blocks([a, b])
    # atomic: nothing from the failed batch landed
    assert bc.last_block_id == 0
    assert bc.get_latest("imm", b"k", cat_type=IMMUTABLE) is None


# ---------------- accumulation brackets ----------------

def test_accumulation_one_commit_and_read_your_writes():
    db = MemoryDB()
    bc = KeyValueBlockchain(db, use_device_hashing=False)
    writes = []
    orig = db.write
    db.write = lambda wb: (writes.append(len(wb.ops)), orig(wb))[1]
    bc.begin_accumulation()
    for i in range(4):
        bu = BlockUpdates()
        bu.put("kv", b"k", b"v%d" % i, cat_type=VERSIONED_KV)
        bc.add_block(bu)
        # read-your-writes during the run: the handler's conflict check
        # must see the staged block
        assert bc.get_latest("kv", b"k") == (i + 1, b"v%d" % i)
    assert not writes, "accumulation must not touch the DB before end"
    assert bc.end_accumulation() == 4
    assert len(writes) == 1, "one WriteBatch per run"
    assert bc.get_latest("kv", b"k") == (4, b"v3")
    # identical to the sequential path
    seq_db = MemoryDB()
    seq = KeyValueBlockchain(seq_db, use_device_hashing=False)
    for i in range(4):
        bu = BlockUpdates()
        bu.put("kv", b"k", b"v%d" % i, cat_type=VERSIONED_KV)
        seq.add_block(bu)
    assert seq.state_digest() == bc.state_digest()
    assert _dump(seq_db) == _dump(db)


def test_accumulation_abort_rolls_back():
    db = MemoryDB()
    bc = KeyValueBlockchain(db, use_device_hashing=False)
    bu0 = BlockUpdates()
    bu0.put("kv", b"base", b"b", cat_type=VERSIONED_KV)
    bc.add_block(bu0)
    before = _dump(db)
    bc.begin_accumulation()
    bu = BlockUpdates()
    bu.put("kv", b"k", b"v", cat_type=VERSIONED_KV)
    bc.add_block(bu)
    bc.abort_accumulation()
    assert bc.last_block_id == 1
    assert _dump(db) == before
    # and the bracket is reusable after an abort
    bc.begin_accumulation()
    bc.add_block(bu)
    assert bc.end_accumulation() == 2


def _merkle_block(key, value=b"v"):
    bu = BlockUpdates()
    bu.put("mk", key, value, cat_type=BLOCK_MERKLE)
    return bu


def _has_encoded_rows(wb) -> bool:
    from tpubft.storage.interfaces import EncodedRows
    return any(isinstance(p, EncodedRows) for p in wb._parts)


def test_abort_of_a_run_with_encoded_rows_leaves_nothing_readable():
    """A merkle block's walk stages its rows encoded. An aborted run's
    are gone from every view: the ledger's, a fresh tree's, the store's."""
    db = MemoryDB()
    bc = KeyValueBlockchain(db, use_device_hashing=False)
    bc.add_block(_merkle_block(b"kept"))
    before, root = _dump(db), bc.merkle_root("mk")
    bc.begin_accumulation()
    for i in range(3):
        bc.add_block(_merkle_block(b"doomed-%d" % i))
    assert _has_encoded_rows(bc._accum.master)
    assert bc.merkle_root("mk") != root          # the run reads its own
    assert bc.get_latest("mk", b"doomed-1", cat_type=BLOCK_MERKLE) \
        is not None
    bc.abort_accumulation()
    assert bc.last_block_id == 1 and bc.merkle_root("mk") == root
    assert bc.get_latest("mk", b"doomed-1", cat_type=BLOCK_MERKLE) is None
    assert SparseMerkleTree(db, family=b"smt.mk",
                            use_device=False).root() == root
    assert _dump(db) == before
    # and the next run starts from the kept state
    bc.begin_accumulation()
    bc.add_block(_merkle_block(b"next"))
    assert bc.end_accumulation() == 2
    seq = KeyValueBlockchain(MemoryDB(), use_device_hashing=False)
    seq.add_block(_merkle_block(b"kept"))
    seq.add_block(_merkle_block(b"next"))
    assert bc.state_digest() == seq.state_digest()


def test_block_n_plus_1_reads_the_nodes_block_n_staged_encoded():
    """Inside one run nothing has reached the store: block N+1's walk
    finds block N's nodes (its probes, its sibling reads, the root) only
    through the overlay that block N's encoded rows fed."""
    db = MemoryDB()
    bc = KeyValueBlockchain(db, use_device_hashing=False)
    seq = KeyValueBlockchain(MemoryDB(), use_device_hashing=False)
    bc.begin_accumulation()
    for i in range(6):
        key = b"shared" if i % 3 == 2 else b"k%d" % i     # overwrites too
        bc.add_block(_merkle_block(key, b"v%d" % i))
        seq.add_block(_merkle_block(key, b"v%d" % i))
        assert bc.merkle_root("mk") == seq.merkle_root("mk"), i
        assert bc.prove("mk", key) == seq.prove("mk", key), i
    assert _has_encoded_rows(bc._accum.master)
    assert not _dump(db), "nothing reaches the store before the run ends"
    assert bc.end_accumulation() == 6
    assert bc.state_digest() == seq.state_digest()
    assert _dump(db) == _dump(seq._db)


def test_accumulation_extra_ops_ride_the_same_batch():
    from tpubft.storage.interfaces import WriteBatch
    db = MemoryDB()
    bc = KeyValueBlockchain(db, use_device_hashing=False)
    bc.begin_accumulation()
    bu = BlockUpdates()
    bu.put("kv", b"k", b"v", cat_type=VERSIONED_KV)
    bc.add_block(bu)
    extra = WriteBatch()
    extra.put(b"reply", b"bytes", b"respages")
    bc.end_accumulation(extra=extra)
    assert db.get(b"reply", b"respages") == b"bytes"


# ---------------- an open run and the other threads ----------------

def test_a_reader_on_another_thread_never_sees_a_run_torn():
    """The run's overlay is the process's: a reader on another thread
    (a read-only query on the dispatcher) may see an open run's blocks
    ahead of their seal — they are committed slots — but never a torn
    state. Once it has read a key it reads it ever after: through the
    overlay, through the pending store the sealed run moves into, and
    from the engine once the group has landed; and a head it has read
    always names a block it can read."""
    import threading
    from tpubft.durability.pipeline import PendingStore
    db = MemoryDB()
    bc = KeyValueBlockchain(db, use_device_hashing=False)
    store = PendingStore("t")
    bc.attach_durability(store)
    bc.add_block(_merkle_block(b"base"))
    stop, torn = threading.Event(), []

    def reader():
        seen = False
        while not stop.is_set():
            head = bc.last_block_id
            if head and bc.get_raw_block(head) is None:
                torn.append(("head names no block", head))
            hit = bc.get_latest("mk", b"in-the-run", cat_type=BLOCK_MERKLE)
            if seen and hit is None:
                torn.append(("a key read once was gone", head))
            seen = seen or hit is not None

    th = threading.Thread(target=reader, daemon=True)
    th.start()
    try:
        for _round in range(20):
            bc.begin_accumulation()
            bc.add_block(_merkle_block(b"in-the-run", b"v%d" % _round))
            bc.add_block(_merkle_block(b"other-%d" % _round))
            bc.end_accumulation(defer=True)
            run_no, batch, base = bc.take_deferred()
            base.write(batch)                  # the io thread's apply
            store.mark_applied(run_no)
    finally:
        stop.set()
        th.join(10)
    assert not th.is_alive() and not torn, torn[:3]
    assert bc.last_block_id == 41 and store.empty
    seq = KeyValueBlockchain(MemoryDB(), use_device_hashing=False)
    seq.add_block(_merkle_block(b"base"))
    for _round in range(20):
        seq.add_block(_merkle_block(b"in-the-run", b"v%d" % _round))
        seq.add_block(_merkle_block(b"other-%d" % _round))
    assert bc.state_digest() == seq.state_digest()
    assert _dump(db) == _dump(seq._db)


def test_link_st_chain_waits_for_an_open_run_then_links():
    """State transfer's link and the lane's run share the staged-read
    redirect: a link that arrives while a run is open waits for the run
    to end and then adopts what is staged after the new head."""
    import threading
    import time
    source = KeyValueBlockchain(MemoryDB(), use_device_hashing=False)
    for i in range(4):
        source.add_block(_merkle_block(b"k%d" % i))
    bc = KeyValueBlockchain(MemoryDB(), use_device_hashing=False)
    bc.add_block(_merkle_block(b"k0"))
    bc.add_raw_st_blocks({b: source.get_raw_block(b) for b in (2, 3, 4)})
    opened, ended = threading.Event(), []

    def run():
        bc.begin_accumulation()
        bc.add_block(_merkle_block(b"k1"))    # the run executes block 2
        opened.set()
        time.sleep(0.4)
        ended.append(time.monotonic())
        bc.end_accumulation()

    th = threading.Thread(target=run, daemon=True)
    th.start()
    assert opened.wait(10)
    assert bc.link_st_chain() == 4
    assert ended and time.monotonic() >= ended[0], \
        "the link did not wait for the open run"
    th.join(10)
    assert not th.is_alive()
    assert bc.state_digest() == source.state_digest()
    assert bc.merkle_root("mk") == source.merkle_root("mk")
