"""The `served_ycsb` driver through a whole run at a tiny size on XLA-CPU
(n=4, 300 records, 8 clients), its controls, YCSB's key chooser, and
the plain reference's merkle root against the program's tree. The look
for a chip is the one thing skipped (`require_tpu=False`)."""
import collections
import hashlib
import random

import pytest

from cellbench import control_ycsb, harness, run, ycsb
from cellbench.reference import ycsb as ref

CELL = "ycsb_n4.ycsb_a_c128"
SECONDS = 6


def tiny_cell(records=300):
    cell = harness.Cell(CELL)
    cell.config["recordcount"] = records
    cell.config["ledger_blocks_at_start"] = 3
    cell.traffic["clients"] = 8
    # eight clients never gather the 32 signatures a device batch needs
    # at the default floor; the rehearsal lowers it so the window
    # launches the kernel, as the cell's 128 clients do on the chip
    cell.config["replica_config"] = dict(cell.config["replica_config"],
                                         device_min_verify_batch=2)
    # the first run of this process compiles the kernel ahead of time,
    # the later ones find it in the process; the tiny load's blocks of
    # 100 records take the native walk and hash nothing on the device
    cell.workload["programs"] = ({} if _warmed
                                 else {"ed25519_batches": [32]})
    _warmed.append(True)
    cell.workload["warmup_s"] = 1
    cell.workload["settle_quiet_s"] = 1
    cell.workload["untouched_keys_read"] = 16
    return cell


_warmed = []


def test_a_sound_run_is_correct_with_every_row_at_zero(one_chip_plane):
    cell = tiny_cell()
    r = run.run_cell(cell, 3_700_000_021, SECONDS, True, require_tpu=False)
    assert r["correct"] is True, r["compared"]
    assert all(v["value"] == 0 and v["limit"] == 0
               for v in r["compared"].values()), r["compared"]
    assert {"reads_not_linearizable", "final_state_wrong",
            "merkle_root_wrong", "ledgers_divergent",
            "ed25519_device_items_missing"} <= set(r["compared"])
    assert r["failed"] == 0 and r["attempted"] > 0
    got = r["metrics"]
    # the cell's own readers print on XLA-CPU; the trace's do not
    assert {"read_p50_ms", "ro_read_ms", "ro_read_wait_ms",
            "smt_overwrite_pct", "window_writes_per_s",
            "slot_exec_run_ms"} <= set(got), got
    assert not [n for n in got if "roofline" in n or "idle" in n]
    assert got["smt_overwrite_pct"]["value"] == 100.0
    units = {m["name"]: m["unit"] for m in cell.per_layer()}
    assert all(v["unit"] == units[n] for n, v in got.items())


@pytest.mark.parametrize("plant,must_fail", [
    ("stale_reads", "reads_not_linearizable"),
    ("lost_overwrites", "acked_writes_without_block"),
])
def test_each_control_is_not_correct(one_chip_plane, plant, must_fail):
    # 30 records: a few seconds of eight clients read keys written in the
    # last two blocks often enough for a stale answer to show
    with control_ycsb.planted(plant):
        r = run.run_cell(tiny_cell(records=30), 3_700_000_031, SECONDS,
                         False, require_tpu=False)
    assert r["correct"] is False
    assert r["compared"][must_fail]["value"] > 0, r["compared"]


def test_the_program_lacking_the_counter_is_refused_at_once(monkeypatch):
    from cellbench.drivers import served_ycsb
    from tpubft.kvbc import sparse_merkle
    counters = dict(sparse_merkle.METRICS.snapshot()["counters"])
    counters.pop("smt_keys_overwritten")
    monkeypatch.setattr(sparse_merkle.METRICS, "snapshot",
                        lambda: {"counters": counters})
    with pytest.raises(SystemExit, match="smt_keys_overwritten"):
        served_ycsb.Driver(harness.Cell(CELL), 1, None)


# ---------------------------------------------------------------------
# YCSB's keys and key chooser
# ---------------------------------------------------------------------

def test_key_names_are_ycsb_s():
    # CoreWorkload's first key with orderedinserts=false
    assert ycsb.key_name(0) == b"user6284781860667377211"
    assert ycsb.fnvhash64(0) == 6284781860667377211
    assert all(ycsb.fnvhash64(v) >= 0 for v in range(1000))
    assert len({ycsb.key_name(r) for r in range(10_000)}) == 10_000


def test_the_hottest_record_takes_ycsb_s_share():
    chooser = ycsb.ScrambledZipfian(10_000)
    rng = random.Random(7)
    counts = collections.Counter(chooser.next(rng) for _ in range(100_000))
    assert set(counts) <= set(range(10_000))
    hottest = counts.most_common(1)[0][1] / 100_000
    # item 0 of the Zipfian is drawn with probability 1 / zeta(n, 0.99)
    assert hottest == pytest.approx(1 / ycsb.ZETAN, rel=0.2)
    assert ycsb.zeta(2, 0.99) == pytest.approx(1 + 0.5 ** 0.99)


def test_operations_are_a_function_of_the_seed():
    cfg = harness.Cell(CELL).config
    mix = harness.Cell(CELL).traffic

    def ops(seed):
        recs = ycsb.Records(dict(cfg, recordcount=500), seed)
        cls = ycsb.clients(mix, recs, seed)
        return [c.op(i) for c in cls[:4] for i in range(50)], recs.value(3)

    a, b, c = ops(2_900_000_001), ops(2_900_000_001), ops(2_900_000_002)
    assert a == b and a != c
    kinds = collections.Counter(k for k, _r, _v in a[0])
    assert kinds["read"] and kinds["update"]
    assert all(len(v) == 1000 for k, _r, v in a[0] if k == "update")
    assert len(a[1]) == 1000 and min(a[1]) >= 32


# ---------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------

@pytest.mark.parametrize("wide", [False, True], ids=["native", "levels"])
def test_the_plain_root_is_the_program_s(wide):
    """A random state with overwrites and deletes, block by block
    through the walk of narrow (< 192 changed leaves) or wide blocks."""
    from tpubft.kvbc.sparse_merkle import SparseMerkleTree
    from tpubft.storage import MemoryDB
    tree = SparseMerkleTree(MemoryDB(), use_device=False)
    rng = random.Random(11)
    keys = [b"user%d" % i for i in range(700)]
    state = {}
    for block in range(1, 5):
        ups = {}
        for k in rng.sample(keys, 250 if wide else 60):
            if k in state and rng.random() < 0.25:
                ups[k] = None
            else:
                ups[k] = hashlib.sha256(rng.randbytes(16)).digest()
        tree.update_batch(ups, version=block)
        for k, vh in ups.items():
            if vh is None:
                state.pop(k, None)
            else:
                state[k] = vh
        assert tree.root() == ref.merkle_root(state)
    assert ref.merkle_root({}) \
        == SparseMerkleTree(MemoryDB(), use_device=False).root()


def test_a_read_is_judged_against_the_versions_in_its_bounds():
    h = ref.History()
    h.apply(1, b"k", b"v1")
    h.apply(3, b"k", b"v3")
    h.apply(4, b"j", b"j4")
    h.apply(6, b"k", b"v6")
    assert h.read_is_linearizable(b"k", b"v1", 1, 2)
    assert h.read_is_linearizable(b"k", b"v3", 1, 3)
    assert not h.read_is_linearizable(b"k", b"v1", 3, 5)   # stale
    assert not h.read_is_linearizable(b"k", b"v6", 1, 5)   # from the future
    assert h.read_is_linearizable(b"k", b"v3", 5, 5)
    assert not h.read_is_linearizable(b"k", b"zz", 0, 9)
    assert h.read_is_linearizable(b"j", None, 0, 3)
    assert not h.read_is_linearizable(b"j", None, 4, 9)
    assert h.state() == {b"k": b"v6", b"j": b"j4"}
    # updates (sent, done, block); reads (sent, done)
    acked = [(0.0, 1.0, 11), (0.5, 3.0, 12), (2.5, 4.0, 13)]
    assert ref.bounds(acked, [(0.2, 0.4), (1.5, 2.6), (3.5, 9.0)]) \
        == [(0, 11), (11, 13), (12, 13)]
