"""`smt_engine_reads_per_key` reads the program's process-wide totals:
the ratio where the program keeps them, and nothing — not 0 — on a
program without them (the parent commit, over which this benchmark's
files are laid too), before any key was written, or in a window that
acknowledged no write."""
import hashlib

import pytest

from cellbench import harness
from tpubft.kvbc import sparse_merkle
from tpubft.kvbc.sparse_merkle import SparseMerkleTree
from tpubft.storage.memorydb import MemoryDB
from tpubft.utils.metrics import Component


def read(ctx):
    return harness.load_by_name(
        "layer_metrics", "smt_engine_reads_per_key").read(ctx)


@pytest.fixture
def totals(monkeypatch):
    """The counters at zero for one test, whatever ran before it."""
    if not hasattr(sparse_merkle, "METRICS"):
        pytest.skip("this program keeps no totals")
    for c in sparse_merkle.METRICS.counters.values():
        monkeypatch.setattr(c, "value", 0)
    return sparse_merkle.METRICS.counters


def test_the_ratio_of_the_program_s_totals(totals):
    ctx = {"writes_acked": 600}
    assert read(ctx) is None                     # no key written yet
    totals["smt_keys_updated"].inc(1280)
    totals["smt_engine_reads"].inc(1280 * 21 + 64)
    totals["smt_siblings_bounded"].inc(1280 * 244)
    assert read(ctx) == pytest.approx(21.05)
    assert read({"writes_acked": 0}) is None


def test_the_walk_itself_feeds_it(totals):
    tree = SparseMerkleTree(MemoryDB(), use_device=False)
    vh = hashlib.sha256(b"v").digest()
    for i in range(64):
        tree.update_batch({b"key-%d" % i: vh}, version=1 + i)
    got = read({"writes_acked": 64})
    # the leaf probe, 8 bisection probes, and a sibling read for each
    # populated level of a tree of at most 64 keys
    assert 10 <= got <= 9 + 12, got
    tree.update_batch({b"key-0": vh}, version=65)        # an overwrite
    assert read({"writes_acked": 65}) * 65 == pytest.approx(got * 64 + 257)


@pytest.mark.parametrize("program", ["no_totals", "other_counters"])
def test_nothing_on_a_program_without_the_totals(monkeypatch, program):
    if program == "no_totals":
        monkeypatch.delattr(sparse_merkle, "METRICS", raising=False)
    else:
        monkeypatch.setattr(sparse_merkle, "METRICS", Component("kvbc"),
                            raising=False)
    assert read({"writes_acked": 600}) is None
