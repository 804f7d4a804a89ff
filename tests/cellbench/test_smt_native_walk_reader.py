"""`smt_native_walk_pct` reads the program's process-wide totals: the
share where the program keeps them, and nothing — not 0 — on a program
without the counter (the parent commit, over which this benchmark's
files are laid too), before any key was written, or in a window that
acknowledged no write. A tiny served run reads 100: every block of the
served cells changes one leaf."""
import pytest

from cellbench import harness, run
from tpubft.kvbc import sparse_merkle
from tpubft.utils.metrics import Component

METRIC = "smt_native_walk_pct"


def read(ctx):
    return harness.load_by_name("layer_metrics", METRIC).read(ctx)


@pytest.fixture
def totals(monkeypatch):
    """The counters at zero for one test, whatever ran before it."""
    if "smt_keys_native" not in getattr(sparse_merkle, "METRICS",
                                        Component("kvbc")).counters:
        pytest.skip("this program keeps no such total")
    for c in sparse_merkle.METRICS.counters.values():
        monkeypatch.setattr(c, "value", 0)
    return sparse_merkle.METRICS.counters


def test_the_share_of_the_program_s_totals(totals):
    ctx = {"writes_acked": 600}
    assert read(ctx) is None                     # no key written yet
    totals["smt_keys_updated"].inc(1280)
    assert read(ctx) == 0
    totals["smt_keys_native"].inc(960)
    assert read(ctx) == pytest.approx(75.0)
    assert read({"writes_acked": 0}) is None


@pytest.mark.parametrize("program", ["no_totals", "no_such_counter"])
def test_nothing_on_a_program_without_the_counter(monkeypatch, program):
    if program == "no_totals":
        monkeypatch.delattr(sparse_merkle, "METRICS", raising=False)
    else:
        older = Component("kvbc")
        older.register_counter("smt_keys_updated").inc(64)
        older.register_counter("smt_engine_reads").inc(64 * 21)
        monkeypatch.setattr(sparse_merkle, "METRICS", older, raising=False)
    assert read({"writes_acked": 600}) is None


def test_the_manifest_lists_it_for_the_served_cells():
    m = [x for x in harness.load_manifest()["per_layer"]
         if x["name"] == METRIC]
    assert m == [{"name": METRIC, "unit": "%", "better": "higher",
                  "source": "program_counter",
                  "layer": "execution lane / ledger",
                  "moves": "write_p50_ms",
                  "workloads": ["skvbc_n4.mixed_c64_bulk1",
                                "skvbc_n7_bls.mixed_c64_bulk1",
                                "skvbc_n4.batch64_c8"]}]


def test_a_tiny_served_run_reads_100(one_chip_plane, totals):
    cell = harness.Cell("skvbc_n4.mixed_c64_bulk1")
    cell.traffic["classes"]["interactive"]["clients"] = 3
    # batches below the device tier's floor: no kernel to warm, and no
    # signature for the device to see, which alone keeps it from `correct`
    cell.traffic["classes"]["bulk"]["writes_per_message"] = 8
    cell.workload["programs"] = {}
    cell.workload["warmup_s"] = 1
    cell.workload["settle_quiet_s"] = 1
    r = run.run_cell(cell, 2_900_000_281, 6, True, require_tpu=False)
    assert r["failed"] == 0 and r["attempted"] > 0
    assert {name for name, row in r["compared"].items()
            if row["value"] > row["limit"]} <= {"device_saw_no_signature"}
    assert r["metrics"][METRIC] == {"value": 100.0, "unit": "%"}
    assert totals["smt_keys_native"].value \
        == totals["smt_keys_updated"].value > 0
