"""The two cells PR 27 brought, each through whole runs at a tiny size
on XLA-CPU, in one file so that their runs never share the CPU with each
other: `skvbc_n7_bls.mixed_c64_bulk1` — seven replicas, threshold-BLS
certificates, the `served_bls` driver — sound, and `correct` coming out
false, by the comparison each names, under the three plants of
`cellbench/control_bls.py`; and `skvbc_n4.batch64_c8` — the served n=4
configuration under bulk loaders alone. The look for a chip is the one
thing skipped (`require_tpu=False`); main() keeps it.
"""
import pytest

from cellbench import control_bls, generate, harness, run

CELL = "skvbc_n7_bls.mixed_c64_bulk1"
BULK = "skvbc_n4.batch64_c8"
SECONDS = 4
SOUND_SECONDS = 8      # room for a whole round when six workers share the CPU
NEW = {"slot_fast_path_pct", "cert_share_sign_ms",
       "cert_share_decompress_ms", "cert_combine_ms", "cert_verify_ms"}
OWN = {"certificate_mismatches", "certificates_unverified",
       "certificate_slots_missing", "slots_on_no_path"}


def tiny_cell():
    cell = harness.Cell(CELL)
    cell.traffic["classes"]["interactive"]["clients"] = 3
    cell.traffic["classes"]["bulk"]["writes_per_message"] = 32
    # the first run of this process lowers and compiles the kernel ahead
    # of time, as every run on the chip does; the later ones find it in
    # the process. Two of XLA-CPU's size classes (64 and 256 lanes; one
    # tile on the chip): a backup may verify a PrePrepare's 35 client
    # signatures in one batch with the 35 the clients sent it
    cell.workload["programs"] = ({} if _warmed
                                 else {"ed25519_batches": [32, 128]})
    _warmed.append(True)
    cell.workload["warmup_s"] = 1
    cell.workload["settle_quiet_s"] = 1
    cell.workload["check_slots"] = 8
    return cell


_warmed = []


def test_the_cell_is_the_n4_cells_traffic_on_the_n7_deployment():
    cell, n4 = harness.Cell(CELL), harness.Cell("skvbc_n4.mixed_c64_bulk1")
    assert cell.traffic == n4.traffic and cell.chips == 1
    assert cell.config["cluster"] == {"n": 7, "f": 2, "c": 0}
    assert cell.config["driver"] == "served_bls"
    # the configuration sets these five and no other ReplicaConfig field
    assert cell.config["replica_config"] == {
        "crypto_backend": "tpu", "threshold_scheme": "threshold-bls",
        "client_sig_scheme": "ed25519", "view_change_timer_ms": 60000}
    served = {m["name"] for m in n4.per_layer()}
    mine = {m["name"] for m in cell.per_layer()}
    assert served <= mine and mine - served == NEW - served
    assert {m["name"] for m in cell.end_to_end()} \
        == {m["name"] for m in n4.end_to_end()}


def test_a_sound_traced_run_is_correct_and_complete(one_chip_plane):
    cell = tiny_cell()
    r = run.run_cell(cell, 2_900_000_121, SOUND_SECONDS, True,
                     require_tpu=False)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "compared"
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert OWN <= set(r["compared"])
    assert all(v["limit"] == 0 and v["value"] == 0
               for v in r["compared"].values())
    got = r["metrics"]
    # no device plane on XLA-CPU: the trace's metrics stay out
    assert not [n for n in got if "roofline" in n or "idle" in n]
    assert NEW | {"slot_commit_ms", "slot_exec_run_ms", "reqs_per_slot",
                  "verify_device_share", "window_writes_per_s"} <= set(got)
    units = {m["name"]: m["unit"] for m in cell.per_layer()}
    assert all(v["unit"] == units[n] for n, v in got.items())
    assert 0 <= got["slot_fast_path_pct"]["value"] <= 100
    assert all(got[n]["value"] > 0 for n in NEW - {"slot_fast_path_pct"})


@pytest.mark.parametrize("plant,must_fail,must_hold", [
    ("control.shares_swapped", "certificate_mismatches", None),
    ("fault.verify_rejects", "certificates_unverified",
     "certificate_mismatches"),
    ("fault.path_unrecorded", "slots_on_no_path", "certificate_mismatches"),
])
def test_a_broken_certificate_path_is_not_correct(one_chip_plane, plant,
                                                  must_fail, must_hold):
    with control_bls.planted(plant):
        r = run.run_cell(tiny_cell(), 2_900_000_131, SECONDS, False,
                         require_tpu=False)
    assert r["correct"] is False
    assert r["compared"][must_fail]["value"] > 0, r["compared"]
    if must_hold:
        assert r["compared"][must_hold]["value"] == 0, r["compared"]
    # the ledger path's comparisons are the served driver's, and sound
    assert r["compared"]["ledgers_divergent"]["value"] == 0
    assert r["compared"]["reads_wrong"]["value"] == 0


def test_the_mix_is_eight_loaders_of_64_write_messages():
    cell = harness.Cell(BULK)
    assert cell.chips == 1 and cell.config["driver"] == "served"
    assert cell.config == harness.Cell("skvbc_n4.mixed_c64_bulk1").config
    clients = generate.kv_clients(cell.traffic, 2_900_000_141)
    assert len(clients) == 8
    assert {c.cls for c in clients} == {"bulk"}
    assert {c.writes_per_message for c in clients} == {64}
    first = clients[0].message(0)
    assert len(first) == 64 and all(len(ws) == 1 for ws in first)
    assert all(len(k) == 21 and len(v) == 21 for ws in first for k, v in ws)
    # 512 writes in flight, and no key twice over clients and messages
    keys = [ws[0][0] for c in clients for i in range(3)
            for ws in c.message(i)]
    assert len(keys) == len(set(keys)) == 8 * 3 * 64
    # the programs warmed reach from one message's signatures to all
    # that can be in flight at once (on the chip both pad to one tile)
    batches = cell.workload["programs"]["ed25519_batches"]
    assert min(batches) == 64 and max(batches) == 8 * 64


def test_a_sound_traced_bulk_run_is_correct_and_complete(one_chip_plane):
    cell = harness.Cell(BULK)
    cell.traffic["classes"]["bulk"]["clients"] = 2
    cell.traffic["classes"]["bulk"]["writes_per_message"] = 32
    # XLA-CPU's 64- and 256-lane classes (one tile on the chip): 64
    # signatures in flight, and a PrePrepare's beside the clients' own
    cell.workload["programs"] = ({} if _warmed
                                 else {"ed25519_batches": [32, 128]})
    _warmed.append(True)
    cell.workload["warmup_s"] = 1
    cell.workload["settle_quiet_s"] = 1
    r = run.run_cell(cell, 2_900_000_151, 8, True, require_tpu=False)
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert all(v["limit"] == 0 and v["value"] == 0
               for v in r["compared"].values())
    got = r["metrics"]
    assert {"slot_fast_path_pct", "verify_device_share", "verify_batch_mean",
            "slot_commit_ms", "slot_exec_run_ms", "reqs_per_slot",
            "window_writes_per_s"} <= set(got)
    assert not [n for n in got if n.startswith("cert_")]
    # nothing but batches: the device is given every client signature
    # that comes in a batch of 32 or more
    assert got["verify_device_share"]["value"] > 50
    assert got["verify_batch_mean"]["value"] >= 32
