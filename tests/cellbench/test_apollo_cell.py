"""The `apollo_n31` configuration's cell (ISSUE 33), tiny on XLA-CPU:
the plain ECDSA reference against the program's host engine and its
device tier on seeded keys; what every tier does with a public key's
encodings; the `served_apollo` driver end to end, sound and under
`cellbench/control_ecdsa.py`; its refusal of a program that cannot
serve the deployment; and each reader it brought, on a context worked
by hand. The look for a chip is the one thing skipped."""
import time
from unittest import mock

import pytest

from cellbench import control_ecdsa, harness, run, work, work_ecdsa
from cellbench.reference import ecdsa as ref

CELL = "apollo_n31.mixed_c32_bulk1"
CURVE = "secp256k1"
LANES = 8
OWN = {"requests_reference_rejects", "verdict_mismatches",
       "ecdsa_device_calls_missing"}
NEW = {"ecdsa_call_device_ms", "ecdsa_prep_ms", "ecdsa_device_share",
       "cert_shares_per_flush", "client_broadcast_pct"}


# ---------------------------------------------------------------------
# the reference against the program's tiers, case by case
# ---------------------------------------------------------------------

def _cases():
    sk = [ref.secret_of(b"apollo-%d" % i) for i in range(3)]
    pk = [ref.public_of(s) for s in sk]
    msg = [b"request %d" % i for i in range(8)]
    sig = [ref.sign(sk[i % 3], msg[i]) for i in range(8)]
    r, s = sig[4][:32], sig[4][32:]
    n = ref.N.to_bytes(32, "big")
    return {                      # name: (key, message, signature), wanted
        "honest": ((pk[0], msg[0], sig[0]), True),
        "forged": ((pk[1], msg[1] + b"!", sig[1]), False),
        "truncated": ((pk[2], msg[2], sig[2][:40]), False),
        "high_s": ((pk[0], msg[3], ref.high_s(sig[3])), True),
        "r_zero": ((pk[1], msg[4], bytes(32) + s), False),
        "r_is_n": ((pk[1], msg[4], n + s), False),
        "s_zero": ((pk[1], msg[4], r + bytes(32)), False),
        "s_is_n": ((pk[1], msg[4], r + n), False),
        "duplicated": ((pk[0], msg[0], sig[0]), True),
        "other_key": ((pk[2], msg[0], sig[0]), False),
    }


CASES = _cases()


@pytest.fixture(scope="module")
def device_verdicts():
    """Every case in ONE launch of the RLC kernel on XLA-CPU (16 lanes;
    the failing aggregate bisects down to the guilty items)."""
    from tpubft.ops import ecdsa
    items = [(m, s, k) for (k, m, s), _ in CASES.values()]
    return dict(zip(CASES, ecdsa.rlc_verify_batch(CURVE, items)))


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_host_engine_and_device_tier_agree(case, device_verdicts):
    from tpubft.crypto import scalar
    (key, message, sig), wanted = CASES[case]
    assert ref.verify(key, message, sig) is wanted
    assert scalar.ecdsa_verify(key, message, sig, CURVE) is wanted
    assert scalar.ecdsa_verify_batch([(key, message, sig)], CURVE) \
        == [wanted]
    assert bool(device_verdicts[case]) is wanted


def test_reference_signs_as_rfc_6979_and_imports_nothing_of_the_program():
    from tpubft.crypto import scalar
    sk = ref.secret_of(b"apollo-0")
    assert ref.sign(sk, b"m") == scalar.ecdsa_sign(sk, b"m", CURVE)
    assert ref.public_of(sk) == scalar.ecdsa_public_key(sk, CURVE)
    with open(ref.__file__, encoding="utf-8") as fh:
        assert "tpubft" not in fh.read().replace("tpubft`", "")


def _encodings():
    key = ref.public_of(ref.secret_of(b"apollo-0"))
    x, y = key[1:33], key[33:]
    odd = y[-1] & 1
    p = ref.P.to_bytes(32, "big")
    return {                          # name: (encoding, decodes)
        "uncompressed": (key, True),
        "compressed": (bytes([2 + odd]) + x, False),
        "compressed_wrong_parity": (bytes([3 - odd]) + x, False),
        "hybrid": (bytes([6 + odd]) + x + y, False),
        "short": (key[:64], False),
        "long": (key + b"\x00", False),
        "off_curve": (key[:-1] + bytes([key[-1] ^ 1]), False),
        "x_not_below_p": (b"\x04" + p + y, False),
        "empty": (b"", False),
    }


ENCODINGS = _encodings()


@pytest.mark.parametrize("name", sorted(ENCODINGS))
def test_every_tier_takes_sec1_uncompressed_keys_only(name):
    """PR 21's standing question: OpenSSL would decode a compressed
    point, the batched host engine and the device's prechecks would
    not. Settled: `EcdsaVerifier` refuses what they refuse, under
    OpenSSL too, and the reference states the same rule."""
    from tpubft.crypto import cpu, scalar
    encoding, decodes = ENCODINGS[name]
    sk = ref.secret_of(b"apollo-0")
    sig = ref.sign(sk, b"m")
    assert (ref.decode_public(encoding) is not None) is decodes
    assert ref.verify(encoding, b"m", sig) is decodes
    assert scalar.ecdsa_verify(encoding, b"m", sig, CURVE) is decodes
    assert scalar.ecdsa_verify_batch([(encoding, b"m", sig)], CURVE) \
        == [decodes]
    assert cpu._openssl() is not None       # the tier in question
    if decodes:
        assert cpu.EcdsaVerifier(encoding, CURVE).verify(b"m", sig)
    else:
        with pytest.raises(ValueError):
            cpu.EcdsaVerifier(encoding, CURVE)


# ---------------------------------------------------------------------
# the driver, end to end
# ---------------------------------------------------------------------

def tiny_cell():
    cell = harness.Cell(CELL)
    cell.config["cluster"] = {"n": 4, "f": 1, "c": 0}
    cell.config["replica_config"].update(device_min_verify_batch=LANES,
                                         autotune_enabled=False)
    cell.traffic["classes"]["interactive"]["clients"] = 3
    cell.traffic["classes"]["bulk"]["writes_per_message"] = LANES
    cell.workload["programs"] = ({} if _warmed
                                 else {"ed25519_batches": [32, 128]})
    _warmed.append(True)
    cell.workload["ecdsa_lanes"] = [LANES]
    cell.workload["warmup_s"] = 1
    cell.workload["settle_quiet_s"] = 2
    return cell


_warmed = []


@pytest.fixture
def device_tier_on_cpu(monkeypatch, one_chip_plane):
    """XLA-CPU prefers the host engine for ECDSA; the cell is about the
    device tier, so the crossover is the accelerator's (1)."""
    monkeypatch.setenv("TPUBFT_ECDSA_CROSSOVER_B", "1")
    # and tiny: a launch of LANES lanes, a sample of LANES-item batches
    from cellbench.drivers import served_apollo
    from tpubft.ops import ecdsa
    monkeypatch.setattr(ecdsa, "DEVICE_LANES", LANES)
    monkeypatch.setattr(served_apollo, "SAMPLE_MIN", LANES)


def test_the_cell_is_the_apollo_deployment():
    cell = harness.Cell(CELL)
    assert cell.chips == 1 and cell.config["driver"] == "served_apollo"
    cluster = cell.config["cluster"]
    assert cluster == {"n": 31, "f": 10, "c": 0}
    assert cell.config["reduced"] == ["ledger_blocks_at_start"]
    # every ReplicaConfig default as it stands, but the liveness timer
    assert set(cell.config["replica_config"]) == {
        "crypto_backend", "threshold_scheme", "client_sig_scheme",
        "view_change_timer_ms"}
    assert cell.config["replica_config"]["client_sig_scheme"] \
        == "ecdsa-secp256k1"
    assert cell.config["replica_config"]["threshold_scheme"] \
        == "threshold-bls"
    assert cell.traffic["classes"] == {
        "interactive": {"clients": 32, "writes_per_message": 1,
                        "pairs_per_write": 1},
        "bulk": {"clients": 1, "writes_per_message": 64,
                 "pairs_per_write": 1}}
    assert cell.traffic["key_bytes"] == cell.traffic["value_bytes"] == 21
    mine = {m["name"] for m in cell.per_layer()}
    assert NEW <= mine
    # the trace holds the cell's ECDSA launches whole: its kernel's
    # roofline and the device's idle share are read from it
    assert {n for n in mine if "roofline" in n or "idle" in n} \
        == {"ecdsa_roofline.apollo", "device_idle_pct.skvbc"}
    assert cell.workload["ecdsa_lanes"] == [128]
    assert {m["name"] for m in cell.end_to_end()} \
        == {"write_p50_ms", "write_p95_ms", "setup_s"}


def test_a_sound_traced_run_is_correct_and_reads_the_new_metrics(
        device_tier_on_cpu):
    r = run.run_cell(tiny_cell(), 3_300_000_121, 8, True,
                     require_tpu=False)
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert OWN <= set(r["compared"])
    assert all(v["limit"] == 0 and v["value"] == 0
               for v in r["compared"].values())
    got = r["metrics"]
    assert NEW <= set(got), sorted(got)
    # no device plane on XLA-CPU: the trace's metrics stay out
    assert not [n for n in got if "roofline" in n or "idle" in n]
    assert got["ecdsa_call_device_ms"]["value"] > 0
    assert 0 < got["ecdsa_device_share"]["value"] <= 100
    assert 1 <= got["cert_shares_per_flush"]["value"] <= 4
    assert 0 <= got["client_broadcast_pct"]["value"] <= 100


def test_a_device_tier_that_accepts_everything_is_not_correct(
        device_tier_on_cpu):
    with control_ecdsa.planted():
        r = run.run_cell(tiny_cell(), 3_300_000_131, 4, False,
                         require_tpu=False)
    assert r["correct"] is False
    assert r["compared"]["verdict_mismatches"]["value"] > 0, r["compared"]
    # the honest traffic cannot show it: every other comparison holds
    assert all(v["value"] == 0 for k, v in r["compared"].items()
               if k != "verdict_mismatches"), r["compared"]


@pytest.mark.parametrize("stub,names", [
    ("kernel", "ecdsa_rlc_kernel"),
    ("replica_field", "ReplicaConfig.no_such_field"),
    ("client_field", "ClientConfig.no_such_timer"),
    ("client_default", "ClientConfig.retry_timeout_ms"),
    ("lanes", "DEVICE_LANES"),
])
def test_a_program_that_cannot_serve_the_deployment_is_refused_at_once(
        stub, names, few_ecdsa_lanes, monkeypatch):
    from cellbench.drivers import served_apollo
    from tpubft.ops import ecdsa
    monkeypatch.setattr(ecdsa, "DEVICE_LANES", few_ecdsa_lanes)  # the chip's
    cell = harness.Cell(CELL)
    patch = mock.patch.object(ecdsa, "rlc_kernel", lambda _c: (lambda: 0)) \
        if stub == "kernel" else mock.patch.object(ecdsa, "CURVES",
                                                   ecdsa.CURVES)
    if stub == "replica_field":
        cell.config["replica_config"]["no_such_field"] = 1
    elif stub == "client_field":
        cell.config["client_config"]["no_such_timer"] = 1
    elif stub == "client_default":
        cell.config["client_config"]["retry_timeout_ms"] = 9
    elif stub == "lanes":
        patch = mock.patch.object(ecdsa, "DEVICE_LANES", 64)
    t0 = time.monotonic()
    with patch, pytest.raises(SystemExit) as refusal:
        served_apollo.Driver(cell, 1, None)
    assert time.monotonic() - t0 < 1.0
    said = str(refusal.value)
    assert names in said and "cannot serve apollo_n31" in said
    assert "\n" not in said
    # and the program as it stands lacks nothing
    sound = harness.Cell(CELL)
    assert served_apollo.missing_capabilities(
        sound.config, sound.workload["ecdsa_lanes"]) == []


# ---------------------------------------------------------------------
# the readers, on contexts worked by hand
# ---------------------------------------------------------------------

def read(metric, ctx):
    return harness.load_by_name("layer_metrics", metric).read(ctx)


COUNTED = {
    "ecdsa_device_share": (
        dict(ecdsa_device_items=10, ecdsa_host_items=5),
        dict(ecdsa_device_items=310, ecdsa_host_items=105), 75.0),
    "cert_shares_per_flush": (
        dict(bls_shares_batch_decoded=31, bls_decode_batches=1),
        dict(bls_shares_batch_decoded=31 + 93, bls_decode_batches=4), 31.0),
    "client_broadcast_pct": (
        dict(client_broadcasts=2, client_sends=10),
        dict(client_broadcasts=5, client_sends=70), 5.0),
}


@pytest.mark.parametrize("metric", sorted(COUNTED))
def test_counter_readers_take_the_window_s_delta(metric):
    before, after, wanted = COUNTED[metric]
    assert read(metric, dict(apollo_before=before, apollo_after=after)) \
        == pytest.approx(wanted)
    # nothing moved in the window, a program without the counters, a
    # driver that snapshots none: nothing, never 0
    assert read(metric, dict(apollo_before=after, apollo_after=after)) \
        is None
    assert read(metric, dict(apollo_before={}, apollo_after={})) is None
    assert read(metric, {}) is None


@pytest.mark.parametrize("metric,field", [
    ("ecdsa_call_device_ms", "device_us"), ("ecdsa_prep_ms", "prep_us")])
def test_call_row_readers_take_the_median_of_the_window_s_ecdsa_rows(
        metric, field):
    from tpubft.utils import flight
    flight.kernel_profiler().reset()
    prof = flight.kernel_profiler()
    ctx = dict(before={"kernels": harness.kernel_profile()})
    assert read(metric, dict(ctx, after=ctx["before"])) is None
    with mock.patch.object(prof, "_rows", prof._rows):
        for us in (100_000, 140_000, 900_000):
            row = prof.record("ecdsa", 64, us * 1000, "closed",
                              prep_ns=us * 10)
        prof.record("ed25519", 64, 1, "closed")     # another kind
        assert set(row) >= {"prep_us", "gate_wait_us", "device_us"}
        ctx["after"] = {"kernels": harness.kernel_profile()}
        want = 140.0 if field == "device_us" else 1.4
        assert read(metric, ctx) == pytest.approx(want)
    flight.kernel_profiler().reset()


def test_roofline_reads_the_traced_launches_against_the_hand_count():
    assert work_ecdsa.ECDSA_FIELD_MULTS == 3922
    one = work_ecdsa.ecdsa_verify(1)
    assert one["ops"] == 3922 * 1024 * 2 and one["bytes"] == 130
    peak = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    least = work.least_seconds(one, peak)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(20.44e-9, rel=1e-3)
    ctx = dict(device_kind="TPU v5 lite",
               before={"kernels": {"ecdsa": (10, 640)}},
               after={"kernels": {"ecdsa": (40, 640 + 30 * 93)}},
               trace={"kernels": {"ecdsa": {"calls": 2,
                                            "device_s": 0.288}}})
    share = read("ecdsa_roofline.apollo", ctx)
    assert share == pytest.approx(100 * 2 * 93 * least["seconds"] / 0.288)
    assert 0 < share < 0.01
    # no launch in the trace, none counted in the window: nothing
    empty = dict(ctx, trace={"kernels": {"ecdsa": {"calls": 0,
                                                   "device_s": 0.0}}})
    assert read("ecdsa_roofline.apollo", empty) is None
    assert read("ecdsa_roofline.apollo",
                dict(ctx, after=ctx["before"])) is None
    assert read("ecdsa_roofline.apollo", dict(ctx, trace={"kernels": {}})) \
        is None
