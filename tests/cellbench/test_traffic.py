"""Each mix is a function of the seed and of its data file alone."""
import pytest

from cellbench import generate, harness

SEEDS = (7, 2_900_000_011)


@pytest.mark.parametrize("seed", SEEDS)
def test_kv_writes_are_a_function_of_the_seed(seed):
    mix = harness.load_json("traffic", "mixed_c64_bulk1.json")
    a, b = generate.kv_clients(mix, seed), generate.kv_clients(mix, seed)
    assert len(a) == 65
    assert [c.cls for c in a].count("interactive") == 64
    bulk = a[-1]
    assert bulk.cls == "bulk" and len(bulk.message(0)) == 64
    for ca, cb in zip(a, b):
        assert ca.message(3) == cb.message(3)
    other = generate.kv_clients(mix, seed + 1)
    assert a[0].message(0) != other[0].message(0)
    # keys are distinct over clients, messages and writes
    keys = [k for c in a for i in range(3) for ws in c.message(i)
            for k, _ in ws]
    assert len(keys) == len(set(keys)) == 3 * (64 + 64)
    assert all(len(k) == 21 and len(v) == 21
               for ws in bulk.message(1) for k, v in ws)


def _tiny_flood():
    mix = dict(harness.load_json("traffic", "slots.json"))
    mix.update(principals=12, messages_per_slot=12, signers=9, threshold=5,
               shares_per_slot=5, digests=2, forged=1, truncated=1,
               duplicates=1)
    return mix


@pytest.mark.parametrize("seed", SEEDS)
def test_flood_slots_are_a_function_of_the_seed(seed):
    mix = _tiny_flood()
    a, b = generate.Flood(mix, seed), generate.Flood(mix, seed)
    assert a.public_keys() == b.public_keys()
    assert a.slot(4) == b.slot(4)
    assert a.slot(4) != a.slot(5)
    assert generate.Flood(mix, seed + 1).slot(4) != a.slot(4)
    items, d, offered = a.slot(4)
    assert len(items) == 12 and len(offered) == 5 + 3
    # messages carry the slot's number: nothing repeats over a run
    assert all(msg.startswith(b"preprepare/4/") for _, msg, _ in items)
    verdicts = a.reference_verdicts(items)
    assert 1 <= verdicts.count(False) <= 3


def test_flood_mix_is_the_deployment_s_size():
    mix = harness.load_json("traffic", "slots.json")
    assert (mix["principals"], mix["messages_per_slot"]) == (1000, 1000)
    assert (mix["threshold"], mix["signers"]) == (667, 1000)
    assert mix["shares_per_slot"] == mix["threshold"]
