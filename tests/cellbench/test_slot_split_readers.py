"""The readers of `exec_run`'s and `dur_wait`'s three parts each, and of
the durability group's run count: a median over the window's requests
on rows that carry them, and nothing — not 0 — on rows that do not (the
parent's program) or with no row at all."""
import pytest

from cellbench import harness

PARTS = {"slot_exec_app_ms": "exec_app", "slot_exec_reply_ms": "exec_reply",
         "slot_exec_seal_ms": "exec_seal", "slot_dur_queue_ms": "dur_queue",
         "slot_dur_apply_ms": "dur_apply", "slot_dur_fsync_ms": "dur_fsync"}


def read(metric, ctx):
    return harness.load_by_name("layer_metrics", metric).read(ctx)


def split_rows():
    """A slot of 30 requests and three of one, as the program rows them:
    each part a tenth of the slot's `exec_run` or `dur_wait` times its
    place in PARTS, so the readers' answers tell the parts apart."""
    def row(reqs, run, dur, runs):
        st = dict(exec_run=run, dur_wait=dur)
        for i, stage in enumerate(PARTS.values()):
            st[stage] = (run if i < 3 else dur) * (i % 3 + 1) / 10
        return {"primary": True, "reqs": reqs, "group_runs": runs,
                "stages_ms": st}
    return [row(30, 400, 200, 3), row(1, 100, 20, 1), row(1, 110, 30, 1),
            row(1, 120, 40, 1)]


@pytest.mark.parametrize("metric,want", [
    # the median request rides the slot of 30
    ("slot_exec_app_ms", 40.0), ("slot_exec_reply_ms", 80.0),
    ("slot_exec_seal_ms", 120.0), ("slot_dur_queue_ms", 20.0),
    ("slot_dur_apply_ms", 40.0), ("slot_dur_fsync_ms", 60.0),
    ("dur_group_runs", 3),
])
def test_split_readers_weight_each_slot_by_its_requests(metric, want):
    rows = split_rows()
    assert read(metric, {"slots": rows}) == pytest.approx(want)
    # over slots, not requests, the small slots would answer
    per_slot = [dict(r, reqs=1) for r in rows]
    assert read(metric, {"slots": per_slot}) != pytest.approx(want)


def test_dur_group_runs_leaves_out_slots_no_group_covered():
    rows = split_rows()
    rows[0]["group_runs"] = 0          # the barrier slot of the window
    assert read("dur_group_runs", {"slots": rows}) == 1
    for r in rows:
        r["group_runs"] = 0
    assert read("dur_group_runs", {"slots": rows}) is None


@pytest.mark.parametrize("metric", sorted(PARTS) + ["dur_group_runs"])
def test_split_readers_find_nothing_in_the_parent_s_rows(metric):
    """The parent's rows: ten stages, `reqs` and `primary`, no split and
    no group run count."""
    parent = [{"primary": True, "reqs": 3, "stages_ms": dict(
        adm_wait=1, dispatch=2, prepare=3, commit=4, exec=5, reply=6,
        cert_lag=0, order_wait=7, exec_wait=1, exec_run=4, dur_wait=2)}]
    assert read(metric, {"slots": parent}) is None
    assert read(metric, {"slots": []}) is None
