"""Closed-loop autotuner units (ISSUE 14): knob-policy semantics —
bounds clamping, hysteresis (no flip-flop across one noisy sample),
degraded-mode reset-to-default, frozen-pin wins over policy — plus the
controller loop, seed files, actuator seams, EV_TUNE flight events, and
the `status get tuning` / dump-provider surfaces."""
import json
import threading
import time

import pytest

from tpubft.tuning.controller import TuningController
from tpubft.tuning.knobs import (GROW, HOLD, SHRINK, Knob, KnobRegistry,
                                 load_seed, write_seed)
from tpubft.tuning.policies import (Telemetry, batch_amortize_policy,
                                    breaker_readmission_policy,
                                    client_table_policy,
                                    device_min_batch_policy,
                                    ecdsa_crossover_policy,
                                    exec_accumulation_policy,
                                    optimistic_combine_policy,
                                    st_window_policy, stage_fraction)
from tpubft.utils import flight


def _knob(name="k", value=100, lo=10, hi=1000, **kw):
    kw.setdefault("cooldown_s", 0.0)
    kw.setdefault("hysteresis", 2)
    return Knob(name=name, value=value, default=value, lo=lo, hi=hi,
                **kw)


def _reg(*knobs, clock=time.monotonic):
    r = KnobRegistry("t", clock=clock)
    for k in knobs:
        r.register(k)
    return r


# ----------------------------------------------------------------------
# knob registry semantics
# ----------------------------------------------------------------------
class TestKnobRegistry:
    def test_bounds_clamp_on_set(self):
        r = _reg(_knob())
        assert r.set("k", 5000) == 1000          # clamped to hi
        assert r.set("k", 1) == 10               # clamped to lo
        assert r.get("k") == 10

    def test_set_same_value_is_noop(self):
        r = _reg(_knob())
        assert r.set("k", 100) is None
        assert r.knob("k").changes == 0

    def test_apply_fn_pushed_on_every_change(self):
        seen = []
        r = _reg(_knob(apply_fn=seen.append))
        r.set("k", 200)
        r.set("k", 99999)
        assert seen == [200, 1000]

    def test_apply_fn_exception_does_not_lose_the_store(self):
        def boom(_v):
            raise RuntimeError("actuator died")
        r = _reg(_knob(apply_fn=boom))
        assert r.set("k", 200) == 200
        assert r.get("k") == 200

    def test_frozen_pin_blocks_set_and_step(self):
        r = _reg(_knob())
        r.freeze("k", 300)
        assert r.get("k") == 300
        assert r.set("k", 500) is None           # policy-style store
        assert r.step("k", GROW) is None
        assert r.get("k") == 300
        r.unfreeze("k")
        assert r.set("k", 500) == 500

    def test_hysteresis_no_flip_flop_on_one_noisy_sample(self):
        r = _reg(_knob())
        # sustained growth interrupted by ONE noisy shrink sample: the
        # shrink must never fire (streak of 1 < hysteresis 2)
        assert not r.vote("k", GROW)
        assert r.vote("k", GROW)                 # 2 consecutive: due
        assert r.step("k", GROW) == 150
        assert not r.vote("k", SHRINK)           # the noisy sample
        assert not r.vote("k", GROW)             # streak restarted
        assert r.vote("k", GROW)
        assert r.get("k") == 150                 # noise never moved it

    def test_hold_resets_streak(self):
        r = _reg(_knob())
        assert not r.vote("k", GROW)
        assert not r.vote("k", HOLD)
        assert not r.vote("k", GROW)             # back to streak 1
        assert r.vote("k", GROW)

    def test_cooldown_blocks_consecutive_moves(self):
        t = [0.0]
        r = _reg(_knob(cooldown_s=5.0), clock=lambda: t[0])
        r.vote("k", GROW)
        assert r.vote("k", GROW)
        assert r.step("k", GROW) == 150
        r.vote("k", GROW)
        assert not r.vote("k", GROW)             # within cooldown
        t[0] = 6.0
        assert r.vote("k", GROW)                 # cooldown elapsed

    def test_direction_flip_accounting(self):
        r = _reg(_knob())
        r.set("k", 200)
        r.set("k", 150)
        r.set("k", 180)
        assert r.knob("k").direction_flips == 2

    def test_reset_to_defaults_spares_frozen(self):
        a, b = _knob("a"), _knob("b")
        r = _reg(a, b)
        r.set("a", 500)
        r.freeze("b", 700)
        changes = r.reset_to_defaults()
        assert changes == [("a", 500, 100)]
        assert r.get("a") == 100
        assert r.get("b") == 700                 # pin survives the reset

    def test_step_policy_moves_at_least_one(self):
        k = _knob(value=10, lo=1, hi=1000, step_up=1.01, step_down=0.99)
        r = _reg(k)
        assert r.step("k", GROW) == 11           # ceil past the 1% step
        assert r.step("k", SHRINK) == 10


# ----------------------------------------------------------------------
# seed files
# ----------------------------------------------------------------------
class TestSeedFiles:
    def test_roundtrip_value_and_frozen(self, tmp_path):
        p = str(tmp_path / "seed.json")
        write_seed(p, {"a": 250, "b": {"value": 40, "frozen": True}})
        r = _reg(_knob("a"), _knob("b"))
        assert load_seed(r, p) == 2
        assert r.get("a") == 250
        assert r.knob("b").frozen and r.get("b") == 40

    def test_seed_rebaselines_default(self, tmp_path):
        p = str(tmp_path / "seed.json")
        write_seed(p, {"a": 250})
        r = _reg(_knob("a"))
        load_seed(r, p)
        r.set("a", 900)
        r.reset_to_defaults()
        assert r.get("a") == 250                 # seed IS the default now

    def test_unknown_knob_ignored(self, tmp_path):
        p = str(tmp_path / "seed.json")
        write_seed(p, {"nope": 1, "a": 50})
        r = _reg(_knob("a"))
        assert load_seed(r, p) == 1
        assert r.get("a") == 50

    def test_malformed_seed_raises(self, tmp_path):
        p = tmp_path / "seed.json"
        p.write_text('{"knobs": [1, 2]}')
        with pytest.raises(ValueError):
            load_seed(_reg(_knob("a")), str(p))


# ----------------------------------------------------------------------
# policies
# ----------------------------------------------------------------------
def _tel(slots=10, stages=None, kernels=None, depths=None,
         counters=None, health="healthy", breakers=None):
    return Telemetry(stages=stages or {}, kernels=kernels or {},
                     depths=depths or {}, counters=counters or {},
                     breakers=breakers or {},
                     health=health, completed_slots=slots)


class TestPolicies:
    def test_stage_fraction(self):
        tel = _tel(stages={"commit": {"p50_ms": 6.0},
                           "exec": {"p50_ms": 2.0},
                           "reply": {"p50_ms": 2.0}})
        assert stage_fraction(tel, "commit") == pytest.approx(0.6)
        assert stage_fraction(Telemetry(), "commit") == 0.0

    def test_amortize_holds_without_fresh_slots(self):
        pol = batch_amortize_policy("bls_msm", "commit")
        tel = _tel(slots=5)
        assert pol(tel, _tel(slots=5), _knob()) == HOLD
        assert pol(tel, None, _knob()) == HOLD

    def test_amortize_shrinks_when_latency_stage_dominates(self):
        pol = batch_amortize_policy("bls_msm", "commit")
        cur = _tel(slots=20, stages={"commit": {"p50_ms": 8.0},
                                     "exec": {"p50_ms": 1.0}})
        assert pol(cur, _tel(slots=10), _knob()) == SHRINK

    def test_amortize_grows_on_falling_per_item_cost(self):
        pol = batch_amortize_policy("bls_msm", "commit")
        prev = _tel(slots=10, kernels={"bls_msm": {
            "calls": 4, "batch_avg": 8.0, "warm_avg_ms": 1.0}})
        cur = _tel(slots=20, stages={"commit": {"p50_ms": 1.0},
                                     "exec": {"p50_ms": 4.0}},
                   kernels={"bls_msm": {"calls": 8, "batch_avg": 16.0,
                                        "warm_avg_ms": 1.5}})
        # per-item: prev 125us -> cur ~94us (falling) and commit minor
        assert pol(cur, prev, _knob()) == GROW
        # same cost, nothing falling: hold
        flat = _tel(slots=30, kernels={"bls_msm": {
            "calls": 12, "batch_avg": 16.0, "warm_avg_ms": 2.0}})
        assert pol(flat, cur, _knob()) in (HOLD,)

    def test_exec_accumulation_policy(self):
        pol = exec_accumulation_policy()
        dominated = _tel(slots=20, stages={"exec": {"p50_ms": 8.0},
                                           "commit": {"p50_ms": 1.0}})
        assert pol(dominated, _tel(slots=10), _knob(value=16)) == SHRINK
        deep = _tel(slots=20, stages={"exec": {"p50_ms": 0.5},
                                      "commit": {"p50_ms": 8.0}},
                    depths={"exec_lane": 40})
        assert pol(deep, _tel(slots=10), _knob(value=16)) == GROW
        assert pol(deep, _tel(slots=10), _knob(value=64)) == HOLD

    def test_ecdsa_crossover_policy_follows_cheaper_tier(self):
        pol = ecdsa_crossover_policy()
        prev = _tel(slots=1)
        dev_cheap = _tel(slots=2, kernels={"ecdsa": {
            "calls": 4, "batch_avg": 64.0, "warm_avg_ms": 1.0}},
            counters={"ecdsa_host_items_delta": 100,
                      "ecdsa_host_us_delta": 10000})
        # device ~15.6us/item vs host 100us/item -> admit the device
        assert pol(dev_cheap, prev, _knob()) == SHRINK
        host_cheap = _tel(slots=2, kernels={"ecdsa": {
            "calls": 4, "batch_avg": 64.0, "warm_avg_ms": 10.0}},
            counters={"ecdsa_host_items_delta": 100,
                      "ecdsa_host_us_delta": 1000})
        assert pol(host_cheap, prev, _knob()) == GROW
        # no host signal: hold
        assert pol(_tel(slots=2, kernels={"ecdsa": {
            "calls": 4, "batch_avg": 64.0, "warm_avg_ms": 1.0}}),
            prev, _knob()) == HOLD

    def test_breaker_readmission_policy(self):
        pol = breaker_readmission_policy()
        base = _tel(breakers={"device": {"trips": 2, "recoveries": 2}})
        # a NEW trip after re-admission: the cooldown was too short
        retripped = _tel(breakers={"device": {"trips": 3,
                                              "recoveries": 2}})
        assert pol(retripped, base, _knob()) == GROW
        # recoveries advanced, no new trips: plane held — re-admit faster
        held = _tel(breakers={"device": {"trips": 2, "recoveries": 3}})
        assert pol(held, base, _knob()) == SHRINK
        # a trip WITH its recovery in one interval still grows (the
        # re-trip is the signal; its recovery does not excuse it)
        both = _tel(breakers={"device": {"trips": 3, "recoveries": 3}})
        assert pol(both, base, _knob()) == GROW
        # no fresh history / no baseline: hold
        assert pol(base, base, _knob()) == HOLD
        assert pol(base, None, _knob()) == HOLD

    def test_device_min_batch_policy(self):
        pol = device_min_batch_policy()
        prev = _tel(kernels={"ed25519": {"calls": 4, "batch_avg": 64.0,
                                         "warm_avg_ms": 1.0}})
        falling = _tel(kernels={"ed25519": {"calls": 8, "batch_avg": 128.0,
                                            "warm_avg_ms": 1.5}})
        # per-item: 15.6us -> 11.7us — the device amortizes, lower the
        # floor so smaller batches ride it
        assert pol(falling, prev, _knob()) == SHRINK
        rising = _tel(kernels={"ed25519": {"calls": 8, "batch_avg": 64.0,
                                           "warm_avg_ms": 1.5}})
        assert pol(rising, prev, _knob()) == GROW
        # stale kernel counters (no fresh launches): hold
        assert pol(prev, prev, _knob()) == HOLD
        assert pol(falling, None, _knob()) == HOLD
        # ... unless the floor has grown past its configured value: it
        # may have shut the device out, and only launches teach this
        # policy anything — step back toward the configuration
        grown = Knob(name="k", value=72, default=32, lo=1, hi=1024)
        assert pol(prev, prev, grown) == SHRINK
        assert pol(falling, None, grown) == HOLD
        lowered = Knob(name="k", value=16, default=32, lo=1, hi=1024)
        assert pol(prev, prev, lowered) == HOLD

    def test_optimistic_combine_policy_vetoes_shrink_on_cert_lag(self):
        pol = optimistic_combine_policy(
            batch_amortize_policy("bls_msm", "commit"))
        commit_heavy = {"commit": {"p50_ms": 8.0, "count": 0},
                        "exec": {"p50_ms": 1.0}}
        prev = _tel(slots=10, stages=dict(
            commit_heavy, cert_lag={"count": 5}))
        # fresh cert_lag samples: replies no longer wait on the combine
        # — the dominant commit stage must NOT shrink the flush window
        cur = _tel(slots=20, stages=dict(
            commit_heavy, cert_lag={"count": 9}))
        assert pol(cur, prev, _knob()) == HOLD
        # no fresh lag samples (optimistic idle / mode off): the inner
        # policy's SHRINK passes through untouched
        stale = _tel(slots=30, stages=dict(
            commit_heavy, cert_lag={"count": 9}))
        assert pol(stale, cur, _knob()) == SHRINK
        # GROW is never vetoed: wider windows amortize the deferred
        # combine even harder
        grow_prev = _tel(slots=10, kernels={"bls_msm": {
            "calls": 4, "batch_avg": 8.0, "warm_avg_ms": 1.0}},
            stages={"cert_lag": {"count": 0}})
        grow_cur = _tel(slots=20, stages={
            "commit": {"p50_ms": 1.0}, "exec": {"p50_ms": 4.0},
            "cert_lag": {"count": 7}},
            kernels={"bls_msm": {"calls": 8, "batch_avg": 16.0,
                                 "warm_avg_ms": 1.5}})
        assert pol(grow_cur, grow_prev, _knob()) == GROW

    def test_st_window_policy(self):
        pol = st_window_policy()
        prev = _tel(counters={"st_bytes_delta": 1_000_000.0,
                              "st_failovers_delta": 0.0})
        # byte rate rising interval-over-interval: widen the pipeline
        rising = _tel(counters={"st_bytes_delta": 1_500_000.0,
                                "st_failovers_delta": 0.0})
        assert pol(rising, prev, _knob()) == GROW
        # any fresh failover shrinks — even if the rate also rose (a
        # wide window multiplies the data parked behind a dead source)
        failed = _tel(counters={"st_bytes_delta": 1_500_000.0,
                                "st_failovers_delta": 1.0})
        assert pol(failed, prev, _knob()) == SHRINK
        # falling rate: hold (failover, not throughput, drives shrink)
        falling = _tel(counters={"st_bytes_delta": 400_000.0})
        assert pol(falling, prev, _knob()) == HOLD
        # idle transfer plane / first interval: hold
        assert pol(_tel(), prev, _knob()) == HOLD
        assert pol(rising, _tel(), _knob()) == HOLD
        assert pol(rising, None, _knob()) == HOLD

    def test_client_table_policy(self):
        pol = client_table_policy()
        prev = _tel()
        # thrash: evictions and a high miss rate in the same interval —
        # the hot set doesn't fit, grow the bound
        thrash = _tel(counters={"client_table_hits_delta": 60.0,
                                "client_table_misses_delta": 40.0,
                                "client_table_evictions_delta": 35.0})
        assert pol(thrash, prev, _knob(value=1024)) == GROW
        # cold-start fill (misses but NO evictions, resident near the
        # bound): not thrash — hold
        filling = _tel(counters={"client_table_hits_delta": 10.0,
                                 "client_table_misses_delta": 90.0},
                       depths={"client_table": 900})
        assert pol(filling, prev, _knob(value=1024)) == HOLD
        # slack: traffic with zero evictions and the resident set far
        # under the bound — hand the memory back
        slack = _tel(counters={"client_table_hits_delta": 100.0,
                               "client_table_misses_delta": 1.0},
                     depths={"client_table": 80})
        assert pol(slack, prev, _knob(value=1024)) == SHRINK
        # idle table / first interval: hold
        assert pol(_tel(), prev, _knob(value=1024)) == HOLD
        assert pol(thrash, None, _knob(value=1024)) == HOLD


# ----------------------------------------------------------------------
# controller
# ----------------------------------------------------------------------
class _Sensors:
    """Stub telemetry plane the controller polls."""

    def __init__(self):
        self.slots = 0
        self.stages = {}
        self.kernels = {}
        self.health = "healthy"

    def stages_fn(self):
        return {"finalized_total": self.slots, "stages": self.stages}


def _controller(reg, sensors, **kw):
    kw.setdefault("warmup_polls", 1)
    return TuningController(
        reg, interval_s=0.01,
        stages_fn=sensors.stages_fn,
        kernels_fn=lambda: sensors.kernels,
        health_fn=lambda: sensors.health, **kw)


class TestController:
    def test_sustained_signal_converges_without_oscillation(self):
        reg = _reg(_knob("combine_flush_us", value=300, lo=0, hi=5000))
        s = _Sensors()
        c = _controller(reg, s)
        c.add_policy("combine_flush_us",
                     batch_amortize_policy("bls_msm", "commit"))
        warm, calls = 1.0, 2
        for _ in range(12):
            s.slots += 10
            calls += 2
            warm *= 0.9          # per-item keeps falling: sustained GROW
            s.stages = {"commit": {"p50_ms": 1.0},
                        "exec": {"p50_ms": 4.0}}
            s.kernels = {"bls_msm": {"calls": calls, "batch_avg": 8.0,
                                     "warm_avg_ms": warm}}
            c.poll_once()
        k = reg.knob("combine_flush_us")
        assert k.value > 300
        assert k.direction_flips == 0            # monotone ramp, no wobble
        assert k.value <= 5000

    def test_degraded_resets_and_blocks_tuning(self):
        reg = _reg(_knob("a", value=100), _knob("b", value=50, lo=10,
                                                hi=1000))
        reg.set("a", 400)
        reg.freeze("b", 90)
        s = _Sensors()
        c = _controller(reg, s)
        s.health = "degraded"
        s.slots = 10
        made = c.poll_once()
        assert [(d["knob"], d["old"], d["new"]) for d in made] \
            == [("a", 400, 100)]
        assert made[0]["source"] == "degraded-reset"
        assert reg.get("b") == 90                # frozen pin survives
        # the reset fires once per episode, not per poll
        assert c.poll_once() == []
        assert c.m_resets.value == 1

    def test_open_breaker_counts_as_degraded(self):
        from tpubft.utils.breaker import CircuitBreaker
        b = CircuitBreaker("test-tuning-breaker", failure_threshold=1,
                           cooldown_s=60.0)
        try:
            reg = _reg(_knob("a"))
            reg.set("a", 500)
            c = _controller(reg, _Sensors())
            b.record_failure()
            assert c.poll_once()[0]["source"] == "degraded-reset"
            assert reg.get("a") == 100
        finally:
            b.reset()
            from tpubft.utils import breaker as breaker_mod
            breaker_mod._registry.pop("test-tuning-breaker", None)

    def test_recovery_requires_healthy_warmup(self):
        reg = _reg(_knob("a"))
        s = _Sensors()
        c = _controller(reg, s, warmup_polls=2)
        c.add_policy("a", lambda cur, prev, k: GROW)
        s.health = "degraded"
        c.poll_once()
        s.health = "healthy"
        assert c.poll_once() == []               # streak 1 <= warmup
        assert c.poll_once() == []               # streak 2 <= warmup
        assert c.poll_once() == []               # first vote (streak 3)
        assert c.poll_once() != []               # second vote: move
        assert reg.get("a") == 150

    def test_breaker_cooldown_hysteresis_and_degraded_reset(
            self, monkeypatch):
        """The ISSUE-18 breaker_cooldown_ms policy rides the standard
        stability machinery: one noisy re-trip interval never moves the
        knob (hysteresis 2), a sustained pattern does, and a degraded
        interval resets the knob to its default like every other."""
        reg = _reg(_knob("breaker_cooldown_ms", value=1000, lo=100,
                         hi=120_000))
        c = TuningController(reg, warmup_polls=0)
        c.add_policy("breaker_cooldown_ms", breaker_readmission_policy())

        def bt(trips, recov, health="healthy"):
            return _tel(breakers={"device": {
                "state": "closed", "trips": trips,
                "recoveries": recov}}, health=health)

        feed = [bt(0, 0), bt(1, 0), bt(1, 1), bt(2, 1), bt(3, 1)]
        it = iter(feed)
        monkeypatch.setattr(c, "gather", lambda: next(it))
        c.poll_once()                            # baseline (prev=None)
        assert c.poll_once() == []               # GROW streak 1: no move
        assert c.poll_once() == []               # SHRINK: streak reset
        c.poll_once()                            # GROW streak 1 again
        made = c.poll_once()                     # GROW streak 2: move
        assert made and made[0]["knob"] == "breaker_cooldown_ms"
        assert reg.get("breaker_cooldown_ms") > 1000
        # degraded interval: the moved knob backs off to its default
        it = iter([bt(3, 1, health="degraded")])
        made = c.poll_once()
        assert made[0]["source"] == "degraded-reset"
        assert reg.get("breaker_cooldown_ms") == 1000

    def test_ev_tune_flight_event_and_decision_log(self):
        if not flight.enabled():
            pytest.skip("flight recorder disabled")
        reg = _reg(_knob("a"))
        c = _controller(reg, _Sensors())
        c.add_policy("a", lambda cur, prev, k: GROW)
        for _ in range(4):
            c.poll_once()
        assert reg.get("a") > 100
        evs = [e for e in flight._ring().events()
               if e[1] == flight.EV_TUNE]
        assert evs, "no EV_TUNE event recorded"
        d = c.decisions()[-1]
        t, code, seq, view, arg = evs[-1]
        assert seq == reg.knob_id("a")
        assert (view, arg) == (d["old"], d["new"])
        assert d["knob"] == "a" and d["new"] == reg.get("a")

    def test_status_render_and_dump_provider(self):
        reg = _reg(_knob("a"))
        c = _controller(reg, _Sensors())
        c.track("a")
        payload = json.loads(c.render())
        assert payload["knobs"]["a"]["value"] == 100
        assert payload["knobs"]["a"]["lo"] == 10
        assert "decisions" in payload
        # the dump-provider hook: controller state rides flight dumps
        c.start()
        try:
            snap = flight.snapshot(max_events_per_ring=1)
            prov = snap["providers"]
            assert any(k.startswith("tuning") for k in prov) or prov
        finally:
            c.stop()
        assert f"{c._name}" not in flight._providers

    def test_broken_sensor_reads_as_no_signal(self):
        reg = _reg(_knob("a"))
        c = TuningController(
            reg, stages_fn=lambda: 1 / 0,
            health_fn=lambda: "healthy", warmup_polls=0)
        c.add_policy("a", batch_amortize_policy("bls_msm", "commit"))
        for _ in range(4):
            assert c.poll_once() == []           # HOLD, never a crash
        assert reg.get("a") == 100

    def test_broken_health_sensor_fails_safe_as_degraded(self):
        """A failing PERF sensor is 'no signal' (policies hold), but a
        failing HEALTH sensor must fail SAFE: the degraded rule fires
        and tuned knobs back off — a broken telemetry plane must never
        read as 'healthy and keep tuning'."""
        reg = _reg(_knob("a"))
        reg.set("a", 500)
        c = TuningController(
            reg, health_fn=lambda: 1 / 0, warmup_polls=0)
        made = c.poll_once()
        assert [(d["knob"], d["new"]) for d in made] == [("a", 100)]
        assert made[0]["source"] == "degraded-reset"


# ----------------------------------------------------------------------
# actuator seams
# ----------------------------------------------------------------------
class TestActuatorSeams:
    def test_flush_batcher_reconfigure_live(self):
        from tpubft.utils.batcher import FlushBatcher
        drained = []
        evt = threading.Event()

        def drain(batch):
            drained.append(list(batch))
            evt.set()

        b = FlushBatcher(drain, batch_size=64, flush_us=200_000,
                         name="t-batcher")
        try:
            b.reconfigure(batch_size=2, flush_us=100_000)
            assert b.batch_size == 2 and b.flush_us == 100_000
            b.submit(1)
            b.submit(2)                          # fills the NEW cap
            assert evt.wait(2.0)
            assert drained and len(drained[0]) == 2
        finally:
            b.stop()

    def test_exec_lane_set_max_accumulation(self):
        from tpubft.consensus.execution import ExecutionLane

        class _R:
            id = 0

            class m_exec_lane_depth:
                @staticmethod
                def set(v):
                    pass

        lane = ExecutionLane(_R(), 16, 150)
        lane.set_max_accumulation(4)
        assert lane.max_accumulation == 4
        lane.set_max_accumulation(0)             # clamped to >= 1
        assert lane.max_accumulation == 1

    def test_ecdsa_crossover_override(self):
        from tpubft.crypto import tpu
        base = tpu.ecdsa_crossover()
        try:
            tpu.set_ecdsa_crossover(7)
            assert tpu.ecdsa_crossover() == 7
            assert tpu._ecdsa_device_crossover() == 7
        finally:
            tpu.set_ecdsa_crossover(None)
        assert tpu.ecdsa_crossover() == base


# ----------------------------------------------------------------------
# live replica integration (catalog + status surface)
# ----------------------------------------------------------------------
EXPECTED_KNOBS = {
    "verify_batch_flush_us", "verify_batch_size", "combine_flush_us",
    "combine_batch_max", "execution_max_accumulation",
    "admission_high_watermark", "ecdsa_crossover_b",
    "device_min_verify_batch", "st_window_ranges", "breaker_cooldown_ms",
    "durability_group_max", "durability_window_us", "client_table_max",
}


# the device plane's knobs exist only on a replica whose resolved
# backend is the device: a cpu-backend replica never starts JAX
DEVICE_KNOBS = {"ecdsa_crossover_b", "device_min_verify_batch"}


def _expected_knobs(backend):
    """crypto_shard_count registers only on multi-chip hosts (the
    tier-1 conftest forces an 8-device CPU mesh, so it is present
    here — but keep the guard honest for single-device runs)."""
    if backend == "cpu":
        return EXPECTED_KNOBS - DEVICE_KNOBS
    from tpubft.ops.dispatch import crypto_mesh
    extra = {"crypto_shard_count"} if crypto_mesh().device_count() > 1 \
        else set()
    return EXPECTED_KNOBS | extra


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_replica_tuning_catalog_and_status(backend):
    """An in-process cluster with the autotuner on registers the full
    knob catalog, serves `status get tuning`, and the controller's
    degraded rule observes the replica's real health plane."""
    from tpubft.testing.cluster import InProcessCluster
    with InProcessCluster(f=1, cfg_overrides={
            "crypto_backend": backend,
            "autotune_enabled": True,
            "autotune_interval_ms": 50}) as cluster:
        rep = cluster.replicas[0]
        assert rep.tuning is not None
        assert set(rep.tuning.registry.names()) == _expected_knobs(backend)
        payload = json.loads(rep.tuning.render())
        assert set(payload["knobs"]) == _expected_knobs(backend)
        assert payload["active"] is True
        # defaults mirror the config fields the knobs replaced
        assert payload["knobs"]["combine_flush_us"]["value"] \
            == rep.cfg.combine_flush_us
        assert payload["knobs"]["execution_max_accumulation"]["value"] \
            == rep.cfg.execution_max_accumulation
        # actuator seam is live: a manual store reaches the lane
        rep.tuning.registry.set("execution_max_accumulation", 4)
        assert rep.exec_lane.max_accumulation == 4
        # ... and the paged client table's residency bound
        rep.tuning.registry.set("client_table_max", 512)
        assert rep.clients.max_resident == 512


def test_replica_autotune_disabled():
    from tpubft.testing.cluster import InProcessCluster
    with InProcessCluster(f=1, cfg_overrides={
            "autotune_enabled": False}) as cluster:
        assert cluster.replicas[0].tuning is None
