"""Self-tuning data plane: the mgr autotuner engine (seeded
determinism, guarded rollback, bounds), the module's command surface
and actuation path, the telemetry spine's SLO pressure rings, and a
CPU-lenient regime-shift parity smoke."""

import json
import time

import pytest

from ceph_tpu.mgr.autotune import (KNOBS, AutotuneEngine,
                                   AutotuneModule)
from ceph_tpu.mgr.telemetry import TelemetrySpine


def _sig(*, bps=2e6, good=100.0, pressure=0.0, dov=0.1, occ=0.9,
         commit=5.0, degraded=0.0, idle=0.0, p99us=1000.0, lps=10.0):
    return {
        "osd": {"occupancy": occ, "idle_gap_s": idle,
                "dispatch_overhead": dov, "launch_p99_us": p99us,
                "commit_ms": commit, "bytes_per_sec": bps,
                "launches_per_sec": lps},
        "slo": {"pressure": pressure, "goodput_ops": good,
                "worst_p99_ms": 40.0},
        "degraded": degraded,
    }


def _varied_trace(n=40):
    """A trace that exercises several decide() guards: dispatch-bound
    stretch, SLO-pressure stretch, recovery stretch, calm tail."""
    out = []
    for i in range(n):
        if i < 12:
            out.append(_sig(dov=0.4, lps=120.0))
        elif i < 22:
            out.append(_sig(pressure=0.5, commit=80.0, good=20.0))
        elif i < 30:
            out.append(_sig(degraded=0.3, pressure=0.0))
        else:
            out.append(_sig())
    return out


def test_same_seed_same_journal():
    trace = _varied_trace()
    a, b = AutotuneEngine(seed=7), AutotuneEngine(seed=7)
    for sig in trace:
        a.step(sig)
        b.step(sig)
    assert a.journal, "trace produced no decisions — guards dead?"
    blob_a = json.dumps(a.journal, sort_keys=True)
    blob_b = json.dumps(b.journal, sort_keys=True)
    assert blob_a == blob_b
    assert a.journal_digest() == b.journal_digest()


def test_replay_reproduces_journal_bit_identically():
    eng = AutotuneEngine(seed=13)
    for sig in _varied_trace():
        eng.step(sig)
    assert eng.journal
    rep = AutotuneEngine.replay(13, eng.trace)
    assert json.dumps(rep.journal, sort_keys=True) == \
        json.dumps(eng.journal, sort_keys=True)
    assert rep.journal_digest() == eng.journal_digest()


def test_regression_triggers_rollback_within_cooldown():
    eng = AutotuneEngine(seed=3)
    # dispatch-bound but healthy: some knob steps up
    adjust = None
    for _ in range(10):
        for d in eng.step(_sig(dov=0.4, lps=120.0)):
            if d["action"] == "adjust":
                adjust = d
                break
        if adjust:
            break
    assert adjust is not None, "no adjustment under dispatch pressure"
    knob, old = adjust["knob"], adjust["old"]
    # objective collapses right after the move → rollback
    rollback = None
    for _ in range(AutotuneEngine.COOLDOWN + 1):
        for d in eng.step(_sig(bps=1e4, good=1.0, dov=0.4,
                               lps=120.0)):
            if d["action"] == "rollback" and d["knob"] == knob:
                rollback = d
                break
        if rollback:
            break
    assert rollback is not None, "regression never rolled back"
    assert rollback["new"] == old, "rollback missed pre-decision value"
    assert eng.values[knob] == old
    assert rollback["tick"] - adjust["tick"] <= \
        AutotuneEngine.COOLDOWN
    assert eng.rollbacks_total == 1
    # the direction that hurt is barred: the same move is not retried
    # immediately even under the original signal
    for _ in range(AutotuneEngine.ROLLBACK_COOLDOWN):
        for d in eng.step(_sig(dov=0.4, lps=120.0)):
            assert not (d["action"] == "adjust"
                        and d["knob"] == knob
                        and d["dir"] == adjust["dir"]), \
                "rolled-back direction retried inside the bar"


def test_values_never_leave_bounds():
    eng = AutotuneEngine(seed=5)
    # slam each guard alternately for a long run
    for i in range(200):
        eng.step(_sig(dov=0.5, lps=200.0) if i % 2 else
                 _sig(pressure=0.9, commit=120.0, good=5.0,
                      degraded=0.2))
    for name, knob in eng.knobs.items():
        v = eng.values[name]
        if knob.ladder is not None:
            assert v in knob.ladder, (name, v)
        else:
            assert knob.lo <= v <= knob.hi, (name, v)
    # the durability ladder may trade fsync granularity but never
    # auto-selects ack-without-durability
    assert eng.values["osd_wal_sync_mode"] != "none"


def test_pin_blocks_adjustment_and_sets_value():
    eng = AutotuneEngine(seed=9)
    eng.pin("osd_batch_flush_ms", 2.0)
    assert eng.values["osd_batch_flush_ms"] == 2.0
    for _ in range(30):
        eng.step(_sig(dov=0.5, lps=200.0))
    assert not any(e["knob"] == "osd_batch_flush_ms"
                   for e in eng.journal)
    assert eng.values["osd_batch_flush_ms"] == 2.0
    eng.unpin("osd_batch_flush_ms")


def test_slo_pressure_rings_accumulate_history():
    spine = TelemetrySpine(None)

    def ingest(violation_s, goodput):
        report = {"goodput_ops": goodput, "offered_rate": 50.0,
                  "tenants": {"t": {"s3_put": {
                      "violation_s": violation_s,
                      "in_violation": violation_s > 0,
                      "p99_ms": 80.0}}}}
        rc, _, _ = spine.handle_command(
            {"prefix": "slo ingest", "scenario": "unit",
             "report": report})
        assert rc == 0

    ingest(0.0, 40.0)
    time.sleep(0.06)        # rings need dt > 0 for a rate
    ingest(0.8, 30.0)
    dump = spine.series_dump()
    assert "slo.unit" in dump, sorted(dump)
    # slo rings surface windowed per-second numbers, not raw sums
    win = dump["slo.unit"]["violation_s_per_s"]
    assert len(win) == 2
    p = spine.slo_pressure()
    assert p["pressure"] > 0.0
    assert p["scenarios"]["unit"]["goodput_ops"] == 30.0
    assert p["worst_p99_ms"] == 80.0
    # the rates view and the series dump agree on the same windowed
    # numbers (slo rings used to be excluded from one, raw in the
    # other)
    view = spine.export_view()
    rates = view["rates"]["slo.unit"]
    assert rates["violation_s_per_s"] == pytest.approx(win[-1][1])
    assert rates["violation_s_per_s"] > 0.0
    assert view["slo_pressure"]["pressure"] > 0.0


def test_module_commands_and_actuation():
    from ceph_tpu.vstart import MiniCluster

    with MiniCluster(n_mons=1, n_osds=2) as c:
        c.start_mgr("a", modules=(TelemetrySpine, AutotuneModule))
        c.wait_for_active_mgr()
        r = c.rados()
        rc, _, st = r.mgr_command({"prefix": "autotune status"})
        assert rc == 0 and st["enabled"] is False
        assert set(st["knobs"]) == set(KNOBS)
        rc, _, out = r.mgr_command(
            {"prefix": "autotune enable", "seed": 42})
        assert rc == 0 and out["seed"] == 42
        rc, _, st = r.mgr_command({"prefix": "autotune status"})
        assert rc == 0 and st["enabled"] is True
        # pin-with-value actuates through the per-OSD admin sockets
        # into the live batch-engine attribute — no restart
        rc, _, out = r.mgr_command(
            {"prefix": "autotune pin",
             "knob": "osd_batch_flush_ms", "value": "1.5"})
        assert rc == 0 and out["pinned"] and out["value"] == 1.5
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if all(o.batch_engine.flush_ms == 1.5
                   for o in c.osds.values()):
                break
            time.sleep(0.05)
        assert all(o.batch_engine.flush_ms == 1.5
                   for o in c.osds.values())
        rc, _, st = r.mgr_command({"prefix": "autotune status"})
        assert st["knobs"]["osd_batch_flush_ms"]["pinned"]
        rc, _, _ = r.mgr_command(
            {"prefix": "autotune unpin",
             "knob": "osd_batch_flush_ms"})
        assert rc == 0
        rc, _, hist = r.mgr_command(
            {"prefix": "autotune history", "trace": True})
        assert rc == 0 and "journal_digest" in hist
        assert isinstance(hist["trace"], list)
        rc, _, out = r.mgr_command({"prefix": "autotune disable"})
        assert rc == 0 and out["enabled"] is False
        # bad knob name is rejected, not crashed
        rc, _, msg = r.mgr_command(
            {"prefix": "autotune pin", "knob": "no_such_knob"})
        assert rc == -22, msg


def test_recovery_max_active_live_observer():
    from ceph_tpu.vstart import MiniCluster

    with MiniCluster(n_mons=1, n_osds=2) as c:
        osd = c.osds[0]
        assert osd.recovery_max_active == 8
        osd.config.set("osd_recovery_max_active", 2)
        assert osd.recovery_max_active == 2


def test_regime_shift_parity_smoke():
    """The tier-1 parity smoke: one static config vs the autotuned
    run on a short regime shift (no recovery storm).  The bar is
    deliberately lenient: this guards the wiring (controller must not
    melt throughput)."""
    from ceph_tpu.vstart import MiniCluster
    from ceph_tpu.workload.scenarios import regime_shift

    kw = dict(base_rate=40.0, phase_duration=1.0, workers=8,
              seed=17, recovery=False)
    with MiniCluster(n_mons=1, n_osds=3) as c:
        static = regime_shift(cluster=c, publish=False, **kw)
    with MiniCluster(n_mons=1, n_osds=3) as c:
        c.start_mgr("auto", modules=(TelemetrySpine, AutotuneModule))
        c.wait_for_active_mgr()
        r = c.rados()
        rc, outs, _ = r.mgr_command(
            {"prefix": "autotune enable", "seed": 0xA070})
        assert rc == 0, outs
        auto = regime_shift(cluster=c, **kw)
        rc, _, hist = r.mgr_command(
            {"prefix": "autotune history", "trace": True})
        assert rc == 0
    assert set(auto["phases"]) == {"steady", "bursty",
                                   "large_object", "recovery_storm"}
    assert auto["sustained_MBps"] >= 0.5 * static["sustained_MBps"], \
        (auto["sustained_MBps"], static["sustained_MBps"])
    # the recorded trace replays to the identical journal
    rep = AutotuneEngine.replay(hist["seed"], hist["trace"])
    assert rep.journal_digest() == hist["journal_digest"]
