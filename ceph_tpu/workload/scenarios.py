"""Scenario scripts over the open-loop generator + SLO tracker.

Each scenario stands up (or borrows) a MiniCluster, fronts it with
the concurrent RGW gateway, drives a seeded open-loop schedule, and
returns a JSON-safe report — the dicts `mgr_command("slo ingest")`
publishes to the exporter.

- `steady_state`: one tenant at a fixed offered rate; the baseline.
- `ramp_to_collapse`: geometric rate ramp until the p99 SLO breaks or
  goodput detaches from offered load — the reported ``knee_rate`` is
  the last sustainable step (closed-loop benches can't see this
  knee; an open loop falls off it).
- `noisy_neighbor`: victim + aggressor tenants; the aggressor is
  capped via per-tenant mClock QoS
  (``osd_mclock_scheduler_client_qos``), and the victim's p99 must
  hold near its solo-run p99.
- `game_day_under_load`: the PR 6 stretch site-loss drill with the
  SLO tracker live through blackout → degraded writes → heal.
- `smoke`: the tier-1 fast path (~2 s, 50 ops/s): asserts nothing
  itself, returns drift/error numbers for the test to check.

Every scenario logs its seeds in the report; replaying with the same
seeds reproduces the identical arrival schedule
(`schedule_fingerprint` is the acceptance hook).
"""

from __future__ import annotations

import hashlib
import json
import threading
import time

from .generator import (S3_GET, S3_PUT, RBD_READ, RBD_WRITE, FS_READ,
                        FS_WRITE, LoadGenerator, OpMix, TenantProfile,
                        Throttled, merge_profiles)
from .slo import SLOTracker

DEFAULT_SLO_MS = {S3_PUT: 250.0, S3_GET: 150.0, "*": 300.0}


def schedule_fingerprint(profiles: list[TenantProfile],
                         duration: float) -> str:
    """Digest of the merged arrival schedule — equal seeds/profiles ⇒
    equal fingerprint (the scenario-replay acceptance criterion)."""
    h = hashlib.sha256()
    for op in merge_profiles(profiles, duration):
        h.update(f"{op.tenant}|{op.op_class}|{op.t_sched:.9f}|"
                 f"{op.seq}\n".encode())
    return h.hexdigest()


def _payload(size: int, seq: int) -> bytes:
    """Deterministic, non-constant payload (dedup/compression lanes
    shouldn't collapse every op into one chunk)."""
    stamp = f"{seq:016d}".encode()
    reps = (size + 63) // 64
    return (hashlib.sha256(stamp).digest() * 2 * reps)[:size]


def make_executor(s3=None, *, bucket: str = "wl",
                  rbd_image=None, fs=None, prefill: int = 16,
                  slots: int = 64):
    """Map `OpRecord`s onto real client calls.  `s3` is one S3Client
    or {tenant: S3Client} (per-tenant clients carry the QoS-tag
    header).  RBD/CephFS handles are optional; their ops serialize on
    a small lock (those clients are not thread-safe) — the mixed-op
    point is exercising all three surfaces, not maximizing RBD
    throughput."""
    rbd_lock = threading.Lock()
    fs_lock = threading.Lock()

    def _s3(op):
        return s3[op.tenant] if isinstance(s3, dict) else s3

    def execute(op):
        data = _payload(op.size, op.seq)
        if op.op_class == S3_PUT:
            st, _ = _s3(op).put(bucket,
                                f"{op.tenant}-{op.seq % slots}", data)
            if st == 503:
                raise Throttled()
            if st != 200:
                raise RuntimeError(f"PUT -> {st}")
        elif op.op_class == S3_GET:
            st, _ = _s3(op).get(bucket, f"warm-{op.seq % prefill}")
            if st == 503:
                raise Throttled()
            if st != 200:
                raise RuntimeError(f"GET -> {st}")
        elif op.op_class == RBD_WRITE:
            with rbd_lock:
                rbd_image.write((op.seq % slots) * op.size, data)
        elif op.op_class == RBD_READ:
            with rbd_lock:
                rbd_image.read((op.seq % slots) * op.size, op.size)
        elif op.op_class == FS_WRITE:
            with fs_lock:
                fs.write_file(f"/wl-{op.seq % slots}", data)
        elif op.op_class == FS_READ:
            with fs_lock:
                fs.read_file(f"/wl-{op.seq % prefill}")
        else:
            raise RuntimeError(f"unknown op class {op.op_class}")

    return execute


def _prefill(s3, bucket: str, prefill: int, size: int):
    s3.make_bucket(bucket)
    for i in range(prefill):
        st, _ = s3.put(bucket, f"warm-{i}", _payload(size, i))
        if st != 200:
            raise RuntimeError(f"prefill PUT -> {st}")


def _run_tracked(gen: LoadGenerator, tracker: SLOTracker) -> dict:
    """gen.run() with a live violation-integrator tick alongside."""
    stop = threading.Event()

    def _ticker():
        while not stop.wait(0.25):
            tracker.evaluate()

    t = threading.Thread(target=_ticker, name="slo-eval", daemon=True)
    t.start()
    try:
        open_loop = gen.run()
    finally:
        stop.set()
        t.join(timeout=2.0)
    tracker.evaluate()
    return {"open_loop": open_loop, "slo": tracker.report()}


def publish_slo(rados, report: dict, *, scenario: str = "") -> bool:
    """Push a scenario report into the mgr telemetry spine ("slo
    ingest") for the exporter's ceph_slo_* gauges.  → False when no
    active mgr answered (scenarios run fine without one)."""
    try:
        rc, _outs, _out = rados.mgr_command(
            {"prefix": "slo ingest", "scenario": scenario,
             "report": report}, timeout=5.0)
        return rc == 0
    except Exception:   # noqa: BLE001 — publication is optional
        return False


class _Rig:
    """Cluster + gateway + warmed bucket, shared by the scenarios.
    Owns (and tears down) whatever it created; borrows what the
    caller passed in."""

    def __init__(self, cluster=None, *, n_osds: int = 3,
                 osd_config: dict | None = None, gw_kw: dict
                 | None = None, prefill: int = 16,
                 size: int = 4096, tenants=("tenantA",)):
        from ..vstart import MiniCluster
        from ..rgw import S3Client
        self._own = cluster is None
        if cluster is None:
            cluster = MiniCluster(n_mons=1, n_osds=n_osds,
                                  osd_config=osd_config).start()
        self.cluster = cluster
        self.rados = cluster.rados()
        self.gw = cluster.start_rgw(self.rados, **(gw_kw or {}))
        self.bucket = "wl"
        self.s3 = {t: S3Client("127.0.0.1", self.gw.port, tenant=t)
                   for t in tenants}
        first = next(iter(self.s3.values()))
        _prefill(first, self.bucket, prefill, size)
        self.prefill = prefill

    def executor(self, **kw):
        kw.setdefault("prefill", self.prefill)
        return make_executor(self.s3, bucket=self.bucket, **kw)

    def close(self):
        if self._own:
            self.cluster.stop()


def steady_state(*, rate: float = 100.0, duration: float = 3.0,
                 seed: int = 7, workers: int = 16, size: int = 4096,
                 kind: str = "poisson", mix: OpMix | None = None,
                 slo_ms: dict | None = None, cluster=None,
                 rbd_image=None, fs=None, publish: bool = False,
                 tenant: str = "tenantA") -> dict:
    """One tenant, one sustained offered rate."""
    rig = _Rig(cluster, tenants=(tenant,), size=size)
    try:
        profile = TenantProfile(tenant, rate, kind=kind, mix=mix,
                                size=size, seed=seed)
        tracker = SLOTracker(slo_ms or DEFAULT_SLO_MS)
        gen = LoadGenerator(
            [profile],
            rig.executor(rbd_image=rbd_image, fs=fs),
            duration=duration, workers=workers, tracker=tracker)
        out = _run_tracked(gen, tracker)
        out["fingerprint"] = schedule_fingerprint([profile], duration)
        if publish:
            publish_slo(rig.rados, out["slo"],
                        scenario="steady_state")
        return out
    finally:
        rig.close()


def smoke(*, rate: float = 50.0, duration: float = 2.0,
          seed: int = 5, workers: int = 8, cluster=None) -> dict:
    """The tier-1 fast path: fixed-rate schedule, small objects."""
    return steady_state(rate=rate, duration=duration, seed=seed,
                        workers=workers, size=2048, kind="fixed",
                        cluster=cluster)


def ramp_to_collapse(*, start_rate: float = 40.0,
                     factor: float = 2.0, steps: int = 4,
                     step_duration: float = 2.0,
                     slo_p99_ms: float = 150.0, seed: int = 11,
                     workers: int = 16, size: int = 4096,
                     cluster=None) -> dict:
    """Geometric ramp; → per-step numbers + the knee.

    ``knee_rate``: the highest offered rate whose windowed p99 held
    the SLO *and* whose goodput stayed ≥90% of offered — the number a
    capacity plan can actually use.  ``collapse_rate``: the first
    step past it (None when the ramp never collapsed — raise the
    ceiling or the step count)."""
    rig = _Rig(cluster, tenants=("ramp",), size=size)
    try:
        execute = rig.executor()
        out_steps = []
        knee = collapse = None
        rate = start_rate
        for step in range(steps):
            tracker = SLOTracker({S3_GET: slo_p99_ms,
                                  S3_PUT: slo_p99_ms,
                                  "*": slo_p99_ms})
            profile = TenantProfile("ramp", rate, kind="poisson",
                                    size=size, seed=seed + step)
            gen = LoadGenerator([profile], execute,
                                duration=step_duration,
                                workers=workers, tracker=tracker)
            res = _run_tracked(gen, tracker)
            slo = res["slo"]
            lanes = slo["tenants"].get("ramp", {})
            p99 = max((lane["p99_ms"] for lane in lanes.values()),
                      default=0.0)
            offered = slo["offered_rate"]
            good = slo["goodput_ops"]
            holds = (p99 <= slo_p99_ms
                     and good >= 0.9 * offered
                     and res["open_loop"]["errors"] == 0)
            out_steps.append({
                "rate": rate, "p99_ms": p99,
                "offered_rate": offered, "goodput_ops": good,
                "drift_pct": res["open_loop"]["drift_pct"],
                "throttled": res["open_loop"]["throttled"],
                "holds_slo": holds,
            })
            if holds:
                knee = rate
            elif collapse is None:
                collapse = rate
                break       # past the knee: further steps only melt
            rate *= factor
        return {"steps": out_steps, "knee_rate": knee,
                "collapse_rate": collapse, "slo_p99_ms": slo_p99_ms,
                "seed": seed}
    finally:
        rig.close()


def _topk_top1_client(cluster):
    """Merge the per-OSD heavy-hitter sketches (client dimension) and
    return the cluster-wide #1 key by BYTES, or None when the
    sketches are off or empty (procs-mode handles expose no
    in-process OSD).  Bytes, not ops: the aggressor's execution is
    mClock-capped, so by executed-op count a well-behaved GET tenant
    can legitimately outrank it — the damage it offers the cluster is
    its write volume, which the cap cannot disguise."""
    from ..core import topk as _topk
    dumps = []
    for osd in getattr(cluster, "osds", {}).values():
        tk = getattr(osd, "topk", None)
        if tk is not None and tk.enabled:
            d = tk.dump().get("clients")
            if d and d.get("entries"):
                dumps.append(d)
    if not dumps:
        return None
    rows = _topk.rank(_topk.merge_sketches(dumps), by="bytes", n=1)
    return rows[0]["key"] if rows else None


def noisy_neighbor(*, victim_rate: float = 30.0,
                   aggressor_rate: float = 200.0,
                   duration: float = 3.0, seed: int = 23,
                   workers: int = 16, aggressor_limit: float = 60.0,
                   size: int = 4096, cluster=None) -> dict:
    """Two tenants on one gateway: a well-behaved victim (GETs at a
    modest rate) and an aggressor (PUT flood).  The aggressor's
    tenant tag is capped via per-tenant mClock QoS, so the victim's
    p99 must stay close to its solo-run p99 — the flat-victim-p99
    acceptance check reads ``p99_ratio``.

    Each tenant drives its own generator worker pool (as separate
    client fleets would): the aggressor's in-flight requests are
    bounded by ITS pool, so the shared resource under test is the
    OSD scheduler — where the per-tenant cap lives — not the test
    harness's own thread pool."""
    # both halves of per-tenant QoS: the aggressor gets a LIMIT (hard
    # ops/s ceiling on its private limit stream), the victim gets a
    # RESERVATION (its ops ride the reservation clock ahead of the
    # aggressor's weight-based share) — limit alone still lets the
    # aggressor's allowed rate contend the victim's p99 upward
    qos = {"rgw:aggressor": [0.0, 1.0, float(aggressor_limit)],
           "rgw:victim": [float(victim_rate) * 1.2, 2.0, 0.0]}
    rig = _Rig(cluster,
               osd_config={
                   "osd_op_queue": "mclock",
                   "osd_mclock_scheduler_client_qos":
                       json.dumps(qos)},
               tenants=("victim", "aggressor"), size=size)
    try:
        inner = rig.executor()
        # the tracker's log2 buckets quantize p99 to powers of two —
        # adjacent buckets differ by exactly 2x, so a 1.5x ratio bar
        # on bucket upper-bounds false-fails whenever the true p99
        # sits near an edge.  The ratio therefore comes from EXACT
        # victim latencies sampled here; the histogram numbers stay
        # in the solo/duo sub-reports for the exporter
        samples: dict[str, list[float]] = {"solo": [], "duo": []}
        phase = {"cur": "solo"}

        def execute(op):
            t0 = time.monotonic()
            inner(op)
            if op.tenant == "victim":
                samples[phase["cur"]].append(time.monotonic() - t0)

        def _exact_p99_ms(tag):
            lat = sorted(samples[tag])
            if not lat:
                return 0.0
            return lat[min(len(lat) - 1,
                           int(0.99 * len(lat)))] * 1e3

        vmix = OpMix({S3_GET: 1})
        amix = OpMix({S3_PUT: 1})
        vworkers = max(2, workers // 2)
        aworkers = max(2, workers - vworkers)
        tracker_solo = SLOTracker(DEFAULT_SLO_MS)
        victim = TenantProfile("victim", victim_rate, kind="poisson",
                               mix=vmix, size=size, seed=seed)
        gen = LoadGenerator([victim], execute, duration=duration,
                            workers=vworkers, tracker=tracker_solo)
        solo = _run_tracked(gen, tracker_solo)
        solo_p99 = _exact_p99_ms("solo")

        phase["cur"] = "duo"
        # attribution accuracy rides this drill: clear the per-OSD
        # top-K sketches so the duo window alone decides whether the
        # sketch's #1 client is the injected aggressor tenant
        for osd in getattr(rig.cluster, "osds", {}).values():
            tk = getattr(osd, "topk", None)
            if tk is not None:
                tk.reset()
        tracker_duo = SLOTracker(DEFAULT_SLO_MS)
        aggressor = TenantProfile("aggressor", aggressor_rate,
                                  kind="poisson", mix=amix,
                                  size=size, seed=seed + 1)
        vgen = LoadGenerator([victim], execute, duration=duration,
                             workers=vworkers, tracker=tracker_duo)
        agen = LoadGenerator([aggressor], execute,
                             duration=duration, workers=aworkers,
                             tracker=tracker_duo)
        agg_out: dict = {}

        def _flood():
            agg_out.update(agen.run())

        at = threading.Thread(target=_flood, name="nn-aggressor",
                              daemon=True)
        at.start()
        duo = _run_tracked(vgen, tracker_duo)
        # the victim's measurement window is closed: abandon the
        # aggressor's remaining backlog rather than draining it —
        # each PUT fans into several RADOS ops and the per-tenant
        # limit caps those, so a full drain takes
        # offered * ops_per_put / limit seconds for nothing
        agen.stop()
        at.join(timeout=120.0)
        if at.is_alive():
            raise TimeoutError("aggressor flood never drained")
        duo["open_loop_aggressor"] = agg_out
        duo_p99 = _exact_p99_ms("duo")
        agg = duo["slo"]["tenants"]["aggressor"][S3_PUT]
        top1 = _topk_top1_client(rig.cluster)
        return {
            "solo_p99_ms": solo_p99,
            "duo_p99_ms": duo_p99,
            # floor the denominator: a sub-ms solo p99 would turn
            # scheduling noise into a huge ratio
            "p99_ratio": duo_p99 / max(solo_p99, 1.0),
            "victim_errors": duo["open_loop"]["errors"],
            "aggressor_goodput_ops": agg["goodput_ops"],
            "aggressor_offered": aggressor_rate,
            "aggressor_limit": aggressor_limit,
            # workload attribution: did the space-saving sketch's
            # heaviest client match the tenant we know flooded?
            "top1_client": top1,
            "top1_is_culprit": top1 == "rgw:aggressor",
            "solo": solo, "duo": duo, "seed": seed,
        }
    finally:
        rig.close()


def regime_shift(*, cluster=None, base_rate: float = 60.0,
                 phase_duration: float = 2.0, seed: int = 17,
                 workers: int = 12, size: int = 4096,
                 large_size: int = 65536, recovery: bool = True,
                 slo_ms: dict | None = None, publish: bool = True,
                 scenario: str = "regime_shift") -> dict:
    """The autotuner proving ground: one rig, four load regimes in
    sequence — steady → bursty → large-object → recovery-storm — so a
    config tuned for any single phase is wrong for another.  Each
    phase runs its own seeded open-loop schedule and SLO tracker, and
    publishes its report to the mgr (``slo ingest``) mid-run so a
    live controller sees the pressure *while the next phase runs*.

    Returns per-phase p99/goodput/violation numbers plus
    ``sustained_MBps`` (goodput bytes over total measured time) and
    ``worst_p99_ms`` — the two scalars to compare between static
    configs and the controller.  Seeds are per-phase
    (``seed + phase_index``); fingerprints make replays checkable."""
    rig = _Rig(cluster, tenants=("shift",), size=size)
    try:
        slo = dict(slo_ms or DEFAULT_SLO_MS)
        phases = [
            ("steady", base_rate, size, None),
            ("bursty", base_rate * 3.0, size, None),
            ("large_object", max(8.0, base_rate / 4.0), large_size,
             OpMix({S3_PUT: 1})),
            ("recovery_storm", base_rate, size, None),
        ]
        cl = rig.cluster
        can_storm = (recovery and hasattr(cl, "crash_osd")
                     and len(getattr(cl, "osds", {})) >= 3)
        out_phases: dict[str, dict] = {}
        fingerprints: dict[str, str] = {}
        good_bytes = 0.0
        elapsed = 0.0
        worst_p99 = 0.0
        for i, (name, rate, psize, mix) in enumerate(phases):
            reviver = None
            if name == "recovery_storm" and can_storm:
                victim = max(cl.osds)
                cl.crash_osd(victim)
                # revive mid-phase: backfill then storms the cluster
                # while the remaining schedule is still offered
                reviver = threading.Timer(
                    phase_duration / 3.0,
                    lambda: cl.revive_osd(victim, timeout=30.0))
                reviver.daemon = True
                reviver.start()
            profile = TenantProfile("shift", rate, kind="poisson",
                                    mix=mix, size=psize,
                                    seed=seed + i)
            tracker = SLOTracker(slo)
            gen = LoadGenerator([profile], rig.executor(),
                                duration=phase_duration,
                                workers=workers, tracker=tracker)
            res = _run_tracked(gen, tracker)
            if reviver is not None:
                reviver.join(timeout=60.0)
            rep = res["slo"]
            if publish:
                publish_slo(rig.rados, rep, scenario=scenario)
            lanes = rep["tenants"].get("shift", {})
            p99 = max((lane["p99_ms"] for lane in lanes.values()),
                      default=0.0)
            worst_p99 = max(worst_p99, p99)
            good_bytes += (rep["goodput_ops"] * rep["elapsed_s"]
                           * psize)
            elapsed += rep["elapsed_s"]
            out_phases[name] = {
                "rate": rate, "size": psize,
                "p99_ms": p99,
                "goodput_ops": rep["goodput_ops"],
                "goodput_MBps": rep["goodput_ops"] * psize / 1e6,
                "offered_rate": rep["offered_rate"],
                "violation_s": sum(lane["violation_s"]
                                   for lane in lanes.values()),
                "throttled": res["open_loop"]["throttled"],
                "errors": res["open_loop"]["errors"],
            }
            fingerprints[name] = schedule_fingerprint(
                [profile], phase_duration)
        if can_storm:
            cl.wait_for_clean(timeout=60.0)
        return {
            "phases": out_phases,
            "sustained_MBps": (good_bytes / elapsed / 1e6
                               if elapsed else 0.0),
            "worst_p99_ms": worst_p99,
            "recovery_storm": can_storm,
            "seed": seed,
            "fingerprints": fingerprints,
        }
    finally:
        rig.close()


def game_day_under_load(*, rate: float = 30.0,
                        duration: float = 30.0, seed: int = 31,
                        workers: int = 16, size: int = 4096,
                        fault_seed: int = 0x5EED60D) -> dict:
    """The PR 6 stretch site-loss drill with the SLO tracker live:
    blackout the west site mid-schedule, write degraded, heal — the
    tracker's violation clock and the per-phase timings land in one
    report.  PUT-only mix: GETs of warm objects would be served
    through the degraded window for free and mask the stall."""
    from ..vstart import MiniCluster, health_event
    sites = {"east": [0, 1], "west": [2, 3]}
    cluster = MiniCluster(n_mons=5, n_osds=4, stretch_sites=sites,
                          fault_seed=fault_seed).start()
    try:
        r = cluster.rados()
        cluster.enable_stretch_mode(r)
        rig = _Rig(cluster, tenants=("drill",), size=size)
        tracker = SLOTracker(DEFAULT_SLO_MS)
        profile = TenantProfile("drill", rate, kind="poisson",
                                mix=OpMix({S3_PUT: 1}), size=size,
                                seed=seed)
        gen = LoadGenerator([profile], rig.executor(),
                            duration=duration, workers=workers,
                            tracker=tracker)
        result: dict = {}

        def _load():
            result.update(_run_tracked(gen, tracker))

        wl = threading.Thread(target=_load, name="gameday-load",
                              daemon=True)
        wl.start()
        time.sleep(min(2.0, duration / 4))      # steady before chaos
        marks = {}

        def _mark(name):
            def _do(_cl):
                marks[name] = tracker.report()
            return _do

        drill = cluster.game_day([
            {"name": "blackout",
             "action": lambda cl: cl.blackout_site("west"),
             "until": health_event("DEGRADED_STRETCH_MODE", "failed"),
             "timeout": 90.0},
            {"name": "degraded-mark", "action": _mark("degraded")},
            {"name": "heal",
             "action": lambda cl: cl.heal_sites(),
             "until": health_event("DEGRADED_STRETCH_MODE",
                                   "cleared"),
             "timeout": 150.0},
            {"name": "healed-mark", "action": _mark("healed")},
        ])
        wl.join(timeout=duration + 120.0)
        if wl.is_alive():
            raise TimeoutError("load generator never drained")
        cluster.wait_for_clean(timeout=60.0)
        return {**result, "drill": drill, "marks": marks,
                "seed": seed, "fault_seed": fault_seed}
    finally:
        cluster.stop()
