#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, from the client's side.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its deployment (``configs/<config>.json``) and its traffic
mix (``traffic/mixes/<traffic>.json``) are found by name through
``BENCHMARK.json``; each metric the cell reports is read by
``metrics/<metric>.py``.  One run, all in this process (the process
that holds the chip):

1. take the chip: no TPU, or fewer chips than the cell asks for, or a
   device kind with no row in ``peaks.json``, exits 2 with no result;
2. keep JAX's persistent compile cache at ``<checkout>/.jax_cache/xla``;
3. warm every device program the traffic launches, then start the
   deployment as an in-process cluster;
4. prefill, run the mix's warm-up ops;
5. measure for ``--seconds`` (``--trace 1``: under the profiler, and
   report the per-layer metrics instead of the end-to-end ones);
6. verify against the plain reference (``verify.py``);
7. print the result as the last line of stdout, and each number
   compared beside its limit as the last lines of stderr.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
GRACE_S = 60.0          # an op still out this long after the close is lost


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell's BENCHMARK.json entry with its config entry, mix name
    and the metrics it reports."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def mine(metrics):
        return [m for m in metrics
                if name in m.get("workloads", [name])]

    return {**cell, "config_entry": config,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def device_gate(chips: int) -> dict:
    """Take the chip; exit 2 (no result) without enough TPUs."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" or info["count"] < chips:
        print(f"bench: needs {chips} TPU chip(s), JAX found {info}",
              file=sys.stderr)
        sys.exit(2)
    from benchmark.trace_reduce import peak
    try:
        peak(info["kind"])
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
    return info


class CompileMeter:
    """JAX's own compile events (copied from chip_smoke's meter):
    programs built (persistent-cache loads included) and cache hits."""

    def __init__(self):
        self.builds = self.hits = 0
        self.build_s = 0.0

    def install(self):
        from jax import monitoring

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.builds += 1
                self.build_s += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        return self

    def snap(self) -> tuple[int, int, float]:
        return self.builds, self.hits, self.build_s


_METER: CompileMeter | None = None


def compile_meter() -> CompileMeter:
    global _METER
    if _METER is None:
        _METER = CompileMeter().install()
    return _METER


class RunData:
    """What a metric reader gets: the window's op records and times,
    counters before and after the window, the reduced trace."""

    def __init__(self, **kw):
        self.trace = None
        self.trace_t0 = self.trace_t1 = None
        self.__dict__.update(kw)

    def latency_quantile_ms(self, q: float) -> float | None:
        lat = sorted(math.inf if r.latency is None else r.latency
                     for r in self.records)
        if not lat:
            return None
        v = lat[max(0, math.ceil(q * len(lat)) - 1)]
        return None if math.isinf(v) else 1000.0 * v

    def traced_bytes(self) -> int:
        return sum(r.nbytes for r in self.records
                   if r.status == "ok"
                   and self.trace_t0 <= r.t_done <= self.trace_t1)


def read_metric(root: str, name: str, run: RunData):
    path = os.path.join(root, "benchmark", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def _merge(base: dict, over: dict | None) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def _device() -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, on_chip: bool = True,
             overrides: dict | None = None,
             t_start: float = T_START) -> dict:
    """One run of one cell; → the result line's dict.  `overrides`
    ({"config": {...}, "mix": {...}}) and ``on_chip=False`` exist for
    the CPU rehearsals in ``benchmark/tests`` only."""
    from benchmark.cluster import Cluster, log, warm_programs
    from benchmark.traffic.client import Client
    from benchmark.traffic.driver import Driver
    from benchmark.traffic.mix import Mix, Model
    from benchmark.verify import verify

    from ceph_tpu.utils import enable_compile_cache

    cell = load_cell(name, root)
    with open(os.path.join(root, cell["config_entry"]["file"])) as f:
        cfg = _merge(json.load(f), (overrides or {}).get("config"))
    with open(os.path.join(root, "benchmark", "traffic", "mixes",
                           f"{cell['traffic']}.json")) as f:
        params = _merge(json.load(f), (overrides or {}).get("mix"))
    enable_compile_cache()
    meter = compile_meter()
    m0 = meter.snap()

    ops = {k for k, w in params["ops"].items() if int(w) > 0}
    fault = params.get("fault") or {}
    warm_programs(cfg, reconstruct="read" in ops
                  and (ops != {"read"} or bool(fault)))
    cluster = Cluster(cfg).start()
    trace_dir = None
    try:
        mix = Mix(params, cfg, seed)
        model = Model(mix)
        driver = Driver(Client(cluster.io, mix, model), mix,
                        loop=params.get("loop", "closed"),
                        depth=int(params["depth"]),
                        rate=params.get("rate"),
                        arrivals=params.get("arrivals", "poisson"))
        setup_recs = []
        if params.get("prefill"):
            setup_recs += driver.run_ops(mix.prefill_ops(), timeout=600)
            log(f"prefilled {len(mix.oids)} objects")
        if "kill_osd_holding_shard" in fault:
            cluster.kill_osd_holding(int(fault["kill_osd_holding_shard"]),
                                     mix.oids[0])
        warm = int(params.get("warmup_ops", 0))
        setup_recs += driver.run_ops([mix.op(i) for i in range(warm)],
                                     timeout=600)
        log(f"warm: {warm} ops")
        before = cluster.counters()
        m1 = meter.snap()
        if trace:
            import jax
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t0 = time.monotonic()
        box = {}
        win = threading.Thread(
            target=lambda: box.update(out=driver.run_window(
                warm, seconds, GRACE_S, t0=t0)), name="bench-window")
        win.start()
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        t_end = time.monotonic()
        after = cluster.counters()
        if trace:
            jax.profiler.stop_trace()
        t_trace_end = time.monotonic()
        m2 = meter.snap()
        win.join()
        records = box["out"][0]
        log(f"window: {len(records)} ops")
        for r in [r for r in setup_recs + records if r.status != "ok"][:8]:
            log(f"{r.status}: {r.detail} (submitted at "
                f"{r.t_sub - t0:+.3f} s of the window)")
        device = _device()
        written = {r.oid for r in records
                   if r.status == "ok" and r.kind != "read"}
        checks = verify(cluster, model, setup_recs + records, written,
                        int(params.get("verify_sample", 12)), seed)
    finally:
        cluster.stop()
    run = RunData(records=records, t0=t0, t_end=t_end,
                  setup_s=t0 - t_start, before=before, after=after)
    out = {}
    if trace:
        from benchmark.trace_reduce import find_xplane, reduce
        pick = None if on_chip else (lambda n: n.startswith("/host:CPU"))
        run.trace = reduce(find_xplane(trace_dir), device_planes=pick)
        run.trace_t0, run.trace_t1 = t0, t_trace_end
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=run.trace["busy_s"],
                      window_s=run.trace["window_s"])
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    metrics = {}
    for m in cell["per_layer" if trace else "end_to_end"]:
        v = read_metric(root, m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    failed = sum(r.status in ("error", "lost") for r in records)
    lat = {f"p{q}": run.latency_quantile_ms(q / 100)
           for q in (50, 90, 95, 99, 100)}

    def delta(key):
        return sum(a.get(key, 0) - b.get(key, 0)
                   for b, a in zip(before["engine"], after["engine"]))
    # launches below decodes: some reconstruct flush held 2 rows or more
    recon = {"decodes": delta("recon_ops_submitted")
             - delta("recon_fast_path") - delta("recon_ops_failed"),
             "launches": delta("recon_launches")}
    print(json.dumps({"latency_ms": lat, "recon": recon, "compile": {
        "setup_builds": m1[0] - m0[0], "setup_cache_hits": m1[1] - m0[1],
        "setup_build_s": m1[2] - m0[2],
        "window_builds": m2[0] - m1[0], "window_cache_hits": m2[1] - m1[1]},
        "window": {"ops": len(records), "failed": failed,
                   "seconds": t_end - t0}}), flush=True)
    return {"correct": all(v <= lim for v, lim in checks.values()),
            "attempted": len(records), "failed": failed,
            "metrics": metrics, "device": device, **out,
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the compile caches live at a fixed path inside this checkout, so
    # only a checkout's first run compiles, and two checkouts share none
    cache = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache, "xla")
    os.environ["CEPH_TPU_CACHE_DIR"] = cache
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    chips = load_cell(args.workload)["chips"]
    device_gate(chips)
    res = run_cell(args.workload, args.seed, args.seconds,
                   bool(args.trace))
    print(json.dumps(res), flush=True)
    for k, (v, lim) in res["checks"].items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
