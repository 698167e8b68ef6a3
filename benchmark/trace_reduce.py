"""Reduce a profiler trace (``.xplane.pb``, as ``jax.profiler`` writes
it) to the device's busy time, its idle share, the device ops that took
most time, and the longest idle gaps named by what the benchmark's own
host spans (``jax.profiler.TraceAnnotation``) were doing in them.

Busy time is the union of the intervals in which an operation ran on
one device plane (its ``XLA Ops`` line where the plane has one, else
every event that carries an ``hlo_op`` stat), averaged over the device
planes.  The window is the profiler session itself.
"""

from __future__ import annotations

import glob
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
HOST_SPANS = ("client_submit", "client_wait")


def peak(kind: str) -> dict:
    """The published peaks of one device kind; a kind that is not in
    ``peaks.json`` is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no entry in peaks.json")
    return table[kind]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals) -> list[tuple[float, float]]:
    """Merge [start, end) intervals; → sorted, disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(ivs, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def _overlap(ivs, lo: float, hi: float) -> float:
    return sum(e - s for s, e in _clip(ivs, lo, hi))


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def device_events(plane) -> list[tuple[float, float, str]]:
    """(start ns, end ns, name) of every op that ran on one plane."""
    lines = list(plane.lines)
    ops = [ln for ln in lines if ln.name == "XLA Ops"]
    out = []
    for ln in ops or lines:
        for ev in ln.events:
            st = None if ops else _stats(ev)
            if st is not None and "hlo_op" not in st:
                continue
            # a TPU op's event name is its whole HLO instruction: keep
            # "%name = type[shape]"
            module = (st or {}).get("hlo_module")
            name = ev.name.split("{", 1)[0].strip()
            name = f"{module}/{name}" if module else name
            out.append((ev.start_ns, ev.start_ns + ev.duration_ns, name))
    return out


def reduce(path: str, *, device_planes=None, host_spans=HOST_SPANS,
           top: int = 10) -> dict:
    """→ {"busy_s", "window_s", "idle_pct", "device_events",
    "device_ops": [[name, s], ...], "idle_gaps": [[name, s], ...]}.

    `device_planes` picks the planes that are devices (default: names
    starting ``/device:``); a CPU trace in tests passes its host plane.
    """
    from jax.profiler import ProfileData
    pick = device_planes or (lambda name: name.startswith("/device:"))
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    t_start = t_stop = None
    for pl in planes:
        st = dict(pl.stats) if pl.name == "Task Environment" else {}
        if "profile_start_time" in st and "profile_stop_time" in st:
            t_start = float(st["profile_start_time"])
            t_stop = float(st["profile_stop_time"])
    dev = [device_events(pl) for pl in planes if pick(pl.name)]
    dev = [evs for evs in dev if evs]
    if not dev:
        raise ValueError("the trace holds no device operation")
    spans: dict[str, list] = {n: [] for n in host_spans}
    for pl in planes:
        for ln in pl.lines:
            for ev in ln.events:
                if ev.name in spans:
                    spans[ev.name].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    # event times are offsets from the session start; were they
    # wall-clock instead, shift them onto the session
    first = min(s for evs in dev for s, _e, _n in evs)
    shift = t_start if t_start is not None and first >= t_start else 0.0
    dev = [[(s - shift, e - shift, n) for s, e, n in evs] for evs in dev]
    spans = {n: union((s - shift, e - shift) for s, e in v)
             for n, v in spans.items()}
    if t_start is not None:
        window = t_stop - t_start
    else:
        window = max([e for evs in dev for _s, e, _n in evs]
                     + [e for v in spans.values() for _s, e in v])
    busy = []
    by_op: dict[str, float] = {}
    gaps = []
    for evs in dev:
        merged = _clip(union((s, e) for s, e, _n in evs), 0.0, window)
        busy.append(sum(e - s for s, e in merged))
        for s, e, n in evs:
            by_op[n] = by_op.get(n, 0.0) + (e - s)
        edges = [0.0] + [x for iv in merged for x in iv] + [window]
        for lo, hi in zip(edges[::2], edges[1::2]):
            if hi > lo:
                cover = {n: _overlap(v, lo, hi) for n, v in spans.items()}
                name = max(cover, key=cover.get) if cover else ""
                if not cover or cover[name] <= 0:
                    name = "no_host_span"
                gaps.append((hi - lo, f"{name}@{lo / 1e9:.3f}s"))
    busy_ns = sum(busy) / len(busy)
    gaps.sort(reverse=True)
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window / 1e9,
        "idle_pct": 100.0 * (1.0 - busy_ns / window),
        "device_events": sum(len(evs) for evs in dev),
        "device_ops": [[n, t / 1e9] for n, t in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, t / 1e9] for t, n in gaps[:top]],
    }
