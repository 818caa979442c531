"""Profiler traces: capture, normalise, and reduce to device numbers.

``capture`` wraps a window in ``jax.profiler`` tracing.  ``load`` reads the
``.xplane.pb`` it wrote into a small normalised dict:

    {"devices": {plane: {"ops": [[start_ns, dur_ns, name], ...],
                         "modules": [[start_ns, dur_ns, name], ...]}},
     "host": [[start_ns, dur_ns, name], ...]}

holding, per device plane, the ``XLA Ops`` and ``XLA Modules`` lines, and
from the host planes only the benchmark's own spans (names starting with
``bench.``).  ``reduce`` turns that into the numbers the readers use:
busy time (the union of op intervals inside the traced window), idle
share, per-program device time, per-op totals, and the longest idle gaps
named by the benchmark span the host was in.  A recorded normalised trace
is kept in ``bench/tests/data`` and the reduction is tested on it.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace device ops and the benchmark's own host spans.  The Python
    tracer (on by default) records every Python call: on the serving
    cell's host path it cost enough to push the engine past its capacity,
    so it is off, and the host tracer keeps only annotations such as the
    ``bench.*`` spans (level 1)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _op_name(name: str) -> str:
    """``%fusion.20 = f32[2,256]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.20 = f32[2,256] fusion(...`` (layouts dropped, 80 chars)."""
    return re.sub(r"\{[^{}]*\}", "", name.lstrip("%"))[:80]


def _module_name(name: str) -> str:
    return name.split("(", 1)[0]


def load(log_dir: str) -> dict:
    """Normalise the newest ``.xplane.pb`` under ``log_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key is None:
                    continue
                fix = _op_name if key == "ops" else _module_name
                dev[key] = [[float(e.start_ns), float(e.duration_ns),
                             fix(e.name)] for e in line.events]
            if dev["ops"] or dev["modules"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [float(e.start_ns), float(e.duration_ns), e.name]
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return out


def _clip(events, t0: float, t1: float):
    out = []
    for s, d, name in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b, name))
    return out


def _union(intervals):
    merged = []
    for a, b in sorted((a, b) for a, b, _ in intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def reduce(norm: dict, top: int = 10) -> dict:
    """Device numbers of a normalised trace, inside its ``bench.window``
    span (or, without one, the extent of all events)."""
    host = norm["host"]
    win = [e for e in host if e[2] == WINDOW_SPAN]
    if win:
        t0, t1 = win[0][0], win[0][0] + win[0][1]
    else:
        ev = [e for d in norm["devices"].values()
              for e in d["ops"] + d["modules"]] + list(host)
        t0 = min(e[0] for e in ev)
        t1 = max(e[0] + e[1] for e in ev)
    spans = [e for e in host if e[2] != WINDOW_SPAN]
    devices, modules, ops = {}, {}, {}
    gaps_first = None
    for name in sorted(norm["devices"]):
        dev = norm["devices"][name]
        busy_ev = _clip(dev["ops"] or dev["modules"], t0, t1)
        if not busy_ev:
            continue
        union = _union(busy_ev)
        busy = sum(b - a for a, b in union)
        devices[name] = {"busy_s": busy * 1e-9,
                         "idle_share": 1.0 - busy / (t1 - t0)}
        for a, b, mod in _clip(dev["modules"], t0, t1):
            modules.setdefault(mod, []).append((b - a) * 1e-9)
        for a, b, op in busy_ev:
            ops[op] = ops.get(op, 0.0) + (b - a) * 1e-9
        if gaps_first is None:
            edges = [t0] + [x for ab in union for x in ab] + [t1]
            gaps_first = [(edges[i], edges[i + 1])
                          for i in range(0, len(edges), 2)
                          if edges[i + 1] > edges[i]]
    n_dev = len(devices)
    if not n_dev:
        return {"window_s": (t1 - t0) * 1e-9, "devices": {}, "busy_s": 0.0,
                "modules": {}, "device_ops": [],
                "idle_gaps": []}
    gaps = sorted(gaps_first or [], key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (t1 - t0) * 1e-9,
        "devices": devices,
        "busy_s": sum(d["busy_s"] for d in devices.values()) / n_dev,
        "modules": {k: {"count": len(v), "mean_s": sum(v) / len(v),
                        "total_s": sum(v)} for k, v in modules.items()},
        "device_ops": [[k, v / n_dev] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_host_span(spans, a, b), (b - a) * 1e-9]
                      for a, b in gaps],
    }


def _host_span(spans, a: float, b: float) -> str:
    """The benchmark span that covers most of the gap ``[a, b)``."""
    best, best_ov = "no bench span", 0.0
    for s, d, name in spans:
        ov = min(b, s + d) - max(a, s)
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def idle_share(summary: dict):
    """Mean idle share of the devices that ran anything, or None."""
    devs = summary["devices"]
    if not devs:
        return None
    return sum(d["idle_share"] for d in devs.values()) / len(devs)


def module_mean_s(summary: dict, prefix: str):
    """Mean device time of the programs whose name starts with ``prefix``
    (``None`` when the window ran none)."""
    hits = [m for k, m in summary["modules"].items() if k.startswith(prefix)]
    n = sum(m["count"] for m in hits)
    return sum(m["total_s"] for m in hits) / n if n else None

