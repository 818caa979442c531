"""One run of one cell: set-up, the measured window, the check, the result.

The order inside a run is fixed:

1. set-up (``setup_s``): imports, the device gate, the generator's ``setup``
   (load the configuration, build the system under test, warm every shape
   the window uses);
2. the window: the generator's ``run`` for ``seconds``, traced when
   ``trace`` is on (then only per-layer metrics are reported);
3. the device's peak memory is read, the generator releases the program's
   state, and only then the generator's ``check`` runs the plain reference
   and compares.

The result is one JSON object; the numbers compared, each with its limit,
are printed last on standard error and are the last key of the object.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time

from benchlib import device, registry, trace as trace_mod


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit (<=)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class WindowOut:
    """What a generator's window produced."""

    e2e: dict             # end-to-end metric name -> value
    attempted: int
    failed: int
    counters: dict        # counts the program or the generator kept


@dataclasses.dataclass
class Ctx:
    """What a generator gets: the cell's files, the seed, the chips."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    devices: list

    def say(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


def setup_jax(root: str) -> str:
    """Persistent compile cache at the checkout's fixed ``.jax_cache``;
    the program is imported from the checkout's ``src``."""
    cache = os.path.join(root, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: no program at {src}/repro")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import repro

    if not os.path.realpath(repro.__file__).startswith(
            os.path.realpath(src) + os.sep):
        raise SystemExit(f"bench: imported repro from {repro.__file__}, "
                         f"not from {src}")
    return cache


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = registry.ROOT, gate: bool = True,
             trace_out: str = None, t_start: float = None) -> dict:
    """Run ``workload`` once and return the result object.  ``gate=False``
    (tests only) takes the host's own devices instead of failing without
    a TPU."""
    t0 = time.perf_counter() if t_start is None else t_start
    reg = registry.Registry(root)
    cell = reg.cell(workload)
    cfg = reg.config(cell["config"])
    traffic = reg.traffic(cell["traffic"])
    generator = reg.generator(traffic["generator"])
    setup_jax(root)
    import jax

    devs = device.gate(cell["chips"]) if gate else \
        jax.devices()[: cell["chips"]]
    ctx = Ctx(cell=cell, config=cfg, traffic=traffic, seed=int(seed),
              devices=devs)
    state = generator.setup(ctx)
    # What set-up built lives to the end of the run: keep it out of the
    # collector's way so a collection inside the window walks only the
    # window's own objects.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t0
    ctx.say(f"[setup] {workload}: {setup_s:.3f} s on "
            f"{len(devs)} x {devs[0].device_kind}")

    summary = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            with trace_mod.capture(tdir):
                with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                    out = generator.run(state, seconds)
            norm = trace_mod.load(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        if trace_out:
            with open(trace_out, "w") as f:
                json.dump(norm, f)
        summary = trace_mod.reduce(norm)
    else:
        out = generator.run(state, seconds)
    mem = device.memory_peak(devs)
    readings = generator.readings(state) if trace else {}
    generator.release(state)
    checks = generator.check(state)

    if trace:
        metrics = {}
        peaks = device.peaks(devs[0].device_kind)
        for m in reg.per_layer(workload):
            v = reg.reader(m["name"]).read(summary, out.counters, readings,
                                           peaks)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = dict(out.e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in reg.end_to_end(workload)}
    dev = dict(device.describe(devs), memory_peak_bytes=mem)
    if summary is not None:
        dev["busy_s"] = summary["busy_s"]
        dev["window_s"] = summary["window_s"]
    result = {
        "correct": bool(checks) and all(c.ok for c in checks),
        "attempted": int(out.attempted),
        "failed": int(out.failed),
        "metrics": metrics,
        "device": dev,
    }
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
        for name, d in summary["devices"].items():
            ctx.say(f"[trace] {name}: busy {d['busy_s']:.6f} s, idle share "
                    f"{d['idle_share']:.6f}")
    for c in checks:
        ctx.say(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
                f"{'ok' if c.ok else 'FAIL'}")
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result
