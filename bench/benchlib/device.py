"""The device gate, the table of peaks, and what a run reports of its chips."""
from __future__ import annotations

import json
import os
import sys

from benchlib.registry import BENCH_DIR


def _refuse(msg: str):
    print(msg, file=sys.stderr, flush=True)
    raise SystemExit(3)


def gate(chips: int):
    """The TPU devices of this host; exits when there are fewer than
    ``chips``.  Nothing runs on the CPU in a chip's place."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        _refuse(f"bench: no TPU (JAX sees {len(devs)} "
                     f"{devs[0].platform!r} device(s)); nothing runs")
    if len(devs) < chips:
        _refuse(f"bench: the cell needs {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs


def peaks(device_kind: str, path: str = None) -> dict:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    with open(path or os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


def describe(devs) -> dict:
    import jax

    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices())}


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest of ``devs`` (0 where the backend
    keeps no such count)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
