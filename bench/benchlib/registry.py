"""Find a cell's configuration, traffic, generator and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, named after it:

* ``BENCHMARK.json`` (checkout root): cells, metrics, bounds;
* ``bench/configs/<config>.json``: a deployment and its data files;
* ``bench/traffic/<traffic>.json``: the parameters of a traffic mix,
  including the ``generator`` module that makes it and the limits its
  correctness comparison holds;
* ``bench/generators/<generator>.py``: one generator per kind of traffic;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric; a
  metric split by cell kind (``device_idle_share.serve``,
  ``device_idle_share.signoff``) may share one reader named by its stem
  (``bench/metrics/device_idle_share.py``) where no reader of its full
  name exists.

Adding a cell, a configuration or a metric therefore means adding files
and entries, never editing this module.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(name: str) -> str:
    if not _NAME.match(name or ""):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, tag: str):
    """Import the Python file at ``path`` under a private module name."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod_name = "_bench_" + tag + "_" + re.sub(r"\W", "_",
                                              os.path.basename(path)[:-3])
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


class Registry:
    """The benchmark's files, rooted at a checkout (``root``)."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "bench")
        self.spec = _json(os.path.join(root, "BENCHMARK.json"))

    # -- cells ----------------------------------------------------------------

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        path = os.path.join(self.bench_dir, "configs", _checked(name) + ".json")
        cfg = _json(path)
        cfg["_dir"] = os.path.dirname(path)
        return cfg

    def traffic(self, name: str) -> dict:
        return _json(os.path.join(self.bench_dir, "traffic",
                                  _checked(name) + ".json"))

    def generator(self, kind: str):
        return load_module(os.path.join(self.bench_dir, "generators",
                                        _checked(kind) + ".py"), "generator")

    # -- metrics --------------------------------------------------------------

    def end_to_end(self, cell: str) -> list[dict]:
        """End-to-end metrics the cell reports (``setup_s`` among them)."""
        return [m for m in self.spec["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]

    def per_layer(self, cell: str) -> list[dict]:
        """Per-layer metrics read in the cell's traced run."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        out = []
        for m in self.spec["per_layer"]:
            listed = m.get("workloads")
            if (cell in listed) if listed is not None else m["moves"] in e2e:
                out.append(m)
        return out

    def reader(self, metric: str):
        """The reader of ``metric``: ``metrics/<metric>.py``, else the one
        of its stem (the name up to its first ``.``)."""
        d = os.path.join(self.bench_dir, "metrics")
        path = os.path.join(d, _checked(metric) + ".py")
        if not os.path.isfile(path):
            path = os.path.join(d, metric.split(".", 1)[0] + ".py")
        return load_module(path, "metric")
