"""The harness finds cells, configurations, traffic, generators and metric
readers by name, from files alone."""
import json
import os
import shutil

import pytest

from benchlib import registry
from conftest import with_held

ROOT = registry.ROOT


def test_every_cell_resolves():
    reg = registry.Registry(ROOT)
    names = {w["name"] for w in reg.spec["workloads"]}
    assert names
    for w in reg.spec["workloads"]:
        cfg = reg.config(w["config"])
        assert cfg["name"] == w["config"]
        tr = reg.traffic(w["traffic"])
        drv = reg.generator(tr["generator"])
        for fn in ("setup", "run", "readings", "release", "check",
                   "control"):
            assert callable(getattr(drv, fn)), (w["name"], fn)
        e2e = {m["name"] for m in reg.end_to_end(w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = reg.per_layer(w["name"])
        assert layer, w["name"]
        for m in layer:
            assert callable(reg.reader(m["name"]).read)
            assert m["moves"] in e2e


def test_benchmark_files_name_real_paths():
    reg = registry.Registry(ROOT)
    for c in reg.spec["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for p in reg.spec["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def _copy_bench(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def test_new_cell_config_and_metric_are_files_and_entries(tmp_path):
    """A later cell, configuration and metric: new files plus new entries
    in BENCHMARK.json, and no edit to any file already there."""
    root = _copy_bench(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    (root / "bench/configs/tiny.json").write_text(json.dumps(
        {"name": "tiny", "data": {}, "sha256": {}}))
    (root / "bench/traffic/tiny_steady.json").write_text(json.dumps(
        {"generator": "serve", "rate_per_s": 10}))
    (root / "bench/metrics/tiny_metric.serve.py").write_text(
        "def read(summary, counters, readings, peaks):\n    return 1.5\n")
    spec = with_held(json.loads((root / "BENCHMARK.json").read_text()))
    spec["configs"].append({"name": "tiny", "source": "x",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "serve.tiny", "config": "tiny",
                              "traffic": "tiny_steady", "chips": 1,
                              "why": "x"})
    for m in spec["end_to_end"]:
        if m["name"] == "serve_p95_ms":
            m["workloads"].append("serve.tiny")
    spec["per_layer"].append({"name": "tiny_metric.serve", "unit": "ms",
                              "better": "lower", "source": "program_counter",
                              "layer": "serving engine",
                              "moves": "serve_p95_ms",
                              "workloads": ["serve.tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    reg = registry.Registry(str(root))
    assert reg.config("tiny")["name"] == "tiny"
    assert reg.traffic(reg.cell("serve.tiny")["traffic"])["rate_per_s"] == 10
    assert [m["name"] for m in reg.per_layer("serve.tiny")] == \
        ["tiny_metric.serve"]
    assert reg.reader("tiny_metric.serve").read(None, {}, {}, {}) == 1.5
    assert {m["name"] for m in reg.end_to_end("serve.tiny")} == \
        {"serve_p95_ms", "setup_s"}
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, p


def test_metric_without_workloads_goes_where_its_moves_goes(tmp_path):
    root = _copy_bench(tmp_path)
    spec = with_held(json.loads((root / "BENCHMARK.json").read_text()))
    spec["per_layer"].append({"name": "everywhere.serve", "unit": "ms",
                              "better": "lower", "source": "device_trace",
                              "layer": "device", "moves": "serve_p95_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    reg = registry.Registry(str(root))
    for w in spec["workloads"]:
        names = [m["name"] for m in reg.per_layer(w["name"])]
        serving = "serve_p95_ms" in {m["name"]
                                     for m in reg.end_to_end(w["name"])}
        assert ("everywhere.serve" in names) == serving


def test_unknown_or_malformed_names_are_refused():
    reg = registry.Registry(ROOT)
    with pytest.raises(KeyError):
        reg.cell("no.such.cell")
    with pytest.raises(ValueError):
        reg.config("../src")
    with pytest.raises(FileNotFoundError):
        reg.traffic("no_such_traffic")


def test_metric_split_by_cell_kind_shares_its_stem_reader(tmp_path):
    """``device_idle_share.serve`` and ``.signoff`` read through the one
    ``metrics/device_idle_share.py``; a reader of the full name, where a
    later PR adds one, is found first."""
    reg = registry.Registry(ROOT)
    shared = reg.reader("device_idle_share.serve")
    assert reg.reader("device_idle_share.signoff") is shared
    assert shared.__file__.endswith("device_idle_share.py")
    root = _copy_bench(tmp_path)
    (root / "bench/metrics/device_idle_share.fit.py").write_text(
        "def read(summary, counters, readings, peaks):\n    return 7.0\n")
    own = registry.Registry(str(root)).reader("device_idle_share.fit")
    assert own.read(None, {}, {}, {}) == 7.0
    with pytest.raises(FileNotFoundError):
        reg.reader("no_such_metric.serve")


def test_held_cell_comes_back_by_entries_alone(tmp_path):
    """``serve.balance``, held out of BENCHMARK.json, resolves with its
    traffic, generator and metric readers once its entries are added."""
    root = _copy_bench(tmp_path)
    spec = with_held(json.loads((root / "BENCHMARK.json").read_text()))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    reg = registry.Registry(str(root))
    cell = reg.cell("serve.balance")
    assert reg.generator(reg.traffic(cell["traffic"])["generator"]).control
    e2e = {m["name"] for m in reg.end_to_end("serve.balance")}
    assert e2e == {"serve_p95_ms", "setup_s"}
    for m in reg.per_layer("serve.balance"):
        assert callable(reg.reader(m["name"]).read)
