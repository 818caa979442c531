"""The device gate refuses a run without a TPU; the peaks table refuses
an unknown device."""
import os
import subprocess
import sys

import pytest

from benchlib import device, registry


def test_gate_refuses_cpu_only():
    import jax

    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as e:
        device.gate(1)
    assert e.value.code not in (0, None)


def test_run_exits_nonzero_without_result_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(registry.BENCH_DIR, "run.py"),
         "--workload", "signoff.balance", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    """A checkout that holds only BENCHMARK.json and bench/ has no program
    to measure: the run fails and prints no result."""
    import shutil

    root = tmp_path / "bare"
    shutil.copytree(registry.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(registry.ROOT, "BENCHMARK.json"), root)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "signoff.balance",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=root,
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_peaks_known_and_unknown():
    p = device.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("TPU v99")
