"""Whole runs on the CPU (the chip gate skipped) with the timed path
broken underneath: each fault must make ``correct`` come out false, and
the unbroken run must come out true.  ``serve.balance`` is held out of
BENCHMARK.json (PERF.md) and runs here from a copy that holds it.

Faults: half of the batch left out, the rest folded or served as if it
were all; and an answer altered where it is produced.  (The cells run on
one chip, so there is no exchange between chips to leave out.)
"""
import pytest

from benchlib import harness

SEED = 2**31 + 11


def _run(root: str, workload: str, seconds: float = 1.0) -> dict:
    return harness.run_cell(workload, SEED, seconds, False, root=root,
                            gate=False)


def _break_signoff(monkeypatch, fault: str) -> None:
    from repro.api import compiled

    orig = compiled.StreamingMCMachine._chunk_acc

    def broken(self, x, v_idx, assignments, y, u):
        if fault == "half_batch":
            h = x.shape[0] // 2
            return orig(self, x[:h], v_idx, assignments, y[:h], u)
        acc, w, log_ref, bits = orig(self, x, v_idx, assignments, y, u)
        return acc.at[0].add(8.0 / x.shape[0]), w, log_ref, bits

    monkeypatch.setattr(compiled.StreamingMCMachine, "_chunk_acc", broken)


def _break_serve(monkeypatch, fault: str) -> None:
    from repro.api import fleet

    orig = fleet.FleetMachine._labels

    def broken(self, x, model_idx):
        lab = orig(self, x, model_idx)
        if fault == "half_batch":
            return lab.at[lab.shape[0] // 2:].set(0)
        return lab.at[0].set((lab[0] + 1) % 3)

    monkeypatch.setattr(fleet.FleetMachine, "_labels", broken)


@pytest.mark.parametrize("workload", ["signoff.balance", "serve.balance"])
def test_sound_run_is_correct(held_root, workload):
    r = _run(held_root, workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
@pytest.mark.parametrize("workload,breaker", [
    ("signoff.balance", _break_signoff), ("serve.balance", _break_serve)])
def test_fault_is_not_correct(monkeypatch, held_root, workload, breaker,
                              fault):
    breaker(monkeypatch, fault)
    r = _run(held_root, workload)
    assert not r["correct"], r["checks"]
