"""Yield signoff: back-to-back Monte-Carlo jobs through the public entry.

Each job is ``MixedKernelSVM.monte_carlo(x_val, y_val, n_variants=V,
method="iid", mc_chunk=B, key=PRNGKey(mc_key), accuracy_floor=F)`` on the
configuration's fitted machine and its circuit assignment.  Job ``j`` of a
run signs off its own ``rows`` validation rows, drawn from the
configuration's test split by ``(--seed, j)``: every job has the same
shapes, so nothing compiles in the window, and no two jobs share their
inputs, so no job's answer can be a repeat of another's.

The Monte-Carlo key is fixed by the traffic (``mc_key``), not drawn from
the seed: the engine bakes the key into its compiled step as a constant,
so a new key compiles the step again, and set-up could never find it in
the cache.  What a user pays for that compile is therefore not measured;
the control (``control``) signs off a second key (``control_key``)
against the reference, so the draws are checked for more than one key.

After the window, ``check_jobs`` of the window's jobs, drawn by the seed,
are compared with the plain reference; their gaps are summed.

Traffic parameters: ``rows``, ``n_variants``, ``mc_chunk``, ``mc_key``,
``control_key``, ``accuracy_floor``, ``check_jobs``, ``tie_eps`` (per pair
kind), ``limits``.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchlib import configs, counts
from benchlib.harness import Check, WindowOut

#: The engine's chunk step program (``StreamingMCMachine._step``).
STEP_PROGRAM = "jit__step"


class State:
    pass


def setup(ctx) -> State:
    import jax

    from repro.api import MixedKernelSVM

    tr = ctx.traffic
    s = State()
    s.ctx = ctx
    s.x_all, s.y_all = configs.test_split(ctx.config)
    s.v = int(tr["n_variants"])
    s.chunk = int(tr["mc_chunk"])
    s.floor = float(tr["accuracy_floor"])
    s.est = MixedKernelSVM.load(configs.data_path(ctx.config, "fit"))
    s.key_seed = int(tr["mc_key"])
    s.key = jax.random.PRNGKey(s.key_seed)
    s.results = {}
    _job(s, 0)                     # warm-up: the window's own call
    s.done = []
    return s


def job_rows(s: State, j: int):
    """Validation rows of job ``j``: distinct rows of the test split."""
    idx = np.random.default_rng([s.ctx.seed, j]).choice(
        len(s.y_all), int(s.ctx.traffic["rows"]), replace=False)
    return s.x_all[idx], s.y_all[idx]


def _job(s: State, j: int) -> None:
    x, y = job_rows(s, j)
    r = s.est.monte_carlo(x, y, n_variants=s.v, method="iid",
                          mc_chunk=s.chunk, key=s.key,
                          accuracy_floor=s.floor)
    s.results[j] = dict(answer(r.mean, r.worst, r.best,
                               r.yield_ * r.n_variants, r.hist), n=len(y))


def answer(mean, worst, best, in_spec, hist) -> dict:
    """The program's answer to a job.  ``hist`` is the fold's accuracy
    histogram: with one bin per row count (``rows + 1`` bins) it holds
    every variant's number of correctly classified rows, as a multiset."""
    hist = np.rint(np.asarray(hist, np.float64)).astype(int)
    return {"mean": float(mean), "worst": float(worst), "best": float(best),
            "in_spec": float(in_spec),
            "rows": np.repeat(np.arange(len(hist)), hist)}


def reference_answer(ref, x, y, key_seed: int, v: int, floor: float,
                     tie_eps: dict, dtype=np.float64) -> dict:
    """The plain reference's answer to the same job: per variant, its
    number of correct rows (``rows``) and the range a comparator tie can
    move it in (``lo``..``hi``: a tied row the reference has right may go
    wrong, one it has wrong may go right; DESIGN.md §1.4), and the fold
    of ``rows`` (``mean``, ``worst``, ``best``, ``in_spec``)."""
    from reference.circuit import fold

    scores = ref.scores(x, key_seed=key_seed, variants=range(v), dtype=dtype)
    right = ref.labels(scores) == y[None]                       # (V, n)
    tied = ref.ambiguous(scores, tie_eps)
    rows = right.sum(axis=1)
    f = fold(rows / len(y), floor)
    return dict(f, rows=rows, lo=rows - (tied & right).sum(axis=1),
                hi=rows + (tied & ~right).sum(axis=1),
                ties=int(tied.sum()))


def as_answer(want: dict) -> dict:
    """A reference answer in the program's form (for the control)."""
    n_bins = int(want["hi"].max()) + 1
    return answer(want["mean"], want["worst"], want["best"],
                  want["in_spec"], np.bincount(want["rows"],
                                               minlength=n_bins))


def unexplained(points, lo, hi) -> int:
    """Variants of the program whose correct-row count no variant of the
    reference can take: ``V`` minus the largest matching of the program's
    counts (a multiset) into the reference's ``[lo, hi]`` ranges, one
    count to a range.  Greedy in ascending order, each count to the open
    range that closes first, which is optimal for ranges on a line."""
    import heapq

    order = np.argsort(lo, kind="stable")
    lo, hi = np.asarray(lo)[order], np.asarray(hi)[order]
    heap, i, miss = [], 0, 0
    for p in sorted(int(c) for c in points):
        while i < len(lo) and lo[i] <= p:
            heapq.heappush(heap, int(hi[i]))
            i += 1
        while heap and heap[0] < p:
            heapq.heappop(heap)
        if heap:
            heapq.heappop(heap)
        else:
            miss += 1
    return miss + max(0, len(lo) - len(points))


def gaps(got: dict, want: dict, floor: float) -> dict:
    """Numbers compared, each beyond what the reference's comparator ties
    can explain: ``variants_unexplained``, the program's variants whose
    correct-row count no reference variant can take (see
    :func:`unexplained`); ``fold_gap``, in rows (variants for the in-spec
    count), the widest distance of the program's folded statistics (mean
    x V x n, worst x n, best x n, in-spec) outside the range the ties
    leave the reference's."""
    v, n = len(want["rows"]), got["n"]
    lo, hi = want["lo"], want["hi"]
    # fewest correct rows in spec, as the program's float32 fold tests it
    need = next(k for k in range(n + 2) if k > n or
                np.float32(k) / np.float32(n) >= np.float32(floor))

    def outside(val, a, b):
        return float(max(a - val, val - b, 0.0))

    fg = max(outside(got["mean"] * v * n, lo.sum(), hi.sum()),
             outside(got["worst"] * n, lo.min(), hi.min()),
             outside(got["best"] * n, lo.max(), hi.max()),
             outside(got["in_spec"], int((lo >= need).sum()),
                     int((hi >= need).sum())))
    return {"variants_unexplained": float(unexplained(got["rows"], lo, hi)),
            "fold_gap": fg}


def run(s: State, seconds: float) -> WindowOut:
    import jax

    t0 = time.perf_counter()
    t_end = t0
    while t_end - t0 < seconds:
        j = len(s.done) + 1
        with jax.profiler.TraceAnnotation("bench.job"):
            _job(s, j)
        t_end = time.perf_counter()
        s.done.append(j)
    n = len(s.done)
    rows = int(s.ctx.traffic["rows"])
    rate = n * s.v * rows / (t_end - t0)
    steps = -(-s.v // s.chunk)
    s.ctx.say(f"[window] {n} jobs of {s.v} variants x {rows} rows in "
              f"{t_end - t0:.3f} s ({steps} steps a job)")
    return WindowOut(e2e={"mc_variant_rows_per_s": rate}, attempted=n,
                     failed=0, counters={"jobs": n, "steps": n * steps})


def readings(s: State) -> dict:
    """Work per step program, counted from the configuration's shapes."""
    from reference.circuit import Machine

    ref = Machine(s.ctx.config, s.ctx.config["_dir"])
    ops, byt = counts.signoff_step(
        ref.kernel_map, [a["m"] for a in ref.analog], s.x_all.shape[1],
        int(s.ctx.traffic["rows"]), s.chunk, ref.k)
    return {"step_program": STEP_PROGRAM, "step_ops": ops,
            "step_bytes": byt}


def release(s: State) -> None:
    s.est = None
    gc.collect()


def checked_jobs(s: State) -> list:
    """The window's jobs the check compares, drawn by the seed."""
    k = min(int(s.ctx.traffic["check_jobs"]), len(s.done))
    pick = np.random.default_rng([s.ctx.seed, 0x5EED]).choice(
        len(s.done), k, replace=False)
    return sorted(s.done[i] for i in pick)


def check(s: State) -> list:
    """The reference's answers against the sampled jobs' answers; the
    gaps of the compared jobs are summed."""
    from reference.circuit import Machine

    ref = Machine(s.ctx.config, s.ctx.config["_dir"])
    total = {"variants_unexplained": 0.0, "fold_gap": 0.0}
    for j in checked_jobs(s):
        x, y = job_rows(s, j)
        want = reference_answer(ref, x, y, s.key_seed, s.v, s.floor,
                                s.ctx.traffic["tie_eps"])
        got = s.results[j]
        g = gaps(got, want, s.floor)
        for k, v in g.items():
            total[k] += v
        say_job(s.ctx, j, got, want, g)
    lim = s.ctx.traffic["limits"]
    return [Check(k, total[k], float(lim[k])) for k in total]


def say_job(ctx, j: int, got: dict, want: dict, g: dict) -> None:
    n = got["n"]
    ctx.say(f"[check] job {j}: mean correct rows {got['mean'] * n:.4f} "
            f"(reference {want['mean'] * n:.4f}), worst {got['worst'] * n:g}"
            f" ({want['worst'] * n:g}), best {got['best'] * n:g} "
            f"({want['best'] * n:g}), in spec {got['in_spec']:g} "
            f"({want['in_spec']}) of {len(want['rows'])} variants; "
            f"{want['ties']} variant-rows a tie decides; {g}")


def _bf16():
    import ml_dtypes

    return ml_dtypes.bfloat16


def control(ctx_for, seeds, seconds, emit) -> None:
    """Readings the limits are set from (``bench/control.py``): for each
    seed, the gaps of the program and of the bfloat16 control against the
    float64 reference over ``check_jobs`` jobs, summed as a run's check
    sums them; and the program's gaps on one job signed off with the
    traffic's ``control_key`` instead of ``mc_key``."""
    import jax

    from reference.circuit import Machine

    ref = None
    for seed in seeds:
        s = setup(ctx_for(seed))
        tr = s.ctx.traffic
        if ref is None:
            ref = Machine(s.ctx.config, s.ctx.config["_dir"])
        jobs = list(range(1, int(tr["check_jobs"]) + 1))
        t0 = time.perf_counter()
        for j in jobs:
            _job(s, j)
        job_s = (time.perf_counter() - t0) / len(jobs)
        total = {"program": {}, "control": {}}
        for j in jobs:
            x, y = job_rows(s, j)
            want = reference_answer(ref, x, y, s.key_seed, s.v, s.floor,
                                    tr["tie_eps"])
            ctrl = reference_answer(ref, x, y, s.key_seed, s.v, s.floor,
                                    tr["tie_eps"], dtype=_bf16())
            ctrl = dict(as_answer(ctrl), n=len(y))
            for side, got in (("program", s.results[j]), ("control", ctrl)):
                for k, v in gaps(got, want, s.floor).items():
                    total[side][k] = total[side].get(k, 0.0) + v
        for side in ("program", "control"):
            emit(dict(total[side], workload=s.ctx.cell["name"], seed=seed,
                      side=side, jobs=jobs, key=s.key_seed,
                      job_s=job_s if side == "program" else None))
        # The same job under a second key: its draws, checked likewise.
        s.key_seed = int(tr["control_key"])
        s.key = jax.random.PRNGKey(s.key_seed)
        j = jobs[0]
        _job(s, j)
        x, y = job_rows(s, j)
        want = reference_answer(ref, x, y, s.key_seed, s.v, s.floor,
                                tr["tie_eps"])
        emit(dict(gaps(s.results[j], want, s.floor),
                  workload=s.ctx.cell["name"], seed=seed, side="program",
                  jobs=[j], key=s.key_seed))
        release(s)
