"""Open-loop serving: a Poisson stream of single-row requests to SVMEngine.

The configuration's circuit machine is served as a one-member fleet
(``compile_fleet({member: est.deploy("circuit")})``) behind ``SVMEngine``
with the traffic's ``max_batch`` and ``max_wait_ms``.  The schedule holds
``round(rate_per_s * seconds)`` requests due at uniform order statistics
over the window (a Poisson process given its count, so every seed offers
the same number of requests) and rows drawn from the test split, both
from ``--seed``.  One thread sends each request at its due time; each is
timed from its due time to the moment its future resolved, so a late
sender or a stall counts against latency.

After the window every served label is compared with the plain
reference's.

Traffic parameters: ``rate_per_s``, ``member``, ``max_batch``,
``max_wait_ms``, ``tie_eps`` (per pair kind), ``drain_s``, ``limits``.
"""
from __future__ import annotations

import concurrent.futures as cf
import gc
import threading
import time

import numpy as np

from benchlib import configs
from benchlib.harness import Check, WindowOut


class State:
    pass


def schedule(rate_per_s: float, seconds: float, n_rows: int, seed: int):
    """Due times (s from the window's start) and test-row indices."""
    rng = np.random.default_rng(seed)
    n = int(round(rate_per_s * seconds))
    due = np.sort(rng.uniform(0.0, seconds, n))
    return due, rng.integers(0, n_rows, n)


def setup(ctx) -> State:
    from repro.api import MixedKernelSVM, compile_fleet
    from repro.serving import SVMEngine

    tr = ctx.traffic
    s = State()
    s.ctx = ctx
    s.x_all, s.y_all = configs.test_split(ctx.config)
    est = MixedKernelSVM.load(configs.data_path(ctx.config, "fit"))
    s.member = tr["member"]
    fleet = compile_fleet({s.member: est.deploy("circuit")})
    s.engine = SVMEngine(fleet, max_batch=int(tr["max_batch"]),
                         max_wait_ms=float(tr["max_wait_ms"])).start()
    s.engine.warmup()
    # Warm the request path too: a full batch of requests at once.
    warm = [s.engine.submit(s.x_all[i % len(s.x_all)], s.member)
            for i in range(int(tr["max_batch"]))]
    cf.wait(warm, timeout=120.0)
    s.engine.stats.reset()
    return s


def run(s: State, seconds: float) -> WindowOut:
    import jax

    tr = s.ctx.traffic
    due, rows = schedule(float(tr["rate_per_s"]), seconds, len(s.y_all),
                         s.ctx.seed)
    n = len(due)
    done = np.full(n, np.nan)
    sent = np.zeros(n)
    labels = np.full(n, -1)
    left = [n]
    lock = threading.Lock()
    all_done = threading.Event()

    # The sender keeps no future: each one's callback (run by the engine
    # when it resolves it) stamps the time and keeps the label, so the
    # window's live objects stay few and the collector's passes short.
    def stamp(i):
        def cb(f):
            done[i] = time.perf_counter()
            if f.exception() is None:
                labels[i] = f.result()
            with lock:
                left[0] -= 1
                if not left[0]:
                    all_done.set()
        return cb

    x = s.x_all
    submit = s.engine.submit
    t0 = time.perf_counter() + 0.01
    due_abs = t0 + due
    with jax.profiler.TraceAnnotation("bench.send"):
        for i in range(n):
            dt = due_abs[i] - time.perf_counter()
            if dt > 0:
                time.sleep(dt)
            sent[i] = time.perf_counter()
            submit(x[rows[i]], s.member).add_done_callback(stamp(i))
    with jax.profiler.TraceAnnotation("bench.drain"):
        all_done.wait(timeout=float(tr["drain_s"]))
    t_drain = time.perf_counter()
    missing = np.isnan(done)
    lat_ms = 1e3 * (np.where(missing, t_drain, done) - due_abs)
    late_ms = 1e3 * (sent - due_abs)
    failed = int(np.sum(missing | (labels < 0)))
    s.labels = labels
    s.rows = rows
    s.missing = failed
    s.last_latency_ms = lat_ms
    s.last_completed_per_s = float((~missing).sum()
                                   / (np.nanmax(done) - t0))
    stats = s.engine.stats.summary()
    p95 = float(np.percentile(lat_ms, 95))
    s.ctx.say(f"[window] {n} requests at {tr['rate_per_s']} req/s over "
              f"{seconds} s: p50 {np.percentile(lat_ms, 50):.3f} ms, p95 "
              f"{p95:.3f} ms, p99 {np.percentile(lat_ms, 99):.3f} ms, max "
              f"{lat_ms.max():.3f} ms; {failed} failed or missing")
    s.ctx.say(f"[window] sender lateness p50 {np.percentile(late_ms, 50):.3f}"
              f" ms, p95 {np.percentile(late_ms, 95):.3f} ms, max "
              f"{late_ms.max():.3f} ms at {due[late_ms.argmax()]:.3f} s into "
              f"the window; engine: {stats.get('n_batches')} "
              f"batches, {stats.get('mean_batch_rows')} rows a batch, "
              f"queue wait p50 {stats.get('queue_wait_ms_p50')} ms")
    counters = {k: stats[k] for k in ("n_queries", "n_batches",
                                      "queue_wait_ms_p50") if k in stats}
    counters["lateness_p95_ms"] = float(np.percentile(late_ms, 95))
    return WindowOut(e2e={"serve_p95_ms": p95}, attempted=n, failed=failed,
                     counters=counters)


def readings(s: State) -> dict:
    return {}


def release(s: State) -> None:
    s.engine.stop()
    s.engine = None
    gc.collect()


def reference_labels(cfg: dict, x: np.ndarray, tie_eps: dict,
                     dtype=np.float64):
    """Reference labels of ``x``, whether a comparator tie can change each
    one, and the reference's pair scores."""
    from reference.circuit import Machine

    ref = Machine(cfg, cfg["_dir"])
    sc = ref.scores(x, dtype=dtype)
    return ref.labels(sc[0]), ref.ambiguous(sc, tie_eps)[0], sc[0]


def check(s: State) -> list:
    """Every served label against the reference, except where a
    comparator tie decides the reference's label (DESIGN.md §1.4); a
    request that never resolved is missing."""
    tie_eps = s.ctx.traffic["tie_eps"]
    want, tied, _ = reference_labels(s.ctx.config, s.x_all, tie_eps)
    clear = ~tied[s.rows]
    got = s.labels
    wrong = int(np.sum((got != want[s.rows]) & clear & (got >= 0)))
    s.ctx.say(f"[check] {len(got)} served labels, {int(clear.sum())} on "
              f"rows no tie decides (tie bands {tie_eps}); "
              f"{int(np.sum(got != want[s.rows]))} differ overall")
    lim = s.ctx.traffic["limits"]
    return [Check("wrong_labels", float(wrong), float(lim["wrong_labels"])),
            Check("missing", float(s.missing), float(lim["missing"]))]


def control(ctx_for, seeds, seconds, emit) -> None:
    """Readings the limits are set from (``bench/control.py``): the widest
    gap between the program's pair scores and the reference's over the
    whole test split, which the tie band (``tie_eps``) must clear, and the
    wrong labels of the bfloat16 control on each seed's schedule."""
    import ml_dtypes

    from reference.circuit import Machine
    from repro.api import MixedKernelSVM

    ctx = ctx_for(seeds[0])
    tie_eps = ctx.traffic["tie_eps"]
    x, _ = configs.test_split(ctx.config)
    est = MixedKernelSVM.load(configs.data_path(ctx.config, "fit"))
    prog = np.asarray(est.deploy("circuit").decision_scores(x), np.float64)
    want, tied, want_sc = reference_labels(ctx.config, x, tie_eps)
    ctrl, _, _ = reference_labels(ctx.config, x, tie_eps,
                                  dtype=ml_dtypes.bfloat16)
    gap = np.abs(prog - want_sc)
    kmap = np.array(Machine(ctx.config, ctx.config["_dir"]).kernel_map)
    emit({"workload": ctx.cell["name"], "side": "program",
          "max_score_gap": {k: float(gap[:, kmap == k].max())
                            for k in tie_eps if (kmap == k).any()},
          "rows": len(x), "rows_a_tie_decides": int(tied.sum()),
          "tie_eps": tie_eps})
    for seed in seeds:
        _, rows = schedule(float(ctx.traffic["rate_per_s"]), seconds,
                           len(x), seed)
        emit({"workload": ctx.cell["name"], "seed": seed, "side": "control",
              "wrong_labels": int(np.sum((ctrl[rows] != want[rows])
                                         & ~tied[rows])),
              "requests": len(rows)})
