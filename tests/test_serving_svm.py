"""Tests for the SVM serving stack: FleetMachine co-batching + SVMEngine.

Covers the PR's correctness contracts:

  * FleetMachine outputs are BIT-IDENTICAL to each member CompiledMachine
    (scores compared as raw f32 bit patterns) across ragged model mixes —
    different K, d, bank counts, analog members;
  * per-row routing matches per-member prediction for arbitrary tenant
    mixes;
  * fleet save/load round-trips one npz+json for the whole fleet;
  * the engine's bucket policy and batch assembly at the edges (1 row,
    max_batch rows, max_batch + 1 rows across requests);
  * one compiled program per padding bucket — no per-request recompiles;
  * ServingStats accounting (requests vs queries, occupancy, latency).

All machines are hand-built at tiny shapes (no training), mirroring the
analysis registry's ``_tiny_models`` so the suite stays fast.
"""
import time

import numpy as np
import pytest

from repro.api import FleetMachine, compile_fleet, compile_machine
from repro.core.svm import SVMModel
from repro.serving import BucketPolicy, ServingStats, SVMEngine


def _pair_model(gen, d, m, kind):
    sx = gen.normal(size=(m, d)).astype(np.float32)
    sy = np.where(np.arange(m) % 2 == 0, 1.0, -1.0).astype(np.float32)
    alpha = (np.abs(gen.normal(size=m)) + 0.1).astype(np.float32)
    kw = {}
    if kind == "linear":
        kw["w"] = ((alpha * sy) @ sx).astype(np.float32)
    return SVMModel(kind=kind, support_x=sx, support_y=sy, alpha=alpha,
                    bias=float(gen.normal() * 0.1), gamma=0.7, c=1.0, **kw)


def tiny_machine(seed, d=3, m=6, n_classes=3, analog_pairs=()):
    """Hand-built machine: alternating linear/rbf pairs, optional analog."""
    from repro.core import trainer
    from repro.core.analog import AnalogBinaryClassifier

    gen = np.random.default_rng(seed)
    n_pairs = n_classes * (n_classes - 1) // 2
    clfs = []
    for p in range(n_pairs):
        kind = "linear" if p % 2 == 0 else "rbf"
        model = _pair_model(gen, d, m, kind)
        if p in analog_pairs:
            model = _pair_model(gen, d, m, "rbf")
            model = AnalogBinaryClassifier.deploy(model, trainer.default_hw(0))
        clfs.append(model)
    return compile_machine(clfs, n_classes=n_classes)


@pytest.fixture(scope="module")
def ragged_fleet():
    """Three members with different K, d, m and one analog member."""
    members = {
        "tiny": tiny_machine(0, d=3, m=6, n_classes=3),
        "wide": tiny_machine(1, d=5, m=8, n_classes=4),
        "analog": tiny_machine(2, d=4, m=6, n_classes=3, analog_pairs=(1,)),
    }
    return compile_fleet(members), members


def _queries(gen, n, d):
    return gen.normal(size=(n, d)).astype(np.float32)


# -- FleetMachine ------------------------------------------------------------


def test_fleet_layout(ragged_fleet):
    fleet, members = ragged_fleet
    assert fleet.model_ids == ["tiny", "wide", "analog"]
    assert fleet.n_features == 5            # d_max over members
    assert fleet.n_pairs_total == 3 + 6 + 3
    assert fleet.pair_slice("tiny") == (0, 3)
    assert fleet.pair_slice("wide") == (3, 9)
    assert fleet.pair_slice("analog") == (9, 12)
    assert fleet.member("wide") is members["wide"]
    assert "FleetMachine(3 models" in fleet.describe()


def test_fleet_bit_identical_to_members(ragged_fleet):
    """Scores, bits AND labels from the co-batched forward match each
    member machine bit-for-bit — the contract that lets one fleet program
    replace per-model dispatches without any numeric drift."""
    fleet, members = ragged_fleet
    gen = np.random.default_rng(7)
    for mid, machine in members.items():
        x = _queries(gen, 17, machine.n_features)
        want = machine.decision_scores(x)
        got = fleet.decision_scores(x, mid)
        # Raw f32 bit patterns: stricter than allclose, catches reordered
        # reductions that happen to round the same way most of the time.
        np.testing.assert_array_equal(got.view(np.int32),
                                      want.view(np.int32))
        np.testing.assert_array_equal(fleet.predict_bits(x, mid),
                                      machine.predict_bits(x))
        np.testing.assert_array_equal(fleet.predict(x, mid),
                                      machine.predict(x))


def test_fleet_per_row_routing(ragged_fleet):
    """A mixed batch routed per row gives each row its own member's label."""
    fleet, members = ragged_fleet
    gen = np.random.default_rng(11)
    ids = [fleet.model_ids[i] for i in gen.integers(0, 3, size=29)]
    x = _queries(gen, 29, fleet.n_features)
    got = fleet.predict(x, ids)
    for r, mid in enumerate(ids):
        m = members[mid]
        want = m.predict(x[r:r + 1, : m.n_features])[0]
        assert got[r] == want, f"row {r} ({mid}): {got[r]} != {want}"


def test_fleet_single_member_wraps_machine():
    machine = tiny_machine(3)
    fleet = compile_fleet([machine])           # bare sequence, default ids
    assert fleet.model_ids == ["model0"]
    gen = np.random.default_rng(0)
    x = _queries(gen, 9, machine.n_features)
    np.testing.assert_array_equal(fleet.predict(x, 0), machine.predict(x))


def test_fleet_input_forms_and_errors():
    a, b = tiny_machine(4), tiny_machine(5)
    by_pairs = compile_fleet([("a", a), ("b", b)])
    assert by_pairs.model_ids == ["a", "b"]
    with pytest.raises(ValueError, match="duplicate"):
        FleetMachine(["a", "a"], [a, b])
    with pytest.raises(TypeError, match="CompiledMachine"):
        compile_fleet({"a": a, "bad": object()})
    fleet = compile_fleet({"a": a, "b": b})
    with pytest.raises(KeyError, match="unknown model id"):
        fleet.model_index("missing")
    with pytest.raises(IndexError):
        fleet.model_index(2)
    with pytest.raises(ValueError, match="expected"):
        fleet.predict(np.zeros((4, fleet.n_features + 1), np.float32), "a")


def test_fleet_save_load_round_trip(tmp_path, ragged_fleet):
    fleet, members = ragged_fleet
    path = str(tmp_path / "fleet")
    fleet.save(path)
    back = FleetMachine.load(path)
    assert back.model_ids == fleet.model_ids
    assert back._pair_slices == fleet._pair_slices
    gen = np.random.default_rng(13)
    for mid, machine in members.items():
        x = _queries(gen, 11, machine.n_features)
        np.testing.assert_array_equal(
            back.decision_scores(x, mid).view(np.int32),
            fleet.decision_scores(x, mid).view(np.int32))
        np.testing.assert_array_equal(back.predict(x, mid),
                                      machine.predict(x))


# -- BucketPolicy ------------------------------------------------------------


def test_bucket_policy_edges():
    p = BucketPolicy(max_batch=64, min_bucket=8)
    assert p.buckets == (8, 16, 32, 64)
    assert p.bucket_for(1) == 8
    assert p.bucket_for(8) == 8
    assert p.bucket_for(9) == 16
    assert p.bucket_for(64) == 64
    with pytest.raises(ValueError):
        p.bucket_for(0)
    with pytest.raises(ValueError):
        p.bucket_for(65)
    with pytest.raises(ValueError, match="powers of two"):
        BucketPolicy(max_batch=48)
    with pytest.raises(ValueError, match="min_bucket"):
        BucketPolicy(max_batch=8, min_bucket=16)


# -- SVMEngine ---------------------------------------------------------------


@pytest.fixture(scope="module")
def engine_fleet():
    return compile_fleet({
        "a": tiny_machine(20, d=3, m=6, n_classes=3),
        "b": tiny_machine(21, d=4, m=8, n_classes=3),
    })


def test_engine_routing_matches_members(engine_fleet):
    fleet = engine_fleet
    gen = np.random.default_rng(0)
    with SVMEngine(fleet, max_batch=32, max_wait_ms=1.0) as eng:
        eng.warmup()
        futs = []
        for i in range(100):
            mid = fleet.model_ids[int(gen.integers(0, 2))]
            m = fleet.member(mid)
            x = _queries(gen, 1, m.n_features)[0]     # 1-D -> scalar label
            futs.append((mid, x, eng.submit(x, mid)))
        for mid, x, f in futs:
            want = int(fleet.member(mid).predict(x[None])[0])
            assert f.result(timeout=30.0) == want


def test_engine_bucket_edge_batches(engine_fleet):
    """1 row, exactly max_batch rows, and max_batch + 1 rows (carry into a
    second batch) all produce correct labels."""
    fleet = engine_fleet
    m = fleet.member("a")
    gen = np.random.default_rng(1)
    with SVMEngine(fleet, max_batch=32, max_wait_ms=1.0) as eng:
        eng.warmup()
        for n in (1, 32, 33):
            x = _queries(gen, n, m.n_features)
            want = m.predict(x)
            if n <= 32:                     # one multi-row request
                got = eng.predict(x, "a")
                np.testing.assert_array_equal(np.atleast_1d(got), want)
            # same rows as single-row requests (n=33 spans two batches)
            futs = [eng.submit(x[i], "a") for i in range(n)]
            got = np.asarray([f.result(timeout=30.0) for f in futs])
            np.testing.assert_array_equal(got, want)
        with pytest.raises(ValueError, match="rows"):
            eng.submit(_queries(gen, 33, m.n_features), "a")


def test_engine_one_program_per_bucket(engine_fleet):
    """The padded-bucket contract: after warmup + mixed traffic the jitted
    serving program has exactly one compiled entry per bucket shape."""
    fleet = compile_fleet({"a": tiny_machine(30), "b": tiny_machine(31)})
    gen = np.random.default_rng(2)
    with SVMEngine(fleet, max_batch=32, min_bucket=8,
                   max_wait_ms=0.5) as eng:
        eng.warmup()
        assert eng.n_buckets == 3           # 8, 16, 32
        assert fleet._labels_jit._cache_size() == eng.n_buckets
        futs = [eng.submit(_queries(gen, int(k), 3), "a")
                for k in gen.integers(1, 33, size=40)]
        for f in futs:
            f.result(timeout=30.0)
    assert fleet._labels_jit._cache_size() == eng.n_buckets


def test_engine_stats_accounting(engine_fleet):
    fleet = engine_fleet
    gen = np.random.default_rng(3)
    stats = ServingStats()
    assert stats.summary() == {"n_requests": 0, "n_queries": 0,
                               "n_batches": 0}
    with SVMEngine(fleet, max_batch=16, max_wait_ms=1.0,
                   stats=stats) as eng:
        eng.warmup()
        futs = [eng.submit(_queries(gen, 3, 3), "a") for _ in range(20)]
        for f in futs:
            f.result(timeout=30.0)
    s = stats.summary()
    assert s["n_requests"] == 20
    assert s["n_queries"] == 60             # rows, not requests
    assert 1 <= s["n_batches"] <= 20
    assert 0.0 < s["batch_occupancy"] <= 1.0
    assert s["latency_ms"]["p50"] <= s["latency_ms"]["p99"] \
        <= s["latency_ms"]["max"]
    assert s["queue_wait_ms_p50"] >= 0.0
    stats.reset()
    assert stats.n_requests == 0


def test_engine_lifecycle_and_bare_machine():
    machine = tiny_machine(40)
    eng = SVMEngine(machine, max_batch=8)   # bare machine -> 1-member fleet
    assert eng.fleet.model_ids == ["default"]
    with pytest.raises(RuntimeError, match="not started"):
        eng.submit(np.zeros(3, np.float32))
    with eng:
        with pytest.raises(RuntimeError, match="already started"):
            eng.start()
        lab = eng.predict(np.zeros(3, np.float32))
        assert lab == int(machine.predict(np.zeros((1, 3), np.float32))[0])
    with pytest.raises(RuntimeError):
        eng.submit(np.zeros(3, np.float32))
    with pytest.raises(TypeError, match="cannot serve"):
        SVMEngine(object())


# -- ServingStats memory bound -----------------------------------------------


class _FakeReq:
    def __init__(self, t0, wait, service, n_rows=1, deadline=None):
        import math
        self.t_enqueue = t0
        self.t_dispatch = t0 + wait
        self.t_complete = t0 + wait + service
        self.n_rows = n_rows
        self.deadline = math.inf if deadline is None else deadline


def test_stats_memory_stays_flat_with_tolerable_percentiles():
    """Streaming totals are exact and the latency sample is a fixed-size
    reservoir: feeding 200x the reservoir capacity must not grow memory,
    and reservoir percentiles must track the exact ones."""
    stats = ServingStats(reservoir=512, seed=0)
    gen = np.random.default_rng(0)
    exact = []
    n_total = 512 * 200
    batch = 64
    footprint = stats._res.nbytes
    t = 0.0
    for start in range(0, n_total, batch):
        reqs = []
        for _ in range(batch):
            wait = float(gen.exponential(0.001))
            service = float(gen.exponential(0.002))
            reqs.append(_FakeReq(t, wait, service))
            exact.append((wait + service) * 1e3)
            t += 1e-4
        stats.observe_batch(batch, 64, reqs)
    # memory: the reservoir never grew, and no per-request list exists
    assert stats._res.nbytes == footprint
    assert stats._res.shape == (512, 2)
    assert not any(isinstance(v, list) and len(v) > 1024
                   for v in vars(stats).values())
    s = stats.summary()
    # exact streaming totals
    assert s["n_requests"] == n_total
    assert s["n_queries"] == n_total
    # summary() rounds to 3 decimals; the totals behind it are exact
    assert s["latency_ms"]["mean"] == pytest.approx(
        float(np.mean(exact)), abs=5e-4)
    assert s["latency_ms"]["max"] == pytest.approx(
        float(np.max(exact)), abs=5e-4)
    # reservoir percentiles within sampling tolerance of the exact ones
    for q in (50, 95, 99):
        want = float(np.percentile(exact, q))
        got = s["latency_ms"][f"p{q}"]
        assert got == pytest.approx(want, rel=0.25), (q, got, want)
    assert s["latency_sample_n"] == 512


# -- deadline / priority batch former ----------------------------------------


def _mk_req(eng, seq, *, n_rows=1, priority=0, deadline=None, t0=None):
    """Hand-built queued request for direct batch-former tests."""
    import math
    import time
    from concurrent.futures import Future

    from repro.serving.svm_engine import _Request

    now = time.perf_counter() if t0 is None else t0
    d = eng.fleet.n_features
    return _Request(x=np.zeros((n_rows, d), np.float32), model_idx=0,
                    n_rows=n_rows, scalar=n_rows == 1, future=Future(),
                    t_enqueue=now, priority=priority, seq=seq,
                    deadline=math.inf if deadline is None else deadline)


def test_batch_former_priority_and_edf_backfill(engine_fleet):
    """Selection order: expiring requests EDF across classes (backfill),
    then strictly by priority class; low-priority non-expiring work never
    precedes high-priority work (no inversion)."""
    import time

    eng = SVMEngine(engine_fleet, max_batch=32, max_wait_ms=1.0)
    now = time.perf_counter()
    horizon = eng._horizon(now)
    a = _mk_req(eng, 0, priority=2)                       # high, no deadline
    b = _mk_req(eng, 1, priority=0)                       # low, no deadline
    c = _mk_req(eng, 2, priority=0, deadline=horizon)     # low, expiring
    d = _mk_req(eng, 3, priority=2, deadline=horizon - 1e-4)  # high, expiring
    with eng._cond:
        for r in (a, b, c, d):
            eng._enqueue(r)
        order = [eng._select_locked(now) for _ in range(4)]
    # d, c expiring -> EDF (d earlier); then a (high class); b last
    assert [r.seq for r in order] == [3, 2, 0, 1]

    # equal expiring deadlines tie-break to the higher priority class
    e = _mk_req(eng, 4, priority=0, deadline=horizon)
    f = _mk_req(eng, 5, priority=1, deadline=horizon)
    with eng._cond:
        eng._enqueue(e)
        eng._enqueue(f)
        assert eng._select_locked(now).seq == 5
        assert eng._select_locked(now).seq == 4


def test_batch_former_sheds_expired_when_enabled(engine_fleet):
    import time

    from repro.serving import ShedError

    eng = SVMEngine(engine_fleet, max_batch=32, shed_expired=True)
    now = time.perf_counter()
    dead = _mk_req(eng, 0, deadline=now - 1.0)
    live = _mk_req(eng, 1)
    with eng._cond:
        eng._enqueue(dead)
        eng._enqueue(live)
        assert eng._select_locked(now).seq == 1
        assert eng._select_locked(now) is None
    with pytest.raises(ShedError, match="expired"):
        dead.future.result(timeout=0)
    assert eng.stats.summary()["shed"]["reasons"] == {"expired": 1}

    # without shed_expired the expired request is still served
    eng2 = SVMEngine(engine_fleet, max_batch=32)
    stale = _mk_req(eng2, 0, deadline=now - 1.0)
    with eng2._cond:
        eng2._enqueue(stale)
        assert eng2._select_locked(now).seq == 0


def test_admission_sheds_expired_then_lowest_priority(engine_fleet):
    """Bounded-queue admission: room is made by shedding already-expired
    work first, then strictly lower-priority work (latest deadline
    first); an incoming request with no lower class is itself shed."""
    import time

    from repro.serving import ShedError

    eng = SVMEngine(engine_fleet, max_batch=32, queue_bound=4)
    now = time.perf_counter()
    expired = _mk_req(eng, 0, priority=5, deadline=now - 1.0)
    lo_late = _mk_req(eng, 1, priority=0, deadline=now + 9.0)
    lo_soon = _mk_req(eng, 2, priority=0, deadline=now + 1.0)
    with eng._cond:
        for r in (expired, lo_late, lo_soon):
            eng._enqueue(r)
        # over bound by 2: the expired one goes first ("expired"), then
        # the LATEST-deadline low-priority one ("overflow")
        incoming = _mk_req(eng, 3, priority=1, n_rows=3)
        eng._admit_over_bound(incoming, now)
        assert eng._pending_rows == 4      # lo_soon (1) + incoming (3)
    with pytest.raises(ShedError, match="expired"):
        expired.future.result(timeout=0)
    with pytest.raises(ShedError, match="overflow"):
        lo_late.future.result(timeout=0)
    assert not lo_soon.future.done()
    assert not incoming.future.done()

    # no strictly-lower class left -> the incoming request is shed
    with eng._cond:
        loser = _mk_req(eng, 4, priority=0, n_rows=3)
        eng._admit_over_bound(loser, now)
    with pytest.raises(ShedError, match="overflow"):
        loser.future.result(timeout=0)
    assert not lo_soon.future.done()
    assert eng.stats.summary()["shed"]["reasons"] == \
        {"expired": 1, "overflow": 2}


def test_overload_burst_sheds_only_lowest_priority(engine_fleet):
    """End-to-end overload: a burst larger than the queue bound against a
    slowed-down device sheds SOME priority-0 work and NO priority-1 work;
    everything not shed completes correctly."""
    import time

    from repro.serving import ShedError

    fleet = engine_fleet
    eng = SVMEngine(fleet, max_batch=8, max_wait_ms=0.5, queue_bound=16,
                    shed_expired=True)
    slow, orig = 0.02, eng._forward

    def slow_forward(xbuf, ibuf):
        time.sleep(slow)
        return orig(xbuf, ibuf)

    eng._forward = slow_forward
    gen = np.random.default_rng(5)
    with eng:
        eng.warmup()
        x = _queries(gen, 1, 3)[0]
        lo = [eng.submit(x, "a", priority=0) for _ in range(120)]
        # high-priority burst below the queue bound: admission makes room
        # for every one of these by evicting queued priority-0 work
        hi = [eng.submit(x, "a", priority=1) for _ in range(12)]
        want = int(fleet.member("a").predict(x[None])[0])
        shed_lo = 0
        for f in lo:
            try:
                assert f.result(timeout=60.0) == want
            except ShedError as e:
                assert e.reason in ("overflow", "expired")
                shed_lo += 1
        for f in hi:          # high priority is NEVER shed here
            assert f.result(timeout=60.0) == want
    assert shed_lo > 0
    assert eng.stats.n_shed == shed_lo


def test_backpressure_watermarks(engine_fleet):
    import time

    eng = SVMEngine(engine_fleet, max_batch=8, max_wait_ms=0.5,
                    queue_bound=64, high_watermark=32, low_watermark=8)
    slow, orig = 0.01, eng._forward

    def slow_forward(xbuf, ibuf):
        time.sleep(slow)
        return orig(xbuf, ibuf)

    eng._forward = slow_forward
    gen = np.random.default_rng(6)
    with eng:
        eng.warmup()
        assert eng.backpressure is False
        futs = [eng.submit(_queries(gen, 8, 3), "a") for _ in range(6)]
        assert eng.backpressure is True        # 48 pending rows >= 32
        for f in futs:
            f.result(timeout=60.0)
        deadline = time.monotonic() + 10.0
        while eng.backpressure and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng.backpressure is False       # drained below low watermark


def test_carry_leads_next_batch_with_original_enqueue(engine_fleet):
    """A request that overflows the forming batch is carried and MUST be
    row 0 of the next dispatch, keeping its original enqueue time (its
    max-wait anchor) — large requests cannot starve behind small ones."""
    dispatched = []
    eng = SVMEngine(engine_fleet, max_batch=8, max_wait_ms=30.0)
    orig = eng._dispatch

    def record(batch, rows):
        dispatched.append(list(batch))
        orig(batch, rows)

    eng._dispatch = record
    gen = np.random.default_rng(7)
    with eng:
        eng.warmup()
        dispatched.clear()
        f1 = eng.submit(_queries(gen, 5, 3), "a")
        time.sleep(0.005)                      # batcher anchors on f1
        f2 = eng.submit(_queries(gen, 6, 3), "a")   # 5 + 6 > 8 -> carry
        r1 = f1.result(timeout=30.0)
        r2 = f2.result(timeout=30.0)
        assert len(r1) == 5 and len(r2) == 6
    assert len(dispatched) >= 2
    assert [r.n_rows for r in dispatched[0]] == [5]
    carry_batch = dispatched[1]
    assert carry_batch[0].n_rows == 6          # carried -> batch[0]
    # original enqueue preserved: it waited across BOTH batches
    assert carry_batch[0].t_enqueue <= dispatched[0][0].t_dispatch


def test_pipeline_depth_k(engine_fleet):
    """pipeline_depth=k keeps k batches in flight over k+1 staging
    buffers and still resolves every request correctly."""
    fleet = engine_fleet
    m = fleet.member("a")
    gen = np.random.default_rng(8)
    with pytest.raises(ValueError, match="pipeline_depth"):
        SVMEngine(fleet, pipeline_depth=0)
    with SVMEngine(fleet, max_batch=16, max_wait_ms=0.2,
                   pipeline_depth=3) as eng:
        assert all(len(bufs) == 4 for bufs in eng._staging.values())
        eng.warmup()
        xs = [_queries(gen, 3, 3) for _ in range(50)]
        futs = [eng.submit(x, "a") for x in xs]
        for x, f in zip(xs, futs):
            np.testing.assert_array_equal(f.result(timeout=30.0),
                                          m.predict(x))


def test_engine_mesh_on_one_device(engine_fleet):
    """mesh= dispatch on a 1-device serving mesh: labels identical to the
    plain engine, buckets become per-device sizes."""
    from repro.launch.mesh import make_serving_mesh

    fleet = engine_fleet
    mesh = make_serving_mesh(1)
    gen = np.random.default_rng(9)
    with SVMEngine(fleet, max_batch=16, max_wait_ms=0.5, mesh=mesh) as eng:
        assert eng.n_devices == 1 and eng.max_rows == 16
        eng.warmup()
        for mid in fleet.model_ids:
            m = fleet.member(mid)
            x = _queries(gen, 11, m.n_features)
            np.testing.assert_array_equal(eng.predict(x, mid), m.predict(x))


# -- mesh-sharded forward (8 virtual devices, subprocess) --------------------


def test_sharded_fleet_forward_bit_identity_subprocess():
    """8-fake-device shard_map serving leg: every per-device slice of the
    sharded labels output is bit-identical to the single-device forward
    on the same rows, on ragged mixed-model batches; the engine serves
    through the mesh end-to-end (subprocess so XLA_FLAGS doesn't leak)."""
    import os
    import subprocess
    import sys
    import textwrap

    body = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        from tests.test_serving_svm import tiny_machine, _queries
        from repro.api import compile_fleet
        from repro.launch.mesh import make_serving_mesh
        from repro.serving import SVMEngine

        fleet = compile_fleet({
            "tiny": tiny_machine(0, d=3, m=6, n_classes=3),
            "wide": tiny_machine(1, d=5, m=8, n_classes=4),
            "analog": tiny_machine(2, d=4, m=6, n_classes=3,
                                   analog_pairs=(1,)),
        })
        mesh = make_serving_mesh()
        fwd = fleet.shard(mesh)
        assert fwd.n_devices == 8
        gen = np.random.default_rng(0)

        # ragged mixed-model batch, whole per-device slices (8 x 16 rows)
        n = fwd.global_rows(16)
        x = fleet._pad_features(_queries(gen, n, fleet.n_features))
        idx = fleet._resolve_idx(
            [fleet.model_ids[i] for i in gen.integers(0, 3, size=n)], n)
        sharded = np.asarray(fwd(x, idx.copy()))
        local = np.asarray(fleet._labels_jit(x, idx.copy()))
        # global AND per-device-slice bit identity (i32 labels)
        np.testing.assert_array_equal(sharded, local)
        per = n // 8
        for dev in range(8):
            s = slice(dev * per, (dev + 1) * per)
            np.testing.assert_array_equal(
                sharded[s],
                np.asarray(fleet._labels_jit(x[s], idx[s].copy())))

        # ragged row count: predict pads to whole slices and trims
        x27 = _queries(gen, 27, 4)
        np.testing.assert_array_equal(fwd.predict(x27, "analog"),
                                      fleet.predict(x27, "analog"))

        # engine end-to-end through the mesh, mixed models + deadlines
        with SVMEngine(fleet, max_batch=16, max_wait_ms=1.0,
                       mesh=mesh) as eng:
            assert eng.max_rows == 16 * 8
            eng.warmup()
            futs = []
            for i in range(40):
                mid = fleet.model_ids[i % 3]
                m = fleet.member(mid)
                q = _queries(gen, 3, m.n_features)
                futs.append((mid, q, eng.submit(q, mid, deadline_ms=5e3)))
            for mid, q, f in futs:
                np.testing.assert_array_equal(
                    f.result(timeout=60.0), fleet.member(mid).predict(q))
        print("OK")
    """)
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.path.join(os.path.dirname(__file__), "..")]))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", body], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    assert "OK" in res.stdout


def test_sharded_forward_with_pallas_banks():
    """The mesh forward over Pallas rbf banks (``use_pallas``, the TPU
    default): ``shard_map`` accepts the kernel's outputs and the labels
    equal the unsharded forward (interpret mode, one-device mesh)."""
    from repro.launch.mesh import make_serving_mesh

    fleet = compile_fleet({"tiny": tiny_machine(0),
                           "wide": tiny_machine(1, d=5, m=8, n_classes=4)},
                          use_pallas=True, interpret=True)
    fwd = fleet.shard(make_serving_mesh(1))
    gen = np.random.default_rng(3)
    x = fleet._pad_features(_queries(gen, 16, fleet.n_features))
    idx = fleet._resolve_idx([fleet.model_ids[i % 2] for i in range(16)], 16)
    np.testing.assert_array_equal(np.asarray(fwd(x, idx.copy())),
                                  np.asarray(fleet._labels_jit(x, idx.copy())))


def test_serving_mesh_requires_batch_axis(engine_fleet):
    from repro.launch import mesh as mesh_mod

    m = mesh_mod.make_test_mesh(shape=(1,), axes=("data",))
    with pytest.raises(ValueError, match="batch"):
        engine_fleet.shard(m)
