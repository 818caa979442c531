"""SVM fleet serving driver: stream near-sensor queries through the engine.

Fits one machine per dataset (Algorithm 1), co-batches them into a
:class:`~repro.api.FleetMachine`, and drives an open-loop Poisson query
stream through :class:`~repro.serving.SVMEngine` — the deployed-fleet
picture of ROADMAP item 2: many tenants, continuous small queries, one
device program per padded bucket.

PR 10 controls: ``--deadline-ms``/``--priority-classes`` attach deadlines
and priority classes to the stream (the batch former serves EDF with
cross-class backfill), ``--queue-bound``/``--shed-expired`` switch on
admission control (shed requests resolve with ``ShedError`` and are
reported, backpressure throttles the producer), ``--mesh-devices``
dispatches through the shard_map data-parallel forward on a
``make_serving_mesh``, and ``--pipeline-depth`` sets how many batches
overlap staging and compute.

  PYTHONPATH=src python -m repro.launch.serve_svm \
      --datasets balance,seeds --rate 5000 --n-queries 4000 \
      --deadline-ms 25 --priority-classes 2 --queue-bound 2048 \
      --shed-expired --pipeline-depth 2
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--datasets", default="balance,seeds",
                    help="comma-separated fleet tenants")
    ap.add_argument("--target", default="circuit")
    ap.add_argument("--n-epochs", type=int, default=60)
    ap.add_argument("--n-queries", type=int, default=4000)
    ap.add_argument("--rate", type=float, default=5000.0,
                    help="open-loop Poisson arrival rate (queries/s)")
    ap.add_argument("--max-batch", type=int, default=256,
                    help="per-device max bucket rows")
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request completion deadline (default: none)")
    ap.add_argument("--priority-classes", type=int, default=1,
                    help="spread requests uniformly over this many "
                         "priority classes (0 = lowest)")
    ap.add_argument("--queue-bound", type=int, default=None,
                    help="pending-row bound; overflow sheds expired then "
                         "lowest-priority work (default: unbounded)")
    ap.add_argument("--shed-expired", action="store_true",
                    help="drop queued requests whose deadline passed "
                         "instead of serving them")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="batches in flight before blocking on the oldest")
    ap.add_argument("--mesh-devices", type=int, default=None,
                    help="serve through a make_serving_mesh over this "
                         "many devices (default: single-device dispatch)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from repro.api import MixedKernelSVM, compile_fleet
    from repro.data import datasets
    from repro.serving import ShedError, SVMEngine

    names = [n.strip() for n in args.datasets.split(",") if n.strip()]
    members, pools = {}, {}
    for name in names:
        ds = datasets.load(name)
        t0 = time.time()
        est = MixedKernelSVM(n_epochs=args.n_epochs, seed=args.seed).fit(
            ds.x_train, ds.y_train)
        members[name] = est.deploy(args.target)
        pools[name] = np.asarray(ds.x_test, np.float32)
        print(f"fit+deploy [{name}] in {time.time() - t0:.1f}s "
              f"(K={members[name].n_classes}, d={members[name].n_features})")
    fleet = compile_fleet(members)
    print(fleet.describe())

    mesh = None
    if args.mesh_devices is not None:
        from repro.launch.mesh import make_serving_mesh

        mesh = make_serving_mesh(args.mesh_devices)
        print(f"mesh: {args.mesh_devices} device(s) on the "
              f"'{mesh.axis_names[0]}' axis")

    rng = np.random.RandomState(args.seed)
    with SVMEngine(fleet, max_batch=args.max_batch,
                   max_wait_ms=args.max_wait_ms, mesh=mesh,
                   pipeline_depth=args.pipeline_depth,
                   queue_bound=args.queue_bound,
                   shed_expired=args.shed_expired) as eng:
        eng.warmup()
        futures = []
        next_t = time.perf_counter()
        t0 = next_t
        backpressured = 0
        for i in range(args.n_queries):
            name = names[rng.randint(len(names))]
            pool = pools[name]
            x = pool[rng.randint(len(pool))]
            prio = int(rng.randint(args.priority_classes)) \
                if args.priority_classes > 1 else 0
            if eng.backpressure:
                backpressured += 1
            futures.append((name, x, eng.submit(
                x, name, deadline_ms=args.deadline_ms, priority=prio)))
            next_t += rng.exponential(1.0 / args.rate)
            pause = next_t - time.perf_counter()
            if pause > 0:
                time.sleep(pause)
        labels, n_shed = [], 0
        for _, _, f in futures:
            try:
                labels.append(f.result(timeout=60.0))
            except ShedError:
                labels.append(None)
                n_shed += 1
        wall = time.perf_counter() - t0

    # Spot-check routing against the member machines' direct predictions.
    for (name, x, _), lab in list(zip(futures, labels))[:: max(
            1, args.n_queries // 64)]:
        if lab is None:
            continue
        want = int(fleet.member(name).predict(x[None])[0])
        assert lab == want, f"routing mismatch for {name}: {lab} != {want}"

    summary = eng.stats.summary()
    summary["wall_s"] = round(wall, 3)
    summary["offered_rate"] = args.rate
    if n_shed or args.queue_bound is not None or args.shed_expired:
        summary["shed_futures"] = n_shed
        summary["backpressured_submits"] = backpressured
    print(json.dumps(summary, indent=2))
    return summary


if __name__ == "__main__":
    main()
