"""Streaming serve launcher: the multi-tenant LSH front end, live.

    python -m repro.launch.serve --steps 60 --insert-batch 64 --query-batch 8
    python -m repro.launch.serve --listen 127.0.0.1:0 --max-inflight 64

Two modes share one registry setup (register / restore / recover, mesh,
WAL, telemetry): the scripted demo below, and ``--listen HOST:PORT`` which
hands the registry to the network front-end (``repro.serve.frontend``) and
serves real concurrent traffic -- per-tenant admission control
(``--max-inflight``, ``--queue-depth``), wall-clock micro-batch deadlines
(``--max-delay-ms``), and graceful drain on SIGTERM (``--drain-timeout``,
per-tenant overrides via ``--tenant-drain-timeout NAME=SECS``).  The async
``maintenance`` verb runs on ``--maint-workers`` background threads.  A
third mode, ``--standby WAL_DIR``, runs a warm standby: it tails a
primary's WAL directory continuously and promotes on SIGTERM (failover
with almost nothing left to replay).

Drives the repro.serve stack end to end with synthetic traffic:

* three tenants with different metrics/embedders share one registry --
  ``l2-basis`` (p=2, truncated Chebyshev-basis embedding, Eq. 3),
  ``l1-qmc`` (p=1, QMC node-sample embedding, Eq. 6) and ``w2-quantile``
  (W^2 over 1-D distributions: raw empirical-Gaussian draws embedded by
  their clipped quantile functions, Sec. 2.2 / Remark 1);
* every tick, a batch of random functions (or raw distribution samples,
  for the Wasserstein tenant) is embedded and **inserted** into the
  mutable delta segment while **queries** stream through the
  micro-batcher's admission queue (deadline flush, padded chunk palette);
* a fraction of old items is **deleted** (tombstones); when garbage exceeds
  ``--compact-at`` the tenant is **compacted**;
* the loop ends with a per-tenant report: QPS, latency percentiles, recall
  proxy vs exact brute force, segment occupancy, and the jit-shape audit
  (distinct padded shapes dispatched -- bounded by the chunk palette, NOT by
  the number of requests).

Optionally ``--snapshot DIR`` checkpoints every tenant at the end and
``--restore DIR`` starts from a previous snapshot.  ``--wal-dir DIR``
turns on the durable write path (per-tenant write-ahead delta log,
group-commit interval ``--fsync-every``); with both ``--restore`` and
``--wal-dir`` the launcher goes through ``ServableRegistry.recover`` --
latest verifiable snapshot plus WAL-tail replay, the crash-recovery
path -- and prints each tenant's recovery report.  ``--shard N`` serves
both tenants SPMD over an N-device serve mesh (on CPU it forces N host
devices; results are bit-identical to the unsharded run).
``--replicate {none,static:k,auto}`` additionally materializes hot sealed
segments on several devices -- with ``auto``, each compaction re-derives
the replica factors from the tenant's live ``shard_balance`` merge-win
telemetry (results again bit-identical; only placement changes).

Observability (docs/architecture.md § Observability): ``--metrics-dir DIR``
turns on structured out-of-process export -- the unified metrics registry
and the span ring are flushed every loop step to ``DIR/metrics.jsonl``
(OTel-style JSON lines) and rendered to ``DIR/metrics.prom`` (Prometheus
text), enough for an external reader to reconstruct QPS, per-stage latency,
device balance, WAL fsync latency and the recall gauge without touching the
process.  ``--trace-sample RATE`` samples that fraction of query traces
(each sampled query's fan-out, lock waits, telemetry and result copy get
spans of their own); ``--recall-interval`` / ``--recall-probe-size``
drive the periodic sampled recall-vs-brute-force probe behind the
``serve_recall_proxy`` gauge.
"""

import argparse
import os


def default_specs(n_dims=64, segment_capacity=1024, shard_axis=None,
                  replicate="none", max_delay_ms=2.0, precision="fp32"):
    """The launcher's three-tenant deployment, importable by tests and the
    front-end load generator so the live server and a direct in-process
    registry are built from *the same specs* (the wire-parity tests depend
    on that).  Covers the paper's family: l2-basis (p=2, Eq. 3), l1-qmc
    (p=1, Eq. 6), w2-quantile (W^2 over distributions, Remark 1)."""
    from ..serve import ServableSpec

    common = dict(n_dims=n_dims, segment_capacity=segment_capacity,
                  chunk_sizes=(8, 32, 128), max_delay_ms=max_delay_ms,
                  shard_axis=shard_axis, replication=replicate,
                  precision=precision)
    return (
        ServableSpec(name="l2-basis", p=2.0, r=4.0, embedder="basis",
                     **common),
        ServableSpec(name="l1-qmc", p=1.0, r=8.0, embedder="qmc",
                     **common),
        ServableSpec(name="w2-quantile", p=2.0, r=0.5,
                     embedder="wasserstein", **common),
    )


def synthetic_inputs(sv, n, rng):
    """Per-tenant synthetic inputs for ``Servable.embed``.

    Function tenants get random smooth functions sampled at the tenant's
    node set (mixtures of a few random sines -- bounded, infinitely
    divisible); the Wasserstein tenant gets raw draws from random 1-D
    Gaussians (the empirical-distribution ingest path: the embedder
    computes the clipped quantile function itself).  ``rng`` is a numpy
    Generator, so a seed fixes the data.
    """
    import numpy as np

    if sv.spec.embedder == "wasserstein":
        mu = rng.uniform(-1.0, 1.0, size=(n, 1))
        sig = rng.uniform(0.1, 1.0, size=(n, 1))
        return mu + sig * rng.normal(size=(n, 256))
    nodes = sv.nodes()
    amps = rng.normal(size=(n, 3)) / 3.0
    freqs = rng.uniform(0.5, 4.0, size=(n, 3))
    phase = rng.uniform(0, 2 * np.pi, size=(n, 3))
    return np.sum(amps[:, :, None] *
                  np.sin(freqs[:, :, None] * nodes[None, None, :]
                         + phase[:, :, None]), axis=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--insert-batch", type=int, default=64)
    ap.add_argument("--query-batch", type=int, default=8)
    ap.add_argument("--queries-per-step", type=int, default=4)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--n-probes", type=int, default=4)
    ap.add_argument("--n-dims", type=int, default=64)
    ap.add_argument("--delete-frac", type=float, default=0.05)
    ap.add_argument("--compact-at", type=float, default=0.3,
                    help="compact a tenant when its tombstone fraction "
                         "exceeds this")
    ap.add_argument("--segment-capacity", type=int, default=1024)
    ap.add_argument("--snapshot", default=None, help="write snapshot here")
    ap.add_argument("--restore", default=None, help="restore snapshot first")
    ap.add_argument("--wal-dir", default=None,
                    help="durable write path: per-tenant write-ahead delta "
                         "log under this dir (with --restore this becomes "
                         "full crash recovery: snapshot + WAL-tail replay)")
    ap.add_argument("--fsync-every", type=int, default=None,
                    help="WAL group-commit interval (records per fsync; "
                         "1 = synchronous commit, 0 = only at snapshot "
                         "points; default REPRO_WAL_FSYNC_EVERY or 8)")
    ap.add_argument("--shard", type=int, default=0,
                    help="serve SPMD over this many devices (0 = off; on "
                         "CPU this forces the host device count, so it must "
                         "be the first jax-touching flag)")
    ap.add_argument("--precision", default="fp32",
                    choices=("fp32", "bf16", "int8"),
                    help="sealed-segment storage precision tier for every "
                         "tenant: fp32 is bit-exact, bf16/int8 are "
                         "bounded-loss with exact survivor rerank "
                         "(REPRO_STORE_DTYPE overrides at registration)")
    ap.add_argument("--replicate", default="none",
                    help="hot-segment replication policy for sharded "
                         "tenants: none | static:k | auto (auto re-places "
                         "from live shard_balance telemetry at every "
                         "compaction)")
    ap.add_argument("--metrics-dir", default=None,
                    help="export telemetry here every loop step: "
                         "metrics.jsonl (JSON-lines metric snapshots + "
                         "trace spans) and metrics.prom (Prometheus text)")
    ap.add_argument("--trace-sample", type=float, default=None,
                    help="fraction of query traces to sample (default "
                         "REPRO_TRACE_SAMPLE or 0 = tracing off)")
    ap.add_argument("--recall-interval", type=int, default=20,
                    help="probe sampled recall vs brute force every this "
                         "many steps (0 = only the final probe)")
    ap.add_argument("--recall-probe-size", type=int, default=16,
                    help="queries per periodic recall probe")
    ap.add_argument("--listen", default=None, metavar="HOST:PORT",
                    help="serve live traffic instead of the scripted "
                         "demo: bind the async front-end here (port 0 "
                         "picks a free port; the bound address is printed "
                         "as '[frontend] listening on H:P') and run until "
                         "SIGTERM, then drain gracefully")
    ap.add_argument("--max-inflight", type=int, default=64,
                    help="per-tenant admitted-but-unanswered request "
                         "quota (front-end admission control)")
    ap.add_argument("--queue-depth", type=int, default=256,
                    help="per-tenant batcher queue-depth cap sampled at "
                         "admission (requests beyond it are rejected "
                         "with queue_full + retry_after_ms)")
    ap.add_argument("--max-delay-ms", type=float, default=2.0,
                    help="micro-batcher flush deadline per tenant")
    ap.add_argument("--drain-timeout", type=float, default=10.0,
                    help="graceful-drain backstop on SIGTERM/unload "
                         "(seconds)")
    ap.add_argument("--tenant-drain-timeout", action="append", default=[],
                    metavar="NAME=SECS",
                    help="per-tenant drain budget override (repeatable); "
                         "tenants not named keep --drain-timeout")
    ap.add_argument("--maint-workers", type=int, default=None,
                    help="background maintenance worker threads for the "
                         "async 'maintenance' verb (default "
                         "REPRO_MAINT_WORKERS or 1)")
    ap.add_argument("--standby", default=None, metavar="WAL_DIR",
                    help="run as a warm standby instead of a primary: "
                         "tail the given WAL directory continuously, "
                         "promote on SIGTERM and print the failover "
                         "report (pairs with a primary using --wal-dir "
                         "on the same directory)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.shard > 1:
        # must land before the first jax init -- device count locks then
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.shard}")

    import json

    import numpy as np

    from .. import compile_cache
    from ..obs import Exporter, configure as obs_configure
    from ..serve import ServableRegistry, recall_proxy, run_server
    from ..serve.stats import occupancy_report
    from .mesh import make_serve_mesh

    compile_cache.enable()
    if args.trace_sample is not None:
        obs_configure(sample_rate=args.trace_sample)
    exporter = (Exporter.for_directory(args.metrics_dir)
                if args.metrics_dir else None)

    rng = np.random.default_rng(args.seed)
    mesh = make_serve_mesh(args.shard) if args.shard else None
    shard_axis = "serve" if mesh is not None else None

    if args.standby:
        # warm-standby mode: no tenants of our own -- tail the primary's
        # WAL directory, replaying continuously, and promote on SIGTERM
        import signal
        import threading

        from ..serve.standby import WalStandby

        sb = WalStandby(args.standby, mesh=mesh,
                        fsync_every=args.fsync_every)
        sb.start()
        print(f"[serve] standby tailing {args.standby}", flush=True)
        stop = threading.Event()
        for sig in (signal.SIGTERM, signal.SIGINT):
            signal.signal(sig, lambda *_: stop.set())
        stop.wait()
        reports = sb.promote()
        for name, rep in sorted(reports.items()):
            print(f"[serve] promoted {name}: "
                  f"applied={rep.get('applied', 0)} "
                  f"offset={rep.get('end_offset', 0)} "
                  f"truncated={rep.get('truncated', False)}")
        print(f"[serve] standby promoted: tenants "
              f"{sb.registry.names()}", flush=True)
        print("[serve] OK")
        return

    registry = ServableRegistry(mesh=mesh, wal_dir=args.wal_dir,
                                fsync_every=args.fsync_every)
    if mesh is not None:
        print(f"[serve] SPMD serve mesh: {dict(mesh.shape)}")

    if args.restore and args.wal_dir:
        # crash-recovery path: latest verifiable snapshot + WAL-tail replay
        reports = registry.recover(ckpt_root=args.restore,
                                   wal_dir=args.wal_dir)
        names = sorted(reports)
        for name, rep in reports.items():
            print(f"[serve] recovered {name}: step={rep.get('restored_step')}"
                  f" replayed={rep.get('applied', 0)}"
                  f" dup_dropped={rep.get('dropped_duplicates', 0)}"
                  f" truncated={rep.get('truncated', False)}")
        if mesh is not None:
            for name in names:
                registry.get(name).index.shard(mesh, shard_axis)
        print(f"[serve] recovered tenants {names} from {args.restore} "
              f"+ WAL {args.wal_dir}")
    elif args.restore:
        names = registry.restore(args.restore)
        if mesh is not None:
            # the CLI mesh wins over whatever shard_axis the snapshot was
            # taken with, so --restore --shard N actually serves SPMD even
            # for snapshots taken unsharded (elastic re-mesh)
            for name in names:
                registry.get(name).index.shard(mesh, shard_axis)
        print(f"[serve] restored tenants {names} from {args.restore}")
    else:
        for spec in default_specs(n_dims=args.n_dims,
                                  segment_capacity=args.segment_capacity,
                                  shard_axis=shard_axis,
                                  replicate=args.replicate,
                                  max_delay_ms=args.max_delay_ms,
                                  precision=args.precision):
            registry.register(spec)
        print(f"[serve] registered tenants {registry.names()}")

    if args.listen:
        # traffic-driven mode: hand the populated registry to the async
        # front-end and serve until SIGTERM, then drain gracefully
        host, _, port_s = args.listen.rpartition(":")
        host = host or "127.0.0.1"
        overrides = {}
        for item in args.tenant_drain_timeout:
            name, _, secs = item.partition("=")
            overrides[name] = float(secs)
        run_server(registry, host, int(port_s or 0),
                   max_inflight=args.max_inflight,
                   queue_depth=args.queue_depth,
                   drain_timeout_s=args.drain_timeout,
                   tenant_drain_timeouts=overrides or None,
                   maint_workers=args.maint_workers,
                   exporter=exporter)
        if exporter is not None:
            exporter.close()
            print(f"[serve] telemetry -> {args.metrics_dir}")
        print("[serve] OK")
        return

    inserted = {name: [] for name in registry.names()}
    futures = []
    compactions = {name: 0 for name in registry.names()}

    for step in range(args.steps):
        for name in registry.names():
            sv = registry.get(name)
            # ingest: embed + insert into the delta segment
            emb = np.asarray(sv.embed(
                synthetic_inputs(sv, args.insert_batch, rng)))
            inserted[name].extend(sv.insert(emb).tolist())
            # queries: perturbations of known items -> through the admission
            # queue (several small heterogeneous requests per tick)
            for _ in range(args.queries_per_step):
                base = sv.embed(synthetic_inputs(sv, args.query_batch, rng))
                qs = np.asarray(base) + rng.normal(
                    scale=0.05, size=base.shape).astype(np.float32)
                futures.append(sv.submit_query(qs, args.k, args.n_probes))
            sv.batcher.pump()
            # churn: tombstone a slice of the oldest items
            n_del = int(args.delete_frac * args.insert_batch)
            if n_del and len(inserted[name]) > 4 * n_del:
                victims = inserted[name][:n_del]
                inserted[name] = inserted[name][n_del:]
                sv.delete(victims)
            occ = occupancy_report(sv.index)
            if occ["tombstone_frac"] > args.compact_at:
                # the maintenance handle, not index.compact: under
                # --replicate auto this is where shard_balance skew
                # becomes placement
                sv.maintenance.compact()
                compactions[name] += 1
        if args.recall_interval and (step + 1) % args.recall_interval == 0:
            # the telemetry loop's quality signal: a small sampled probe of
            # recall vs exact brute force, published as a per-tenant gauge
            for name in registry.names():
                sv = registry.get(name)
                qs = np.asarray(sv.embed(
                    synthetic_inputs(sv, args.recall_probe_size, rng)))
                sv.stats.record_recall(recall_proxy(
                    sv.index, qs, args.k, n_probes=args.n_probes))
        if exporter is not None:
            exporter.flush()
        if (step + 1) % 20 == 0:
            done = sum(f.done() for f in futures)
            print(f"[serve] step {step + 1}/{args.steps}: "
                  f"{done}/{len(futures)} queries answered")

    for name in registry.names():
        registry.get(name).batcher.flush_all()
    n_ok = sum(1 for f in futures if f.done() and f.exception() is None)
    print(f"[serve] {n_ok}/{len(futures)} query requests answered")

    probe = {}
    for name in registry.names():
        sv = registry.get(name)
        qs = np.asarray(sv.embed(
            synthetic_inputs(sv, args.recall_probe_size, rng)))
        r = recall_proxy(sv.index, qs, args.k, n_probes=args.n_probes)
        sv.stats.record_recall(r)
        probe[name] = round(r, 3)

    report = registry.report()
    for name, rep in report.items():
        occ = rep["occupancy"]
        lay = rep["shard_layout"]
        shard_s = (f"shards={lay['n_dev']}x{lay['per_dev']}"
                   f" replicas={lay['n_instances']}/{lay['n_sealed']}"
                   if lay else "shards=off")
        bal = rep["stats"]["shard_balance"]
        print(f"[serve] {name}: live={occ['n_live']}/{occ['n_items']} "
              f"segments={occ['n_segments']} "
              f"tombstones={occ['tombstone_frac']:.2f} "
              f"compactions={compactions[name]} "
              f"{shard_s} "
              f"recall_proxy={probe[name]} "
              f"qps={rep['stats']['qps']} "
              f"p95={rep['stats']['p95_ms']}ms "
              f"jit_shapes={rep['batcher']['unique_shapes']} "
              f"dev_imbalance={bal['device_imbalance']}")

    if args.snapshot:
        registry.snapshot(args.snapshot, step=args.steps)
        print(f"[serve] snapshot -> {args.snapshot}")

    if args.wal_dir:
        for name in registry.names():
            wal = registry.get(name).index.wal
            if wal is not None:
                s = wal.stats()
                print(f"[serve] wal {name}: {s['offset']}B "
                      f"appends={s['appends']} syncs={s['syncs']} "
                      f"fsync_every={s['fsync_every']}")

    print("[serve] report:",
          json.dumps({n: r["stats"] for n, r in report.items()}))
    if exporter is not None:
        # final snapshot carries everything after flush_all + snapshot +
        # the last recall probe, then the sink is released
        exporter.flush()
        exporter.close()
        print(f"[serve] telemetry -> {args.metrics_dir}")
    print("[serve] OK")


if __name__ == "__main__":
    main()
