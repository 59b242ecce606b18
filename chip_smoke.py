#!/usr/bin/env python3
"""Bring-up smoke test: the three-tenant serve path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # four chips: sharded vs unsharded
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --items 4096

One chip (the default), all in this one process:

1. run the query kernels (``ops.fused_query_topk`` fp32,
   ``ops.quantized_query_topk`` bf16 and int8; p=1 and 2) at the served
   shapes against their jnp references on the device: ids equal where the
   reference's distances are distinct, distances within f32 tolerance;
2. build a ``ServableRegistry`` from ``launch.serve.default_specs`` -- the
   launcher's deployment: ``l2-basis`` (p=2, Chebyshev basis), ``l1-qmc``
   (p=1, QMC nodes), ``w2-quantile`` (W2 over raw 1-D samples); N=64,
   1,024-row segments, 8 tables x 4 hashes x 2^10 buckets x 32 slots --
   with the write-ahead log on, as ``launch.serve --wal-dir`` does;
3. bulk-load ``ITEMS`` (2^18) items per tenant through
   ``Servable.embed`` then ``Servable.insert``, as the launcher does;
4. serve that registry with ``serve.frontend.Frontend`` on an asyncio loop
   in a background thread, and drive it through
   ``serve.client.FrontendClient``: query batches per tenant, one insert,
   one delete, one ``maintenance`` compact polled with ``job_status``;
5. check that every wire answer is bit-identical to the same call made in
   process on the same registry, that inserted rows read back as their own
   nearest neighbours and deleted ones never come back, and that recall@10
   against exact brute force over the live items (``serve.stats.
   recall_proxy``) is at least ``RECALL_FLOOR``.

Four chips (``--chips 4``), and nothing else: the same three tenants at
``ITEMS`` each on ``make_serve_mesh(4)`` (64 sealed segments per device)
and, in the same process, on one device; both are loaded identically,
deleted from and compacted once, then asked the same queries.  Gids and
distances must be bit-identical.

Earlier lines of output are diagnostics for reading (devices, dispatch
decisions, load sizes, device bytes in use, compile seconds, per-request
latency, recall), not measurements.  The last line, printed only when
every phase passed on a TPU with every kernel path compiled and no
backend override set, is one JSON object ``{"ok": true, "device":
{...}}``.  Anything else exits non-zero.  ``--rehearse`` runs the phases
on whatever platform JAX finds (the CPU here) and then fails the final
gate, which still needs a TPU.

The persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``.jax_cache`` in the checkout (``repro.compile_cache``).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

N_DIMS = 64               # launch.serve --n-dims
SEGMENT_CAPACITY = 1024   # launch.serve --segment-capacity
K = 10                    # launch.serve --k
N_PROBES = 4              # launch.serve --n-probes
ITEMS = 2 ** 18           # live items per tenant: 256 sealed segments
LOAD_CHUNK = 8192         # rows per embed + insert call while loading
QUERY_ROWS = 8            # rows per query request: the palette's 8-chunk
WIRE_BATCHES = 3          # query requests per tenant and round
RECALL_BATCHES = 4        # recall probe: 4 x 8 fresh queries per tenant
COMPACT_TIMEOUT_S = 300.0  # one tenant's background compaction
OVERRIDES = ("REPRO_KERNEL_BACKEND", "REPRO_QUERY_BACKEND")
KERNEL_PATHS = ("kernel_mode", "query_backend", "hash_backend",
                "embed_backend")

# Kernel parity phase: the query kernels at the served shapes -- the chunk
# palette's widest batch, 8 tables x 4 probes x 32 slots of candidates.
KERNEL_NQ = 128
KERNEL_C = 1024
KERNEL_ROWS = 2 * SEGMENT_CAPACITY
KERNEL_RTOL = KERNEL_ATOL = 1e-5   # f32 tolerance on distances

SEED = 0
# recall@10 floors: this script's own recall on the CPU (JAX_PLATFORMS=cpu,
# --rehearse, SEED, 2^18 items per tenant -- the full size: 0.978125,
# 0.421875 and 1.0), less 0.02.
RECALL_FLOOR = {"l1-qmc": 0.958125, "l2-basis": 0.401875, "w2-quantile": 0.98}


class SmokeFailure(AssertionError):
    """A check of this script failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print("[chip_smoke]", *parts, flush=True)


class CompileLog:
    """Seconds XLA spent compiling (or fetching from the persistent cache)
    and the cache's hits/misses, from JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        return (f"compile_s={self.seconds:.3f} programs={self.programs} "
                f"cache_hits={self.hits} cache_misses={self.misses}")


class BackgroundFrontend:
    """``serve.frontend.Frontend`` on an asyncio loop in a daemon thread of
    this process (the chip belongs to one process)."""

    def __init__(self, registry):
        from repro.serve.frontend import Frontend
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True, name="frontend-loop")
        self.thread.start()
        self.frontend = Frontend(registry, drain_timeout_s=60.0)
        self.host, self.port = self._run(
            self.frontend.start("127.0.0.1", 0))

    def _run(self, coro, timeout_s: float = 600.0):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(
            timeout_s)

    def close(self) -> None:
        try:
            self._run(self.frontend.shutdown())
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(timeout=60)
            self.loop.close()


def bitwise_equal(a, b) -> bool:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and \
        a.tobytes() == b.tobytes()


def load(registries, n_items: int, rng, synthetic_inputs) -> dict:
    """Embed with the first registry's servables; insert the same rows into
    every registry.  Returns {tenant: (first rows, their gids)}."""
    import numpy as np
    first = {}
    for name in registries[0].names():
        sv0 = registries[0].get(name)
        t0 = time.perf_counter()
        for start in range(0, n_items, LOAD_CHUNK):
            n = min(LOAD_CHUNK, n_items - start)
            emb = np.asarray(sv0.embed(synthetic_inputs(sv0, n, rng)))
            gids = [reg.get(name).insert(emb) for reg in registries]
            for g in gids[1:]:
                check(bitwise_equal(g, gids[0]), f"{name}: gids diverged")
            if start == 0:
                first[name] = (emb[:QUERY_ROWS], gids[0][:QUERY_ROWS])
        occ = [reg.get(name).index.occupancy() for reg in registries]
        say(f"loaded {name}: items={sum(s['n_live'] for s in occ[0])} "
            f"segments={len(occ[0])} "
            f"sealed={sum(1 for s in occ[0] if s['sealed'])} "
            f"seconds={time.perf_counter() - t0:.3f}")
    return first


def fresh_queries(sv, rng, synthetic_inputs):
    import numpy as np
    return np.asarray(sv.embed(synthetic_inputs(sv, QUERY_ROWS, rng)),
                      np.float32)


def wire_round(client, registry, label, batches) -> None:
    """Send each (tenant, queries) batch over the wire and in process;
    the answers must be bit-identical (invariant 9)."""
    import numpy as np
    for name, qs in batches:
        t0 = time.perf_counter()
        g_w, d_w = client.query_arrays(name, qs, K, n_probes=N_PROBES)
        ms = (time.perf_counter() - t0) * 1e3
        g_l, d_l = registry.get(name).query(qs, K, N_PROBES)
        same = bitwise_equal(g_w, np.asarray(g_l, np.int32)) and \
            bitwise_equal(d_w, np.asarray(d_l, np.float32))
        say(f"{label} {name}: wire query rows={len(qs)} "
            f"latency_ms={ms:.3f} bit_identical={same}")
        check(same, f"{label} {name}: wire answer differs from in-process")


def kernel_parity(rng) -> None:
    """``ops.fused_query_topk`` (fp32) and ``ops.quantized_query_topk``
    (bf16, int8) through the default dispatch, against their jnp references
    on this device: ids equal wherever the reference's distances are
    distinct, distances within f32 tolerance, the same empty slots."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, quantize, ref

    db = jnp.asarray(rng.standard_normal((KERNEL_ROWS, N_DIMS), np.float32))
    q = jnp.asarray(rng.standard_normal((KERNEL_NQ, N_DIMS), np.float32))
    # distinct ids per query, some empty slots, some past the valid prefix
    ids = np.argsort(rng.random((KERNEL_NQ, KERNEL_ROWS)), axis=1)
    ids = ids[:, :KERNEL_C].astype(np.int32)
    ids[rng.random(ids.shape) < 0.05] = -1
    ids = jnp.asarray(ids)
    valid = KERNEL_ROWS - 64
    fused_ref = jax.jit(ref.fused_query_topk_ref, static_argnums=(3, 4, 5))
    quant_ref = jax.jit(quantize.quantized_topk_ref,
                        static_argnums=(4, 5, 6))
    for precision in ("fp32", "bf16", "int8"):
        if precision == "fp32":
            k = K
        else:
            codes, scale = quantize.encode(db, precision)
            k = quantize.survivor_width(K, 0, KERNEL_C)
        for p in (1.0, 2.0):
            t0 = time.perf_counter()
            # the reference one slot wider, so a tie across the k-th place
            # is seen too
            if precision == "fp32":
                got = ops.fused_query_topk(q, db, ids, k, p=p,
                                           valid_items=valid)
                want = fused_ref(q, db, ids, k + 1, p, valid)
            else:
                got = ops.quantized_query_topk(q, codes, scale, ids, k, p=p,
                                               valid_items=valid)
                want = quant_ref(q, codes, scale, ids, k + 1, p, valid)
            (d_k, i_k), (d_w, i_w) = ([np.asarray(a) for a in pair]
                                      for pair in (got, want))
            d_r, i_r = d_w[:, :k], i_w[:, :k]
            fin = np.isfinite(d_r)
            # distinct: apart from both neighbours by more than the
            # tolerance, which covers a different summation order
            tol = KERNEL_ATOL + KERNEL_RTOL * np.abs(d_w)
            with np.errstate(invalid="ignore"):       # inf - inf
                apart = np.diff(d_w, axis=1) > tol[:, 1:]
            distinct = fin & apart
            distinct[:, 1:] &= apart[:, :-1]
            ids_ok = bool((i_k[distinct] == i_r[distinct]).all())
            empty_ok = bool((np.isfinite(d_k) == fin).all()
                            and (i_k[~fin] == -1).all())
            err = np.abs(d_k[fin] - d_r[fin])
            dist_ok = bool((err <= tol[:, :k][fin]).all())
            label = (f"kernel {precision} p={p:g} nq={KERNEL_NQ} "
                     f"C={KERNEL_C} N={N_DIMS} k={k}")
            say(f"{label}: ids_equal_where_distinct={ids_ok} "
                f"compared={int(distinct.sum())}/{distinct.size} "
                f"empty_slots_equal={empty_ok} "
                f"max_abs_dist_err={float(err.max(initial=0.0))!r} "
                f"seconds={time.perf_counter() - t0:.3f}")
            check(ids_ok and empty_ok and dist_ok,
                  f"{label}: kernel differs from its reference")


def run_one_chip(args, rng, compiles) -> None:
    import numpy as np

    import jax
    from repro.launch.serve import default_specs, synthetic_inputs
    from repro.serve import ServableRegistry, recall_proxy
    from repro.serve.client import FrontendClient

    # its own stream: the load's data, and the floors taken on it, do not
    # depend on this phase
    kernel_parity(np.random.default_rng((SEED, 1)))
    say(f"after kernel parity: {compiles.line()}")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_wal_") as wal_dir:
        registry = ServableRegistry(wal_dir=wal_dir)
        for spec in default_specs(n_dims=N_DIMS,
                                  segment_capacity=SEGMENT_CAPACITY):
            registry.register(spec)
        say(f"registered {registry.names()} wal={wal_dir}")

        first = load([registry], args.items, rng, synthetic_inputs)
        stats = jax.devices()[0].memory_stats() or {}
        say(f"after load: bytes_in_use={stats.get('bytes_in_use')} "
            f"{compiles.line()}")

        served = BackgroundFrontend(registry)
        try:
            with FrontendClient(served.host, served.port,
                                timeout_s=900.0) as client:
                states = {n: t["state"]
                          for n, t in client.health()["tenants"].items()}
                say(f"frontend on {served.host}:{served.port} "
                    f"tenants={states}")
                batches = [(name, fresh_queries(registry.get(name), rng,
                                                synthetic_inputs))
                           for name in registry.names()
                           for _ in range(WIRE_BATCHES)]
                wire_round(client, registry, "round 1", batches)

                for name in registry.names():
                    sv = registry.get(name)
                    new = fresh_queries(sv, rng, synthetic_inputs)
                    t0 = time.perf_counter()
                    gids = client.insert(name, new)
                    say(f"{name}: wire insert rows={len(new)} "
                        f"latency_ms={(time.perf_counter() - t0) * 1e3:.3f}")
                    victims_emb, victims = first[name]
                    t0 = time.perf_counter()
                    n_del = client.delete(name, victims)
                    say(f"{name}: wire delete gids={len(victims)} "
                        f"deleted={n_del} latency_ms="
                        f"{(time.perf_counter() - t0) * 1e3:.3f}")
                    check(n_del == len(victims), f"{name}: delete count")
                    batches += [(name, new), (name, victims_emb)]
                    # acknowledged write read back: each new row is its
                    # own nearest neighbour, at distance 0
                    g, d = client.query_arrays(name, new, K,
                                               n_probes=N_PROBES)
                    check(bitwise_equal(g[:, 0], gids),
                          f"{name}: inserted rows not read back")
                    check(bool((d[:, 0] == 0).all()),
                          f"{name}: inserted rows not at distance 0")
                    g, _ = client.query_arrays(name, victims_emb, K,
                                               n_probes=N_PROBES)
                    check(not np.isin(g, victims).any(),
                          f"{name}: a deleted gid was served")

                for name in registry.names():
                    t0 = time.perf_counter()
                    job = client.maintenance(name, "compact")
                    # polls job_status; raises if the job failed or is
                    # still running after the timeout
                    st = client.wait_job(job, timeout_s=COMPACT_TIMEOUT_S,
                                         interval_s=0.05)
                    say(f"{name}: maintenance compact job={job} "
                        f"status={st['status']} result={st.get('result')} "
                        f"seconds={time.perf_counter() - t0:.3f}")

                wire_round(client, registry, "round 2", batches)
        finally:
            served.close()

        recall = {}
        for name in registry.names():
            sv = registry.get(name)
            probes = [recall_proxy(sv.index,
                                   fresh_queries(sv, rng, synthetic_inputs),
                                   K, n_probes=N_PROBES)
                      for _ in range(RECALL_BATCHES)]
            recall[name] = sum(probes) / len(probes)
            occ = sv.index.occupancy()
            say(f"{name}: recall@{K}={recall[name]!r} "
                f"floor={RECALL_FLOOR[name]!r} "
                f"queries={RECALL_BATCHES * QUERY_ROWS} "
                f"live={sum(s['n_live'] for s in occ)} "
                f"segments={len(occ)}")
        for name, r in recall.items():
            check(r >= RECALL_FLOOR[name],
                  f"{name}: recall {r} below floor {RECALL_FLOOR[name]}")
        for name in registry.names():
            s = registry.get(name).index.wal.stats()
            say(f"wal {name}: bytes={s['offset']} appends={s['appends']} "
                f"syncs={s['syncs']}")


def run_four_chips(args, rng, compiles) -> None:
    import numpy as np

    import jax
    from repro.launch.mesh import make_serve_mesh
    from repro.launch.serve import default_specs, synthetic_inputs
    from repro.serve import ServableRegistry

    check(len(jax.devices()) >= 4,
          f"--chips 4 needs four devices, found {len(jax.devices())}")
    mesh = make_serve_mesh(4)
    sharded = ServableRegistry(mesh=mesh)
    single = ServableRegistry()
    for spec in default_specs(n_dims=N_DIMS,
                              segment_capacity=SEGMENT_CAPACITY,
                              shard_axis="serve"):
        sharded.register(spec)
    for spec in default_specs(n_dims=N_DIMS,
                              segment_capacity=SEGMENT_CAPACITY):
        single.register(spec)
    say(f"serve mesh {dict(mesh.shape)}; unsharded twin on "
        f"{jax.devices()[0]}")

    first = load([single, sharded], args.items, rng, synthetic_inputs)
    for name in single.names():
        victims = first[name][1]
        for reg in (single, sharded):
            reg.get(name).delete(victims)
            t0 = time.perf_counter()
            n_seg = reg.get(name).maintenance.compact()
            say(f"{name}: compact {'sharded' if reg is sharded else 'single'}"
                f" segments={n_seg} "
                f"seconds={time.perf_counter() - t0:.3f}")
        say(f"{name}: layout {sharded.get(name).index.shard_layout()}")
    say(f"after load: {compiles.line()}")

    for name in single.names():
        sv = single.get(name)
        for b in range(WIRE_BATCHES):
            qs = fresh_queries(sv, rng, synthetic_inputs)
            t0 = time.perf_counter()
            g_s, d_s = (np.asarray(a) for a in
                        sharded.get(name).query(qs, K, N_PROBES))
            ms = (time.perf_counter() - t0) * 1e3
            g_1, d_1 = (np.asarray(a) for a in sv.query(qs, K, N_PROBES))
            same = bitwise_equal(g_s, g_1) and bitwise_equal(d_s, d_1)
            say(f"{name} batch {b}: sharded latency_ms={ms:.3f} "
                f"found={int((g_s >= 0).sum())} bit_identical={same}")
            check(same, f"{name}: sharded answer differs from unsharded")
            check(not np.isin(g_s, first[name][1]).any(),
                  f"{name}: a deleted gid was served")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--items", type=int, default=ITEMS,
                    help="items per tenant of a --rehearse run (always "
                         f"{ITEMS} otherwise)")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases without a TPU (the final gate "
                         "still fails)")
    args = ap.parse_args(argv)
    if args.items != ITEMS and not args.rehearse:
        ap.error("--items needs --rehearse: the ok line stands for the "
                 "full load, where the recall floors were taken")

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    overrides = [v for v in OVERRIDES if os.environ.get(v)]
    if overrides and not args.rehearse:
        print(f"chip_smoke: backend overrides set: {overrides}",
              file=sys.stderr)
        return 1

    import jax
    import numpy as np

    from repro import compile_cache
    from repro.kernels import dispatch

    say(f"compile cache: {compile_cache.enable()}")
    compiles = CompileLog()
    devices = jax.devices()
    dev = devices[0]
    say(f"jax {jax.__version__} devices={devices}")
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    say(f"dispatch {json.dumps(dispatch.describe(), sort_keys=True)}")

    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args, rng, compiles)
    else:
        run_one_chip(args, rng, compiles)
    say(f"all phases passed in {time.perf_counter() - t0:.3f} s; "
        f"{compiles.line()}")

    desc = dispatch.describe()
    problems = [f"{key}={desc[key]!r}" for key in KERNEL_PATHS
                if desc[key] != "compiled"]
    problems += [f"{v} is set" for v in OVERRIDES if os.environ.get(v)]
    if dev.platform != "tpu":
        problems.append(f"platform {dev.platform!r} is not 'tpu'")
    if problems:
        print(f"chip_smoke: final gate failed: {problems}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
