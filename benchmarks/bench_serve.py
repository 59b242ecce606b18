"""Serve-layer benchmark: streaming mutability + admission batching.

Two experiments, reported into BENCH_results.json:

1. **Insert/query interleave sweep** -- a fresh SegmentedIndex absorbs
   insert and query operations interleaved at mixes 4:1 / 1:1 / 1:4
   (ingest-heavy -> read-heavy), wall-clock timed.  The invariant the serve
   layer exists for is asserted here: the number of distinct jit shapes
   dispatched stays bounded by the chunk palette (queries) and the insert
   chunk (inserts) -- i.e. sustained mixed traffic triggers **zero**
   per-request recompiles.

2. **Batcher latency/throughput curve** -- the deadline dial.  Requests
   arrive on a *simulated* clock (deterministic, CI-friendly) at a fixed
   inter-arrival gap; for each max_delay setting we record queueing latency
   percentiles (in simulated time), mean batch fill (real rows / padded
   rows), and batches dispatched.  Larger deadlines buy fuller batches
   (higher device efficiency) at higher admission latency -- the curve makes
   the trade-off visible per PR.

3. **Tracing overhead** -- batched queries timed in adjacent off/on
   pairs; ``trace_overhead_frac`` (the median per-pair cost of full-rate
   tracing) is gated absolutely at 5% by
   ``tools/check_bench_regression.py`` (docs/architecture.md, invariant
   8).

REPRO_BENCH_SMOKE=1 shrinks both sweeps for CI.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.index import IndexConfig
from repro.obs import trace as obs_trace
from repro.serve.batcher import MicroBatcher
from repro.serve.segments import SegmentedIndex
from repro.serve.stats import occupancy_report, recall_proxy

from .bench_query_engine import smoke_mode

N_DIMS = 32
K = 10
N_PROBES = 2
CHUNK_SIZES = (8, 32, 128)
INSERT_CHUNK = 128


def _cfg() -> IndexConfig:
    return IndexConfig(n_dims=N_DIMS, n_tables=4, n_hashes=4,
                       log2_buckets=10, bucket_capacity=32, r=4.0)


def _fresh_index(segment_capacity: int) -> SegmentedIndex:
    return SegmentedIndex(_cfg(), segment_capacity=segment_capacity,
                          insert_chunk=INSERT_CHUNK, seed=0)


def _interleave_sweep(rng: np.ndarray, n_ops: int, segment_capacity: int
                      ) -> dict:
    """Mixed insert+query traffic; returns per-mix throughput + shape audit."""
    out = {}
    for mix_name, (ins_w, q_w) in (("4:1", (4, 1)), ("1:1", (1, 1)),
                                   ("1:4", (1, 4))):
        idx = _fresh_index(segment_capacity)
        batcher = MicroBatcher(
            lambda q, k, npb: tuple(map(np.asarray,
                                        idx.query(q, k, n_probes=npb))),
            chunk_sizes=CHUNK_SIZES, max_delay_ms=2.0)
        pattern = [True] * ins_w + [False] * q_w
        ins_rows = q_rows = 0
        deleted = 0
        # warmup compiles (excluded from timing)
        idx.insert(rng.normal(size=(INSERT_CHUNK, N_DIMS)))
        batcher.query(rng.normal(size=(8, N_DIMS)), K, N_PROBES)
        t0 = time.perf_counter()
        for op in range(n_ops):
            if pattern[op % len(pattern)]:
                gids = idx.insert(rng.normal(size=(INSERT_CHUNK, N_DIMS)))
                ins_rows += len(gids)
                if op % 7 == 3:       # churn: tombstone a stripe
                    deleted += idx.delete(gids[::8])
            else:
                q = rng.normal(size=(int(rng.integers(1, 24)), N_DIMS))
                fut = batcher.submit(q, K, N_PROBES)
                batcher.pump(force=(op % 4 == 3))
                q_rows += q.shape[0]
        batcher.flush_all()
        dt = time.perf_counter() - t0
        occ = occupancy_report(idx)
        # THE serve-layer invariant: shapes stay within the static palette
        # (one insert shape; at most |palette| query shapes per (k, probes))
        assert batcher.unique_shapes() <= len(CHUNK_SIZES), \
            f"query recompile storm: {dict(batcher.shape_counts)}"
        assert len(idx.query_shapes) <= len(CHUNK_SIZES) + 1, \
            f"index saw unbounded shapes: {idx.query_shapes}"
        out[mix_name] = {
            "wall_s": round(dt, 3),
            "inserts_per_s": round(ins_rows / dt),
            "queries_per_s": round(q_rows / dt),
            "rows_inserted": ins_rows,
            "rows_queried": q_rows,
            "deleted": deleted,
            "n_segments": occ["n_segments"],
            "jit_query_shapes": batcher.unique_shapes(),
        }
    return out


class _SimClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _batcher_curve(rng, n_requests: int, segment_capacity: int) -> dict:
    """Latency/throughput vs deadline on a simulated arrival process."""
    idx = _fresh_index(segment_capacity)
    idx.insert(rng.normal(size=(segment_capacity, N_DIMS)))
    arrival_gap_ms = 0.25          # 4 requests / simulated ms
    out = {}
    for delay_ms in (0.5, 2.0, 8.0):
        clock = _SimClock()
        fills = []
        batcher = MicroBatcher(
            lambda q, k, npb: tuple(map(np.asarray,
                                        idx.query(q, k, n_probes=npb))),
            chunk_sizes=CHUNK_SIZES, max_delay_ms=delay_ms, clock=clock,
            on_batch=lambda real, padded, dt: fills.append(real / padded))
        submitted, latency = {}, []
        for i in range(n_requests):
            clock.advance(arrival_gap_ms / 1e3)
            nq = int(rng.integers(1, 12))
            fut = batcher.submit(rng.normal(size=(nq, N_DIMS)), K, N_PROBES)
            submitted[id(fut)] = (fut, clock())
            batcher.pump()
            for fid in [f for f in submitted if submitted[f][0].done()]:
                fut_, t_sub = submitted.pop(fid)
                latency.append(clock() - t_sub)
        clock.advance(delay_ms / 1e3)
        batcher.pump()
        for fut_, t_sub in submitted.values():
            latency.append(clock() - t_sub)
        lat_ms = np.asarray(latency) * 1e3
        out[f"{delay_ms}ms"] = {
            "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
            "p95_ms": round(float(np.percentile(lat_ms, 95)), 3),
            "mean_batch_fill": round(float(np.mean(fills)), 3),
            "n_batches": batcher.n_batches,
            "n_requests": batcher.n_requests,
        }
    return out


def _trace_overhead(rng, segment_capacity: int, smoke: bool) -> dict:
    """Query cost with tracing off / on at full sampling.

    The dial under test is exactly the production one:
    ``obs.trace.configure``.  The bench host drifts 15-25% across
    multi-second phases (thermal, noisy CI neighbours), which is an
    order of magnitude larger than the effect being measured, so plain
    A-then-B throughput timing flakes the gate no matter how long the
    windows are.  Instead each *single* batched query is timed in an
    adjacent off/on pair -- drift phases are long, so both sides
    of a pair see the same machine -- and the gated number is the
    **median of per-pair ratios**, which additionally discards the
    occasional scheduler stall.  Batches are the palette's largest chunk
    (throughput-shaped traffic): tracing cost is per-span, not per-row,
    so this is the fraction a saturated server actually pays.

    ``qps_trace_*`` are informational aggregates over the same pairs;
    the gated ``trace_overhead_frac`` is the paired median, which is why
    it can differ slightly from ``1 - qps_on/qps_off``.
    """
    idx = _fresh_index(segment_capacity)
    idx.insert(rng.normal(size=(segment_capacity, N_DIMS)))
    qs = rng.normal(size=(CHUNK_SIZES[-1], N_DIMS)).astype(np.float32)
    n_pairs = 60 if smoke else 150
    batcher = MicroBatcher(
        lambda q, k, npb: tuple(map(np.asarray,
                                    idx.query(q, k, n_probes=npb))),
        chunk_sizes=CHUNK_SIZES, max_delay_ms=2.0)
    modes = (("off", 0.0), ("on", 1.0))

    def one(rate: float) -> float:
        obs_trace.configure(sample_rate=rate)
        try:
            t0 = time.perf_counter()
            batcher.query(qs, K, N_PROBES)
            return time.perf_counter() - t0
        finally:
            obs_trace.configure(sample_rate=0.0)

    for _ in range(6):                      # warm every mode's programs
        for _, rate in modes:
            one(rate)
    total = {name: 0.0 for name, _ in modes}
    on_ratio = []
    for _ in range(n_pairs):
        t = {name: one(rate) for name, rate in modes}
        for name in total:
            total[name] += t[name]
        on_ratio.append(t["on"] / t["off"] - 1.0)
    rows = n_pairs * qs.shape[0]
    return {
        "qps_trace_off": round(rows / total["off"]),
        "qps_trace_on": round(rows / total["on"]),
        # the gated number: tracing at sample 1.0 vs off
        "trace_overhead_frac": round(
            max(0.0, float(np.median(on_ratio))), 4),
    }


def run(seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    smoke = smoke_mode()
    n_ops = 20 if smoke else 120
    n_requests = 60 if smoke else 400
    segment_capacity = 512 if smoke else 2048

    interleave = _interleave_sweep(rng, n_ops, segment_capacity)

    # recall sanity on the final mixed-traffic index state
    idx = _fresh_index(segment_capacity)
    emb = rng.normal(size=(2 * segment_capacity, N_DIMS))
    gids = idx.insert(emb)
    idx.delete(gids[:: 5])
    probes = emb[1::97][:16] + 0.05 * rng.normal(size=emb[1::97][:16].shape)
    rec = recall_proxy(idx, probes, K, n_probes=6)

    batcher = _batcher_curve(rng, n_requests, segment_capacity)
    overhead = _trace_overhead(rng, segment_capacity, smoke)

    flat = {"recall_proxy": round(rec, 3), **overhead}
    for mix, vals in interleave.items():
        for kk, vv in vals.items():
            flat[f"interleave_{mix}_{kk}"] = vv
    for dl, vals in batcher.items():
        for kk, vv in vals.items():
            flat[f"batcher_{dl}_{kk}"] = vv
    return flat
