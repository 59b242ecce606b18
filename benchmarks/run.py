"""Benchmark aggregator: one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call where timing makes
sense, else blank; ``derived`` is the figure's summary statistic) and writes
every benchmark's metric dict to ``BENCH_results.json`` so the perf
trajectory is machine-readable across PRs.  Each entry is stamped with the
HEAD ``git_sha`` and its own wall-clock (``wall_s``), so a number in the
trajectory is always attributable to the commit that produced it.

``--smoke`` (or REPRO_BENCH_SMOKE=1) shrinks the expensive sweeps for CI and
writes to ``BENCH_results.smoke.json`` instead -- smoke numbers are sized
for signal-not-noise and must never overwrite the real perf trajectory.
"""

from __future__ import annotations

import json
import os
import sys
import time

RESULTS_JSON = "BENCH_results.json"
SMOKE_RESULTS_JSON = "BENCH_results.smoke.json"


def _git_sha():
    """HEAD commit of the repo the harness runs from (None outside git)."""
    import subprocess
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or None if out.returncode == 0 else None
    except Exception:
        return None


def _run(name, fn):
    from repro.obs import trace as obs_trace
    obs_trace.tracer().drain()     # a previous job's spans are not ours
    t0 = time.perf_counter()
    res = fn()
    us = (time.perf_counter() - t0) * 1e6
    # per-stage wall-clock from whatever spans the job emitted (empty with
    # tracing off): observability rides the perf trajectory, so a stage
    # blowup is attributable to its commit like any other number
    stage = {}
    for s in obs_trace.tracer().drain():
        stage[s["name"]] = stage.get(s["name"], 0.0) + (s["t1"] - s["t0"])
    if stage:
        res = {**res, "trace_stage_s":
               {k: round(v, 4) for k, v in sorted(stage.items())}}
    return name, us, res


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if "--smoke" in argv:
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    from repro import compile_cache
    compile_cache.enable()

    from . import (bench_cosine, bench_embed_error, bench_frontend,
                   bench_hash_throughput, bench_index,
                   bench_ingest_durability, bench_inplace_ingest, bench_l2,
                   bench_query_engine, bench_quantized_serve,
                   bench_replicated_serve, bench_serve, bench_sharded_serve,
                   bench_w2, bench_wasserstein_serve)

    sha = _git_sha()
    print("name,us_per_call,derived")
    jobs = [
        ("fig1_cosine_collisions", bench_cosine.run),
        ("fig2_l2_collisions", bench_l2.run),
        ("fig3_w2_collisions", bench_w2.run),
        ("sec3.2_embed_error", bench_embed_error.run),
        ("index_recall_speedup", bench_index.run),
        ("hash_throughput", bench_hash_throughput.run),
        ("query_engine", bench_query_engine.run),
        ("serve", bench_serve.run),
        ("sharded_serve", bench_sharded_serve.run),
        ("replicated_serve", bench_replicated_serve.run),
        ("wasserstein_serve", bench_wasserstein_serve.run),
        ("quantized_serve", bench_quantized_serve.run),
        ("ingest_durability", bench_ingest_durability.run),
        ("inplace_ingest", bench_inplace_ingest.run),
        ("frontend", bench_frontend.run),
    ]
    all_results = {}
    for name, fn in jobs:
        try:
            n, us, res = _run(name, fn)
            for k, v in res.items():
                print(f"{n}/{k},{us:.0f},{v}")
            # every entry self-stamps provenance: the perf trajectory is
            # only attributable if each number knows its commit + cost
            all_results[name] = {"us_total": round(us),
                                 "wall_s": round(us / 1e6, 3),
                                 "git_sha": sha, **res}
        except Exception as e:  # keep the harness running; report the failure
            print(f"{name},,ERROR:{type(e).__name__}:{e}")
            all_results[name] = {"error": f"{type(e).__name__}: {e}",
                                 "git_sha": sha}

    import jax

    from .bench_query_engine import smoke_mode
    all_results["_meta"] = {
        "backend": jax.default_backend(),
        "smoke": smoke_mode(),
        "git_sha": sha,
    }
    out_json = SMOKE_RESULTS_JSON if smoke_mode() else RESULTS_JSON
    with open(out_json, "w") as f:
        json.dump(all_results, f, indent=2, sort_keys=True)
    print(f"# wrote {out_json}", file=sys.stderr)
    # Every benchmark ran and its result is recorded -- but a failure
    # (including bench_serve's jit shape-count asserts) must still fail the
    # harness, or CI can never catch a regression it exists to guard.
    failed = [n for n, r in all_results.items() if "error" in r]
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
