"""Telemetry export contract check: exported JSON-lines vs the catalog.

Usage (CI runs it right after a ``launch.serve --metrics-dir`` smoke)::

    python tools/check_metrics_export.py DIR [--require NAME ...]

``DIR`` is the ``--metrics-dir`` the serve driver exported into; the check
reads **only** ``DIR/metrics.jsonl`` -- it is deliberately an out-of-process
reader, proving that an external consumer can reconstruct the serving
picture from the export alone (no in-process registry access, no report
JSON).  What it asserts:

* every exported metric line is **documented**: its name exists in
  ``repro.obs.metrics.CATALOG``, its type matches, and its label keys are
  exactly the catalog's label schema -- a metric added to the code without
  a catalog entry (or renamed away from one) fails here, which is the
  drift gate;
* every catalog entry with ``required=True`` actually appears -- the
  standard smoke exercises queries, WAL, snapshot, sharding, recall and
  full-rate tracing, so a required metric missing means an instrumentation
  point silently dropped off;
* extra per-leg requirements via ``--require`` (e.g. the 8-device CI leg
  requires ``serve_device_load_total`` and ``router_device_load``, which a
  single-device run legitimately never emits);
* the export is *sufficient*: QPS reconstructs from ``serve_queries_total``
  deltas between snapshots (> 0), per-stage latency histograms
  (``serve_stage_latency_s``) have observations for the served-path
  stages every sampled query and write passes through,
  per-device win/load balance, WAL fsync latency and the recall gauge are
  all readable.

Span lines (``kind: span``) are validated structurally (ids, t1 >= t0)
and must include a fan-out span (``query.segments`` unsharded,
``query.collective`` sharded) when tracing was on.

Exit 0 on a clean export; 1 with a findings list otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.obs.metrics import CATALOG  # noqa: E402

# stages an out-of-process reader must see latency histograms for after a
# traced smoke, sharded or not (the served path's spans feed these)
SERVED_STAGES = ("batch", "index.lock_wait", "fanout.telemetry",
                 "result.sync", "write.apply")
# one of these is the fan-out of every sampled query
FANOUT_SPANS = ("query.segments", "query.collective")

SPAN_FIELDS = ("trace_id", "span_id", "name", "t0", "t1")


def load_lines(path: str):
    """Parse metrics.jsonl into (metric_lines, span_lines, errors)."""
    metrics, spans, errors = [], [], []
    with open(path, encoding="utf-8") as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except ValueError as e:
                errors.append(f"line {i}: not JSON ({e})")
                continue
            kind = obj.get("kind")
            if kind == "metric":
                metrics.append(obj)
            elif kind == "span":
                spans.append(obj)
            else:
                errors.append(f"line {i}: unknown kind {kind!r}")
    return metrics, spans, errors


def check_metrics(metrics: list) -> tuple:
    """Schema-validate metric lines; returns (findings, seen_names)."""
    findings, seen = [], {}
    for m in metrics:
        name = m.get("name")
        spec = CATALOG.get(name)
        if spec is None:
            findings.append(f"undocumented metric {name!r} exported "
                            f"(no CATALOG entry)")
            continue
        if m.get("type") != spec.type:
            findings.append(f"{name}: exported type {m.get('type')!r} != "
                            f"catalog type {spec.type!r}")
        got = tuple(sorted(m.get("labels", {})))
        want = tuple(sorted(spec.labels))
        if got != want:
            findings.append(f"{name}: label keys {got} != catalog schema "
                            f"{want}")
        if spec.type == "histogram":
            if not isinstance(m.get("buckets"), list) \
                    or "sum" not in m or "count" not in m:
                findings.append(f"{name}: histogram line missing "
                                f"buckets/sum/count")
        elif "value" not in m:
            findings.append(f"{name}: {spec.type} line missing 'value'")
        seen.setdefault(name, []).append(m)
    # dedup repeated findings (one full snapshot per flush -> many lines)
    return sorted(set(findings)), seen


def check_required(seen: dict, extra_required=()) -> list:
    findings = []
    for name, spec in sorted(CATALOG.items()):
        if spec.required and name not in seen:
            findings.append(f"required metric {name} never exported")
    for name in extra_required:
        if name not in CATALOG:
            findings.append(f"--require {name}: not a documented metric")
        elif name not in seen:
            findings.append(f"--require {name}: never exported")
    return findings


def reconstruct(seen: dict) -> tuple:
    """Rebuild the serving picture from metric lines alone; returns
    (findings, summary dict for the human)."""
    findings, summary = [], {}

    # QPS from counter deltas between snapshot timestamps, per tenant
    by_tenant = {}
    for m in seen.get("serve_queries_total", []):
        t = m["labels"].get("tenant", "?")
        by_tenant.setdefault(t, []).append((m["ts"], m["value"]))
    qps = {}
    for t, pts in sorted(by_tenant.items()):
        pts.sort()
        dq = pts[-1][1] - pts[0][1]
        dt = pts[-1][0] - pts[0][0]
        qps[t] = round(dq / dt, 2) if dt > 0 else float(dq)
    if not qps or all(v <= 0 for v in qps.values()):
        findings.append("cannot reconstruct a positive QPS from "
                        "serve_queries_total deltas")
    summary["qps"] = qps

    # per-stage latency histograms (last snapshot wins: counters are
    # cumulative, so the final line per series is the full picture)
    stage_counts = {}
    for m in seen.get("serve_stage_latency_s", []):
        stage_counts[m["labels"].get("stage", "?")] = m.get("count", 0)
    summary["stage_observations"] = stage_counts
    missing = [s for s in SERVED_STAGES if stage_counts.get(s, 0) <= 0]
    if missing:
        findings.append(f"no latency observations for stage(s) "
                        f"{missing} in serve_stage_latency_s")

    # per-device win/load balance
    wins = {}
    for m in seen.get("serve_device_wins_total", []):
        key = (m["labels"].get("tenant", "?"), m["labels"].get("device", "?"))
        wins[key] = m["value"]
    summary["device_wins"] = {f"{t}/{d}": v for (t, d), v in sorted(wins.items())}

    # WAL fsync latency
    fsync = [m for m in seen.get("wal_fsync_latency_s", [])]
    if fsync and all(m.get("count", 0) <= 0 for m in fsync):
        findings.append("wal_fsync_latency_s exported but has no "
                        "observations")
    if fsync:
        last = fsync[-1]
        cnt = last.get("count", 0)
        summary["wal_fsync"] = {
            "count": cnt,
            "mean_s": round(last.get("sum", 0.0) / cnt, 6) if cnt else None}

    # recall gauge
    recall = {}
    for m in seen.get("serve_recall_proxy", []):
        recall[m["labels"].get("tenant", "?")] = m["value"]
    summary["recall_proxy"] = recall
    return findings, summary


def check_spans(spans: list, want_stage_spans: bool) -> list:
    findings = []
    stage_seen = False
    for s in spans:
        for f_ in SPAN_FIELDS:
            if f_ not in s:
                findings.append(f"span line missing field {f_!r}")
                break
        else:
            if s["t1"] < s["t0"]:
                findings.append(f"span {s['name']}: t1 < t0")
            if s["name"] in FANOUT_SPANS:
                stage_seen = True
    if want_stage_spans and not stage_seen:
        findings.append("no fan-out spans exported (tracing was expected "
                        "to be on)")
    return sorted(set(findings))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="validate a --metrics-dir export against the metric "
                    "catalog, from outside the process")
    ap.add_argument("metrics_dir", help="directory given to --metrics-dir")
    ap.add_argument("--require", nargs="*", default=[],
                    help="extra metric names that must appear (per-leg "
                         "requirements, e.g. sharded-only series)")
    ap.add_argument("--no-spans", action="store_true",
                    help="don't require fan-out spans (run was not "
                         "traced)")
    args = ap.parse_args(argv)

    path = os.path.join(args.metrics_dir, "metrics.jsonl")
    if not os.path.exists(path):
        print(f"FAIL: {path} does not exist", file=sys.stderr)
        return 1
    metrics, spans, findings = load_lines(path)
    schema_findings, seen = check_metrics(metrics)
    findings += schema_findings
    findings += check_required(seen, args.require)
    recon_findings, summary = reconstruct(seen)
    findings += recon_findings
    findings += check_spans(spans, want_stage_spans=not args.no_spans)

    print(f"[check_metrics_export] {len(metrics)} metric lines, "
          f"{len(spans)} span lines, {len(seen)} distinct metrics")
    print(f"[check_metrics_export] reconstructed: "
          f"{json.dumps(summary, sort_keys=True)}")
    if findings:
        print(f"\n{len(findings)} finding(s) in {path}:", file=sys.stderr)
        for f_ in findings:
            print(f"  - {f_}", file=sys.stderr)
        return 1
    print("[check_metrics_export] OK: export matches the documented schema")
    return 0


if __name__ == "__main__":
    sys.exit(main())
