"""Observability layer: metrics registry, tracer, exporter, stats clocks.

Three groups:

* in-process unit tests for the unified registry (catalog enforcement),
  the tracer (deterministic sampling, ring bound, nesting/attach), the
  exporter JSONL/Prometheus round trip, and ``ServingStats`` time
  semantics under an injected clock (exact window boundaries, reservoir
  ring wraparound, single-event rates, padding efficiency);
* invariant-8 checks: sampling 0 records nothing and answers bit-
  identically to a sampled run, on the fp32 and int8 query paths and on
  inserts and deletes; with sampling on, a batch's host work is named by
  served-path spans (lock waits, fan-out dispatch, telemetry, the
  survivor gather and rerank, the result copy), each mirrored into a JAX
  profiler trace, and the segment programs carry stable names;
* subprocess tests on an 8-device host mesh: a sampled sharded query
  emits ``query.collective`` and answers bit-identically, and a kill -9
  crash + recover() yields ``recover.restore`` / ``recover.replay`` spans
  plus recovery metrics.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.index import IndexConfig
from repro.obs import export as obs_export
from repro.obs import trace as obs_trace
from repro.obs.metrics import CATALOG, MetricsRegistry
from repro.obs.trace import Tracer
from repro.serve.batcher import MicroBatcher
from repro.serve.segments import SegmentedIndex
from repro.serve.stats import ServingStats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(n_devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count"
                        f"={n_devices}")
    return env


def _run(code: str, n_devices=1, timeout=560):
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=_env(n_devices))


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.inc("serve_queries_total", 3, tenant="t")
    reg.inc("serve_queries_total", 2, tenant="t")
    reg.set("serve_recall_proxy", 0.75, tenant="t")
    reg.observe("serve_query_latency_s", 0.005, tenant="t")
    reg.observe("serve_query_latency_s", 2.0, tenant="t")
    assert reg.value("serve_queries_total", tenant="t") == 5
    assert reg.value("serve_recall_proxy", tenant="t") == 0.75
    h = reg.value("serve_query_latency_s", tenant="t")
    assert h["count"] == 2 and abs(h["sum"] - 2.005) < 1e-9
    # cumulative buckets end at +Inf == count
    assert h["buckets"][-1] == ["+Inf", 2]
    # collect() is export-shaped: name/type/labels per entry
    entries = {e["name"]: e for e in reg.collect()}
    assert entries["serve_queries_total"]["labels"] == {"tenant": "t"}
    assert entries["serve_query_latency_s"]["type"] == "histogram"


def test_registry_rejects_schema_drift():
    reg = MetricsRegistry()
    with pytest.raises(KeyError):
        reg.inc("not_a_documented_metric", tenant="t")
    with pytest.raises(ValueError):
        reg.inc("serve_queries_total", shard="0")      # wrong label key
    with pytest.raises(ValueError):
        reg.inc("serve_queries_total")                 # missing tenant
    with pytest.raises(TypeError):
        reg.set("serve_queries_total", 1.0, tenant="t")  # counter, not gauge


def test_registry_summary_filters_by_label():
    reg = MetricsRegistry()
    reg.inc("serve_queries_total", 7, tenant="a")
    reg.inc("serve_queries_total", 9, tenant="b")
    reg.inc("serve_segment_wins_total", 4, tenant="a", segment="2")
    s = reg.summary(tenant="a")
    assert s["serve_queries_total"] == 7
    assert s["serve_segment_wins_total{segment=2}"] == 4
    assert not any("9" == str(v) for v in s.values())


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_sampling_is_deterministic_in_trace_id():
    a = Tracer(sample_rate=0.5, seed=1234)
    b = Tracer(sample_rate=0.5, seed=1234)
    da = [a.start_trace().sampled for _ in range(200)]
    db = [b.start_trace().sampled for _ in range(200)]
    assert da == db                       # same seed -> same decisions
    frac = sum(da) / len(da)
    assert 0.3 < frac < 0.7               # rate is actually honoured
    c = Tracer(sample_rate=0.0)
    assert c.start_trace() is None        # rate 0: no context at all


def test_span_ring_is_bounded():
    tr = Tracer(sample_rate=1.0, buffer=16)
    for i in range(50):
        with tr.span("hash", tenant="t", i=i):
            pass
    spans = tr.spans()
    assert len(spans) == 16
    assert [s["attrs"]["i"] for s in spans] == list(range(34, 50))
    assert tr.n_spans == 50               # drops are countable
    assert tr.drain() and tr.spans() == []


def test_span_nesting_and_attach():
    tr = Tracer(sample_rate=1.0)
    with tr.span("request", tenant="t") as root:
        ctx = tr.current()
        assert ctx is not None and ctx.sampled and tr.sampled()
        with tr.span("hash", tenant="t") as child:
            assert child.parent_id == root.span_id
        tr.record("admission", 1.0, 2.0, tenant="t")
    assert tr.current() is None           # root span restored the thread
    by_name = {s["name"]: s for s in tr.spans()}
    assert by_name["hash"]["parent_id"] == by_name["request"]["span_id"]
    assert by_name["admission"]["parent_id"] == by_name["request"]["span_id"]
    assert by_name["request"]["parent_id"] is None
    assert len({s["trace_id"] for s in tr.spans()}) == 1  # one trace


def test_unsampled_context_suppresses_descendants():
    tr = Tracer(sample_rate=0.5, seed=0)
    # find an unsampled decision, then check span() under it is a no-op
    for _ in range(100):
        ctx = tr.start_trace()
        if not ctx.sampled:
            break
    assert not ctx.sampled
    with tr.attach(ctx):
        assert tr.span("hash", tenant="t") is obs_trace._NOOP
    assert tr.spans() == []


def test_fanout_counter_and_wait_span_are_declared():
    from repro.obs.trace import STAGE_SPANS

    spec = CATALOG["serve_fanout_batches_total"]
    assert spec.type == "counter"
    assert sorted(spec.labels) == ["path", "tenant"]
    assert "fanout.wait" in STAGE_SPANS
    reg = MetricsRegistry()
    reg.inc("serve_fanout_batches_total", tenant="t", path="stacked")
    assert reg.value("serve_fanout_batches_total", tenant="t",
                     path="stacked") == 1
    tr = Tracer(sample_rate=1.0, metrics=reg)
    with tr.span("fanout.wait", tenant="t"):
        pass
    assert reg.value("serve_stage_latency_s", tenant="t",
                     stage="fanout.wait")["count"] == 1


def test_stage_spans_feed_latency_histogram():
    reg = MetricsRegistry()
    tr = Tracer(sample_rate=1.0, metrics=reg)
    with tr.span("survivor.gather", tenant="t"):
        pass
    with tr.span("not_a_stage", tenant="t"):
        pass
    h = reg.value("serve_stage_latency_s", tenant="t",
                  stage="survivor.gather")
    assert h["count"] == 1
    assert reg.value("serve_stage_latency_s", tenant="t",
                     stage="not_a_stage") is None


# ---------------------------------------------------------------------------
# ServingStats time semantics (injected clock)
# ---------------------------------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _stats(clock, **kw):
    return ServingStats(clock=clock, tenant="t",
                        metrics=MetricsRegistry(), **kw)


def test_window_trim_at_exact_boundary():
    clock = _Clock()
    st = _stats(clock, window_s=10.0)
    st.record_query(4)                       # event at t=0
    clock.t = 10.0                           # exactly window edge
    # trim drops strictly-older events: t=0 is NOT < 10 - 10, so it stays
    assert st.qps() == pytest.approx(4 / 10.0)
    clock.t = 10.0 + 1e-6                    # one tick past the edge
    assert st.qps() == 0.0


def test_latency_reservoir_wraps_as_a_ring():
    clock = _Clock()
    st = _stats(clock, reservoir=8)
    for i in range(1, 21):                   # 20 > 8: ring wraps twice
        st.record_query(1, latency_s=float(i))
    assert st._lat_n == 20
    p = st.latency_percentiles()
    # only the last 8 observations (13..20 s) survive the wraparound
    assert p["p50_ms"] == pytest.approx(
        float(np.percentile(np.arange(13, 21) * 1e3, 50)))
    assert p["p99_ms"] <= 20_000.0 and p["p50_ms"] >= 13_000.0


def test_rate_with_single_event():
    clock = _Clock()
    st = _stats(clock)
    clock.t = 5.0
    st.record_query(6)
    # now == the only event's timestamp: span clamps to 1e-9, rate is
    # finite (never a ZeroDivisionError)
    assert np.isfinite(st.qps()) and st.qps() > 0
    clock.t = 8.0
    assert st.qps() == pytest.approx(6 / 3.0)
    st2 = _stats(clock)
    assert st2.qps() == 0.0                  # no events at all


def test_padding_efficiency_tracks_fill_rows():
    clock = _Clock()
    st = _stats(clock)
    assert st.padding_efficiency() == 1.0    # no batches yet
    st.record_batch(30, 32, 0.01)
    st.record_batch(16, 32, 0.01)
    assert st.padding_efficiency() == pytest.approx(46 / 64)
    snap = st.snapshot()
    assert snap["padding_efficiency"] == pytest.approx(0.7188, abs=1e-4)
    assert snap["recall_proxy"] is None
    st.record_recall(0.9)
    assert st.snapshot()["recall_proxy"] == 0.9
    # the registry saw pad-fill rows only, not the chunk totals
    assert st.metrics.value("serve_batch_rows_real_total",
                            tenant="t") == 46
    assert st.metrics.value("serve_batch_rows_padded_total",
                            tenant="t") == 18


def test_queue_wait_histogram_from_batcher():
    clock = _Clock()
    reg = MetricsRegistry()
    calls = []

    def qfn(q, k, npb):
        calls.append(q.shape)
        return (np.zeros((q.shape[0], k), np.int32),
                np.zeros((q.shape[0], k), np.float32))

    b = MicroBatcher(qfn, chunk_sizes=(8,), max_delay_ms=5.0, clock=clock,
                     tenant="t", metrics=reg)
    b.submit(np.zeros((3, 4), np.float32), k=2)
    clock.t = 0.25                           # request waited 250 ms
    b.flush_all()
    h = reg.value("serve_queue_wait_s", tenant="t")
    assert h["count"] == 1
    assert h["sum"] == pytest.approx(0.25)
    assert calls == [(8, 4)]


# ---------------------------------------------------------------------------
# invariant 8: tracing is invisible; served-path spans when it is on
# ---------------------------------------------------------------------------


def _small_index(seed=0, precision="fp32"):
    cfg = IndexConfig(n_dims=16, n_tables=4, n_hashes=4, log2_buckets=8,
                      bucket_capacity=32, r=4.0)
    idx = SegmentedIndex(cfg, segment_capacity=64, insert_chunk=32,
                         seed=seed, precision=precision,
                         on_fanout=lambda *a: None)
    rng = np.random.default_rng(seed)
    g = idx.insert(rng.normal(size=(150, 16)).astype(np.float32))
    idx.delete(g[::7])
    return idx, rng


def test_rate0_bit_identical_and_span_free():
    idx, rng = _small_index()
    q = rng.normal(size=(8, 16)).astype(np.float32)
    base_g, base_d = map(np.asarray, idx.query(q, 5, n_probes=3))
    tr = obs_trace.tracer()
    tr.drain()
    before = tr.n_spans
    try:
        obs_trace.configure(sample_rate=0.0)
        g, d = map(np.asarray, idx.query(q, 5, n_probes=3))
    finally:
        obs_trace.configure(sample_rate=0.0)
    np.testing.assert_array_equal(base_g, g)
    np.testing.assert_array_equal(base_d, d)
    assert tr.n_spans == before              # not one span was recorded


def _apply(idx, kind, rng_seed=7):
    """One operation of ``kind`` on ``idx``; returns what it answers plus
    the index's answers afterwards (so writes are compared by effect)."""
    rng = np.random.default_rng(rng_seed)
    q = rng.normal(size=(8, 16)).astype(np.float32)
    if kind == "insert":
        out = [idx.insert(rng.normal(size=(40, 16)).astype(np.float32))]
    elif kind == "delete":
        out = [np.asarray(idx.delete(np.arange(3, 120, 5)))]
    else:
        out = []
    out += [np.asarray(a) for a in idx.query(q, 5, n_probes=3)]
    return out


@pytest.mark.parametrize("kind,precision", [
    ("query", "fp32"), ("query", "int8"), ("insert", "fp32"),
    ("delete", "fp32")])
def test_rate0_records_nothing_and_answers_alike(monkeypatch, kind,
                                                 precision):
    """Sampling 0: no span, no profiler annotation, no thread capture and
    no timed lock acquire, on the query (fp32, int8) and write paths, and
    the same answers as the same operation inside a sampled trace."""
    idx_on, _ = _small_index(seed=5, precision=precision)
    idx_off, _ = _small_index(seed=5, precision=precision)
    tr = obs_trace.configure(sample_rate=1.0)
    try:
        with tr.span("batch", tenant="t"):
            traced = _apply(idx_on, kind)
    finally:
        obs_trace.configure(sample_rate=0.0)
    names = {s["name"] for s in tr.drain()}
    assert "index.lock_wait" in names

    def never(*a, **kw):
        raise AssertionError("a tracing hook ran with sampling off")

    monkeypatch.setattr(obs_trace, "Span", never)
    monkeypatch.setattr(obs_trace, "_annotate", never)
    monkeypatch.setattr(obs_trace, "_TimedAcquire", never)
    before = tr.n_spans
    plain = _apply(idx_off, kind)
    assert tr.n_spans == before
    for a, b in zip(traced, plain):
        np.testing.assert_array_equal(a, b)


def _served(name, precision="fp32", mesh=None, **spec):
    from repro.serve import ServableRegistry, ServableSpec

    reg = ServableRegistry(mesh=mesh)
    sv = reg.register(ServableSpec(
        name=name, n_dims=16, r=2.0, log2_buckets=8, bucket_capacity=64,
        segment_capacity=64, insert_chunk=32, chunk_sizes=(8,),
        max_delay_ms=1.0, precision=precision, **spec))
    rng = np.random.default_rng(0)
    for _ in range(4):                       # several sealed segments
        sv.insert(rng.normal(size=(50, 16)).astype(np.float32))
    return sv, rng.normal(size=(8, 16)).astype(np.float32)


def _traced_batch(sv, q):
    """One sampled query through the batcher; returns (answers, spans)."""
    tr = obs_trace.configure(sample_rate=1.0)
    tr.drain()
    try:
        with tr.attach(tr.start_trace()):
            fut = sv.submit_query(q, 5, n_probes=3)
        sv.batcher.flush_all()
        out = fut.result()
    finally:
        obs_trace.configure(sample_rate=0.0)
    return out, tr.drain()


def _inside(spans, outer):
    """Spans whose parent chain reaches ``outer``."""
    by_id = {s["span_id"]: s for s in spans}
    out = []
    for s in spans:
        p = s
        while p["parent_id"] is not None and p["parent_id"] in by_id:
            p = by_id[p["parent_id"]]
            if p is outer:
                out.append(s)
                break
    return out


def test_fp32_batch_names_its_host_work():
    sv, q = _served("t32")
    n_segs = len(sv.index.segments)
    _, spans = _traced_batch(sv, q)
    batch, = [s for s in spans if s["name"] == "batch"]
    inner = _inside(spans, batch)
    names = {s["name"] for s in inner}
    assert {"index.lock_wait", "query.segments", "fanout.wait",
            "fanout.telemetry", "result.sync"} <= names
    seg, = [s for s in inner if s["name"] == "query.segments"]
    # the stacked program reads every segment in one dispatch
    assert seg["attrs"]["segments"] == n_segs > 1
    assert seg["attrs"]["programs"] == 1
    wait, = [s for s in inner if s["name"] == "fanout.wait"]
    tele, = [s for s in inner if s["name"] == "fanout.telemetry"]
    assert seg["t1"] <= wait["t0"] <= wait["t1"] <= tele["t0"]
    # every span of the batch opened on the batcher's thread, inside it
    assert {s["thread"] for s in inner} == {batch["thread"]}
    for s in inner:
        assert batch["t0"] <= s["t0"] <= s["t1"] <= batch["t1"]
    assert {s["attrs"]["op"] for s in inner
            if s["name"] == "index.lock_wait"} == {"query", "telemetry"}


def test_int8_batch_names_the_survivor_gather_and_rerank():
    sv, q = _served("t8", precision="int8")
    want = sv.query(q, 5, n_probes=3)
    got, spans = _traced_batch(sv, q)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    batch, = [s for s in spans if s["name"] == "batch"]
    inner = {s["name"]: s for s in _inside(spans, batch)}
    assert {"survivor.gather", "survivor.rerank", "query.segments",
            "fanout.wait"} <= set(inner)
    assert inner["survivor.gather"]["attrs"]["rows"] == 8
    # the wait for the fan-out program is not the gather's
    assert inner["fanout.wait"]["t1"] <= inner["survivor.gather"]["t0"]
    assert inner["survivor.gather"]["t1"] <= inner["survivor.rerank"]["t0"]
    ops = {s["attrs"]["op"] for s in _inside(spans, batch)
           if s["name"] == "index.lock_wait"}
    assert {"query", "gather"} <= ops


def test_blocked_writer_records_its_lock_wait():
    import threading
    import time

    idx, rng = _small_index()
    rows = rng.normal(size=(8, 16)).astype(np.float32)
    tr = obs_trace.configure(sample_rate=1.0)
    tr.drain()
    hold_s = 0.2
    try:
        with idx._lock:
            def write():
                with tr.attach(tr.start_trace()):
                    idx.insert(rows)

            writer = threading.Thread(target=write)
            writer.start()
            time.sleep(hold_s)
        writer.join(timeout=60)
        assert not writer.is_alive()
    finally:
        obs_trace.configure(sample_rate=0.0)
    spans = tr.drain()
    wait, = [s for s in spans if s["name"] == "index.lock_wait"]
    assert wait["attrs"]["op"] == "insert"
    assert wait["t1"] - wait["t0"] >= hold_s
    assert wait["thread"] != threading.get_ident()
    apply_, = [s for s in spans if s["name"] == "write.apply"]
    assert apply_["attrs"] == {"tenant": "default", "op": "insert",
                               "rows": 8}
    assert apply_["t0"] >= wait["t1"]


def test_sampled_spans_land_in_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    sv, q = _served("tprof")
    sv.query(q, 5, n_probes=3)               # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, spans = _traced_batch(sv, q)
    finally:
        jax.profiler.stop_trace()
    path, = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    host = [ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    for name in ("batch", "index.lock_wait", "query.segments",
                 "fanout.wait", "fanout.telemetry", "result.sync"):
        assert host.count(name) == sum(s["name"] == name for s in spans), \
            name
    # retroactive spans have no mirror
    assert "admission" in {s["name"] for s in spans}
    assert "admission" not in host


@pytest.mark.parametrize("factory,name", [
    ("_segment_query_fn", "segment_query"),
    ("_quantized_segment_query_fn", "segment_query_codes"),
    ("_segment_insert_fn", "segment_insert"),
])
def test_segment_programs_carry_stable_names(factory, name):
    import jax.numpy as jnp
    from repro.serve import segments as segmod

    idx, rng = _small_index(precision="int8")
    seg = idx.segments[0]
    q = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    if factory == "_segment_insert_fn":
        fn = segmod._segment_insert_fn(idx.cfg, idx.insert_chunk)
        args = (seg.state, jnp.zeros((idx.insert_chunk, 16)), jnp.int32(0),
                jnp.int32(0))
    else:
        fn = getattr(segmod, factory)(idx.cfg, 5, 3, idx.backend)
        args = (seg.state, q, seg.live, seg.gids)
        if factory == "_quantized_segment_query_fn":
            args += (seg.scale,)
    text = fn.lower(*args).as_text()
    assert f"module @jit_{name} " in text


def test_bucket_overflow_gauges_match_a_numpy_count():
    from repro.obs import metrics as obs_metrics

    # 2 tables x 4 buckets x 4 slots for 40 items: most buckets overflow
    cfg = IndexConfig(n_dims=16, n_tables=2, n_hashes=2, log2_buckets=2,
                      bucket_capacity=4, r=4.0)
    idx = SegmentedIndex(cfg, segment_capacity=64, insert_chunk=32,
                         tenant="ovf")
    rng = np.random.default_rng(0)
    idx.insert(rng.normal(size=(40, 16)).astype(np.float32))
    table = np.asarray(idx.delta.state.table)
    held = table[table >= 0]
    # every item is placed once per table, in a slot or dropped
    want_dropped = 40 * cfg.n_tables - held.size
    want_unreachable = 40 - np.unique(held).size
    assert want_dropped > 0 and want_unreachable > 0
    tr = obs_trace.configure(sample_rate=1.0)
    tr.drain()
    try:
        idx.maintenance.seal()
    finally:
        obs_trace.configure(sample_rate=0.0)
    seal, = [s for s in tr.drain() if s["name"] == "seal"]
    assert seal["attrs"]["overflow_slots"] == want_dropped
    assert seal["attrs"]["unreachable_items"] == want_unreachable
    # a seal the delta's filling forces: counted on the device, read back
    # (with the first) only when asked
    idx.insert(rng.normal(size=(65, 16)).astype(np.float32))
    second = idx.segments[1]
    assert second.sealed and second.bucket_health is not None
    table = np.asarray(second.state.table)
    held = table[table >= 0]
    want_dropped += 64 * cfg.n_tables - held.size
    want_unreachable += 64 - np.unique(held).size
    assert idx.bucket_overflow() == {"overflow_slots": want_dropped,
                                     "unreachable_items": want_unreachable}
    reg = obs_metrics.registry()
    assert reg.value("index_bucket_overflow_slots",
                     tenant="ovf") == want_dropped
    assert reg.value("index_unreachable_items",
                     tenant="ovf") == want_unreachable


# ---------------------------------------------------------------------------
# exporter round trip
# ---------------------------------------------------------------------------


def test_exporter_jsonl_and_prometheus(tmp_path):
    reg = MetricsRegistry()
    tr = Tracer(sample_rate=1.0, metrics=reg)
    reg.inc("serve_queries_total", 12, tenant="t")
    reg.observe("wal_fsync_latency_s", 0.002, tenant="t")
    with tr.span("query.segments", tenant="t"):
        pass
    exp = obs_export.Exporter(str(tmp_path / "metrics.jsonl"),
                              registry=reg, tracer=tr,
                              prom_path=str(tmp_path / "metrics.prom"))
    n = exp.flush()
    assert n >= 4                 # 2 metric series (one is a stage
    #                               histogram from the span) + 1 span
    lines = [json.loads(x) for x in
             (tmp_path / "metrics.jsonl").read_text().splitlines()]
    metrics = [o for o in lines if o["kind"] == "metric"]
    spans = [o for o in lines if o["kind"] == "span"]
    assert len({o["ts"] for o in metrics}) == 1   # one shared snapshot ts
    for o in metrics:                             # schema-is-code contract
        spec = CATALOG[o["name"]]
        assert o["type"] == spec.type
        assert sorted(o["labels"]) == sorted(spec.labels)
    assert spans and spans[0]["name"] == "query.segments"
    assert spans[0]["t1"] >= spans[0]["t0"]
    # drained: a second flush re-snapshots metrics but not old spans
    exp.flush()
    again = [json.loads(x) for x in
             (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert sum(o["kind"] == "span" for o in again) == 1
    prom = (tmp_path / "metrics.prom").read_text()
    assert 'serve_queries_total{tenant="t"} 12' in prom
    assert "# TYPE wal_fsync_latency_s histogram" in prom
    assert 'wal_fsync_latency_s_count{tenant="t"} 1' in prom
    exp.close()


def test_export_checker_tool_rejects_drift(tmp_path):
    """The CI drift gate really fails on an undocumented metric name."""
    good = {"kind": "metric", "ts": 1.0, "name": "serve_queries_total",
            "type": "counter", "labels": {"tenant": "t"}, "value": 5}
    bad = dict(good, name="serve_undocumented_total")
    p = tmp_path / "metrics.jsonl"
    p.write_text(json.dumps(good) + "\n" + json.dumps(bad) + "\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "check_metrics_export.py"),
         str(tmp_path), "--no-spans"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "undocumented metric" in proc.stderr


# ---------------------------------------------------------------------------
# acceptance: one sampled query on the 8-device sharded path
# ---------------------------------------------------------------------------


def test_sharded_query_collective_span_bit_identical():
    code = """
        import numpy as np
        import jax.numpy as jnp
        from repro.core import distributed
        from repro.launch.mesh import make_serve_mesh
        from repro.obs import trace as obs_trace
        from repro.serve import ServableRegistry, ServableSpec

        mesh = make_serve_mesh(8)
        reg = ServableRegistry(mesh=mesh)
        sv = reg.register(ServableSpec(
            name="t8", n_dims=16, r=2.0, log2_buckets=8, bucket_capacity=64,
            segment_capacity=64, insert_chunk=32, chunk_sizes=(128,),
            max_delay_ms=1.0, shard_axis="serve"))
        rng = np.random.default_rng(0)
        for _ in range(6):                       # several sealed segments
            sv.insert(rng.normal(size=(64, 16)).astype(np.float32))
        q = rng.normal(size=(128, 16)).astype(np.float32)

        tr = obs_trace.tracer()
        before = tr.n_spans
        base_g, base_d = map(np.asarray, sv.query(q, 10, n_probes=3))
        assert tr.n_spans == before, "rate 0 recorded a span"

        tr = obs_trace.configure(sample_rate=1.0)
        tr.drain()
        with tr.attach(tr.start_trace()):
            fut = sv.submit_query(q, 10, n_probes=3)
        sv.batcher.flush_all()
        g, d = fut.result()
        obs_trace.configure(sample_rate=0.0)
        np.testing.assert_array_equal(base_g, np.asarray(g))
        np.testing.assert_array_equal(base_d, np.asarray(d))

        by = {}
        for s in tr.drain():
            by.setdefault(s["name"], []).append(s)
        for name in ("batch", "index.lock_wait", "query.collective",
                     "fanout.telemetry", "result.sync"):
            assert name in by, f"missing span {name}: {sorted(by)}"
        assert "query.segments" not in by
        coll, = by["query.collective"]
        pl = sv.index._placement
        assert coll["attrs"]["devices"] == 8 == pl.n_dev
        assert coll["attrs"]["per_dev"] == pl.per_dev
        batch, = by["batch"]
        assert batch["t0"] <= coll["t0"] <= coll["t1"] <= batch["t1"]

        # the collective's program is named for what it does
        fn = distributed._sharded_segment_query_fn(
            sv.index.cfg, 10, 3, sv.index.backend, pl.mesh, pl.axis,
            pl.per_dev, False)
        text = fn.lower(pl.sealed_state, pl.sealed_gids, pl.sealed_live,
                        jnp.ones((8 * pl.per_dev,), jnp.float32),
                        jnp.ones((8 * pl.per_dev,), jnp.bool_),
                        pl.delta_state, pl.delta_gids, pl.delta_live,
                        jnp.asarray(q)).as_text()
        assert "module @jit_segment_query_sharded " in text, text[:200]
        print("OK")
    """
    proc = _run(code, n_devices=8)
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout


def test_kill9_recovery_emits_recovery_spans(tmp_path):
    wal = str(tmp_path / "wal")
    snap = str(tmp_path / "snap")
    crash = f"""
        import numpy as np
        from repro.serve import ServableRegistry, ServableSpec, faults
        reg = ServableRegistry(wal_dir={wal!r}, fsync_every=2)
        sv = reg.register(ServableSpec(
            name="t", n_dims=16, r=2.0, log2_buckets=8, bucket_capacity=64,
            segment_capacity=64, insert_chunk=32, chunk_sizes=(8, 32)))
        rng = np.random.default_rng(0)
        for _ in range(3):
            sv.insert(rng.normal(size=(40, 16)).astype(np.float32))
        reg.snapshot({snap!r}, step=1)
        faults.install(faults.FaultPlan(("wal.append", 3, "kill")))
        for _ in range(8):
            sv.insert(rng.normal(size=(40, 16)).astype(np.float32))
        raise SystemExit("unreachable: the fault plan must kill us")
    """
    proc = _run(crash)
    assert proc.returncode == -signal.SIGKILL, (proc.returncode,
                                                proc.stderr)
    recover = f"""
        import numpy as np
        from repro.obs import metrics as obs_metrics
        from repro.obs import trace as obs_trace
        from repro.serve import ServableRegistry
        tr = obs_trace.configure(sample_rate=1.0)
        tr.drain()
        reg = ServableRegistry(wal_dir={wal!r})
        reports = reg.recover(ckpt_root={snap!r}, wal_dir={wal!r})
        assert reports["t"]["restored_step"] == 1, reports
        assert reports["t"]["n_records"] > 0, reports
        names = [s["name"] for s in tr.drain()]
        assert "recover.restore" in names, names
        assert "recover.replay" in names, names
        assert "ckpt.restore" in names, names
        m = obs_metrics.registry()
        assert m.value("recovery_restores_total", tenant="t") == 1
        assert m.value("recovery_replayed_records_total", tenant="t") > 0
        assert m.value("ckpt_restores_total", tenant="t") == 1
        g, d = reg.get("t").index.query(
            np.asarray(np.random.default_rng(1).normal(size=(4, 16)),
                       np.float32), 5, n_probes=3)
        assert np.asarray(g).shape == (4, 5)
        print("OK")
    """
    proc = _run(recover)
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout
