"""The per-layer metrics that read the served path's spans
(``index.lock_wait``, ``query.segments``, ``survivor.gather``), checked on
synthetic spans and a synthetic plain trace against hand counts, and
silent where the program emits none of them."""

import os

import numpy as np
import pytest

from chipbench import bench as benchmod
from chipbench import run, spans
from chipbench.trace import reduce

NEW = ["fanout.dispatch_ms", "fanout.dispatch_ms.closed",
       "index.lock_wait_ms", "fanout.survivor_gather_ms",
       "device.idle_in_dispatch_share"]

BATCHER, WRITER = 1, 2
T_PERF0 = 100.0            # perf_counter seconds at the window's start
NS0 = 5e9                  # the same instant on the trace's clock


def _reader(name):
    return benchmod.load_module(
        os.path.join(benchmod.PACKAGE_DIR, "metrics", f"{name}.py"),
        f"served.{name}")


def _span(sid, name, t0, t1, parent=None, thread=BATCHER, **attrs):
    return {"trace_id": "t", "span_id": sid, "parent_id": parent,
            "name": name, "t0": T_PERF0 + t0, "t1": T_PERF0 + t1,
            "thread": thread, "attrs": attrs}


def _spans():
    """Three batches (seconds from the window's start): the first two
    dispatch a fan-out of 0.10 s and 0.30 s, the third of 0.05 s and
    0.15 s (two fan-outs, as two chunks of one batch would not, but a
    reader must sum them); a writer waits 0.2 s and 0.4 s for the lock,
    a query and a telemetry call wait 0.01 s."""
    return [
        _span(1, "batch", 0.0, 1.0),
        _span(2, "index.lock_wait", 0.0, 0.01, parent=1, op="query"),
        _span(3, "query.segments", 0.01, 0.11, parent=1, segments=257),
        _span(4, "fanout.telemetry", 0.11, 0.5, parent=1),
        _span(5, "index.lock_wait", 0.2, 0.21, parent=4, op="telemetry"),
        _span(6, "survivor.gather", 0.5, 0.52, parent=1, rows=8,
              width=40),
        _span(10, "batch", 2.0, 3.0),
        _span(11, "query.segments", 2.0, 2.3, parent=10),
        _span(12, "survivor.gather", 2.4, 2.44, parent=10),
        _span(20, "batch", 4.0, 5.0),
        _span(21, "query.segments", 4.0, 4.05, parent=20),
        _span(22, "query.segments", 4.1, 4.25, parent=20),
        _span(30, "request", 0.05, 0.9, thread=WRITER, op="insert"),
        _span(31, "index.lock_wait", 0.05, 0.25, parent=30, thread=WRITER,
              op="insert"),
        _span(32, "write.apply", 0.25, 0.3, parent=30, thread=WRITER,
              op="insert", rows=8),
        _span(40, "request", 2.1, 2.9, thread=WRITER, op="delete"),
        _span(41, "index.lock_wait", 2.1, 2.5, parent=40, thread=WRITER,
              op="delete"),
    ]


def _plain():
    """A 6 s window on one chip: device work from 0.05-0.08 s, 0.2-0.6 s,
    2.25-2.35 s and 4.0-4.02 s (as nanoseconds on the trace's clock)."""
    ops = [["op", NS0 + a * 1e9, (b - a) * 1e9]
           for a, b in ((0.05, 0.08), (0.2, 0.6), (2.25, 2.35),
                        (4.0, 4.02))]
    return {"marker": [NS0, 6e9],
            "lines": {"/device:TPU:0|XLA Ops": ops,
                      "/device:TPU:0|XLA Modules": []}}


def _ctx(span_list, plain=None):
    plain = _plain() if plain is None else plain
    return run.Context(config={}, traffic={}, records=[], spans=span_list,
                       plain=plain, clock=reduce.Clock(NS0, T_PERF0),
                       peaks=None, busy_s=reduce.busy_s(plain),
                       window_s=6.0)


@pytest.mark.parametrize("metric", ["fanout.dispatch_ms",
                                    "fanout.dispatch_ms.closed"])
def test_dispatch_ms_is_the_median_fan_out_per_batch(metric):
    # per batch: 100 ms, 300 ms, 50 + 150 ms
    assert _reader(metric).read(_ctx(_spans())) == pytest.approx(200.0)


def test_lock_wait_ms_reads_the_writes_only():
    # the writes waited 200 and 400 ms; the query's 10 ms is not a write
    assert _reader("index.lock_wait_ms").read(_ctx(_spans())) == \
        pytest.approx(300.0)


def test_survivor_gather_ms_is_the_median_per_batch():
    assert _reader("fanout.survivor_gather_ms").read(_ctx(_spans())) == \
        pytest.approx(30.0)


def test_idle_in_dispatch_share_against_a_timeline():
    share = _reader("device.idle_in_dispatch_share").read(_ctx(_spans()))
    # the slow way: a 1 us grid over the window
    grid = np.arange(0.0, 6.0, 1e-6)
    busy = np.zeros(grid.shape, bool)
    for _, s, d in _plain()["lines"]["/device:TPU:0|XLA Ops"]:
        a = (s - NS0) / 1e9
        busy |= (grid >= a) & (grid < a + d / 1e9)
    disp = np.zeros(grid.shape, bool)
    for s in _spans():
        if s["name"] == "query.segments":
            disp |= ((grid >= s["t0"] - T_PERF0)
                     & (grid < s["t1"] - T_PERF0))
    want = 100.0 * (disp & ~busy).sum() / (~busy).sum()
    assert share == pytest.approx(want, abs=1e-3)
    # idle inside the dispatch spans: 0.01-0.05, 0.08-0.11, 2.0-2.25,
    # 4.02-4.05 and 4.1-4.25 s = 0.5 s of 5.45 s idle
    assert share == pytest.approx(100 * 0.5 / 5.45, rel=1e-6)


@pytest.mark.parametrize("metric", NEW)
def test_silent_where_the_program_has_no_such_span(metric):
    """The parent commit's spans: ``batch``, ``admission``, ``request``,
    WAL spans; none of the served-path names."""
    old = [s for s in _spans()
           if s["name"] in ("batch", "request")] + [
        _span(50, "wal.fsync", 0.26, 0.27, parent=30, thread=WRITER)]
    assert _reader(metric).read(_ctx(old)) is None


def test_overlap_of_sorted_interval_sets():
    a = reduce.merged([(0, 10), (20, 30), (40, 50)])
    b = reduce.merged([(5, 25), (28, 45), (60, 70)])
    assert spans.overlap(a, b) == 5 + 5 + 2 + 5
    assert spans.overlap(a, []) == 0.0
