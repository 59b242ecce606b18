"""The trace-to-metric reduction, checked on a committed slice of a chip
trace (``fixtures/trace_l1q8.json``: 25 ms of a traced run of the int8
search cell on one TPU v5 lite) against plain recomputations."""

import json
import os
import time

import numpy as np
import pytest

from chipbench import bench as benchmod
from chipbench import run
from chipbench.trace import reduce, xplane
from chipbench.work import fused_query

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_l1q8.json")


@pytest.fixture(scope="module")
def fixture():
    with open(FIXTURE, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ctx(fixture):
    t0, t1 = reduce.window(fixture)
    return run.Context(
        config=benchmod.Benchmark().cell("l1q8-search-closed").config,
        traffic={}, records=[], spans=fixture["spans"], plain=fixture,
        clock=reduce.Clock(*fixture["clock"]),
        peaks=run.peaks_for(benchmod.ROOT, "TPU v5 lite"),
        busy_s=reduce.busy_s(fixture), window_s=(t1 - t0) / 1e9)


def _timeline(events, t0, t1, step=100.0):
    """Busy flags at every ``step`` ns: the union the slow way."""
    grid = np.arange(t0, t1, step)
    busy = np.zeros(grid.shape, bool)
    for _, s, d in events:
        busy |= (grid >= s) & (grid < s + d)
    return busy, step


def _ops(fixture):
    return reduce.device_lines(fixture, reduce.OPS_LINE)["/device:TPU:0"]


def test_busy_union_matches_a_timeline(fixture):
    t0, t1 = reduce.window(fixture)
    flags, step = _timeline(_ops(fixture), t0, t1)
    busy = reduce.busy_ns(_ops(fixture), t0, t1)
    assert busy == pytest.approx(flags.sum() * step, rel=2e-3)
    assert reduce.busy_s(fixture) == pytest.approx(busy / 1e9)
    assert 0 < busy < t1 - t0


def _plain_merged(intervals):
    """The union the slow way, one interval at a time."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_vectorised_union_equals_the_plain_one(seed):
    """Overlapping, nested, touching, empty and out-of-window events, on
    a coarse grid so that ties happen: every number comes out exactly as
    the plain loop gives it."""
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, 400, 3000).astype(float) * 0.5
    durs = rng.choice([0.0, 0.5, 1.0, 3.5, 40.0], 3000)
    events = [["op", s, d] for s, d in zip(starts, durs)]
    t0, t1 = 20.0, 180.0
    cut = [(max(s, t0), min(s + d, t1)) for _, s, d in events]
    cut = [(s, e) for s, e in cut if e > s]
    want = _plain_merged(cut)
    assert reduce.merged(cut) == want
    assert reduce.merged(reduce.clipped(events, t0, t1)) == want
    assert reduce.busy_ns(events, t0, t1) == sum(e - s for s, e in want)
    gaps, cur = [], t0
    for s, e in want:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    assert reduce.idle_gaps(events, t0, t1) == gaps
    assert reduce.merged([]) == []
    assert reduce.idle_gaps([], t0, t1) == [(t0, t1)]


def _plain_labels(gaps, host_spans):
    """Each gap's label the slow way: scan back from the last span opened
    at or before its midpoint to the first one still open."""
    spans = sorted(host_spans, key=lambda sp: sp[1])
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        best = "no span"
        for sp in reversed([sp for sp in spans if sp[1] <= mid]):
            if sp[2] > mid:
                best = sp[0]
                break
        out.append((best, e - s))
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gap_labels_equal_the_plain_scan(seed):
    """Nested, tied and closed spans, gaps in any order."""
    rng = np.random.default_rng(seed)
    host = []
    for i in range(300):
        a = float(rng.integers(0, 200))
        host.append((f"s{i % 7}", a, a + float(rng.choice([0.0, 1.0, 5.0,
                                                           30.0]))))
    gaps = [(float(a), float(a) + float(rng.choice([0.0, 1.0, 2.0])))
            for a in rng.integers(-10, 240, 500)]
    totals = {}
    for name, sec in _plain_labels(gaps, host):
        totals[name] = totals.get(name, 0.0) + sec
    want = [[n, ns / 1e9] for n, ns in
            sorted(totals.items(), key=lambda kv: -kv[1])[:10]]
    assert reduce.label_gaps(gaps, host) == want


def test_idle_gaps_cover_what_busy_leaves(fixture):
    t0, t1 = reduce.window(fixture)
    ops = _ops(fixture)
    gaps = reduce.idle_gaps(ops, t0, t1)
    assert sum(e - s for s, e in gaps) == pytest.approx(
        (t1 - t0) - reduce.busy_ns(ops, t0, t1), rel=1e-9)
    assert all(t0 <= s < e <= t1 for s, e in gaps)
    assert all(a[1] <= b[0] for a, b in zip(gaps, gaps[1:]))


@pytest.mark.parametrize("metric", ["device.idle_share",
                                    "device.idle_share.closed"])
def test_idle_share_metric(ctx, fixture, metric):
    share = benchmod.load_module(
        os.path.join(benchmod.PACKAGE_DIR, "metrics", f"{metric}.py"),
        "idle").read(ctx)
    t0, t1 = reduce.window(fixture)
    flags, _ = _timeline(_ops(fixture), t0, t1)
    assert share == pytest.approx(100.0 * (1 - flags.mean()), abs=0.2)


def test_gaps_are_labelled_by_the_innermost_host_span(fixture):
    t0, t1 = reduce.window(fixture)
    clock = reduce.Clock(*fixture["clock"])
    host = [(s["name"], clock.ns(s["t0"]), clock.ns(s["t1"]))
            for s in fixture["spans"] if s["name"] not in run.WAIT_SPANS]
    gaps = reduce.idle_gaps(_ops(fixture), t0, t1)
    labelled = reduce.label_gaps(gaps, host)
    assert sum(sec for _, sec in labelled) == pytest.approx(
        sum(e - s for s, e in gaps) / 1e9)
    # the fixture lies inside one batch span, the only work span there
    assert [name for name, _ in labelled] == ["batch"]
    # a hand-made case: the later-opened span wins
    assert reduce.label_gaps([(5.0, 7.0)],
                             [("outer", 0.0, 10.0), ("inner", 4.0, 8.0)]) \
        == [["inner", 2e-9]]


@pytest.mark.parametrize("metric,wrappers", [
    ("fused_query_roofline", ("fused", "quantized")),
    ("quantized_query_roofline", ("quantized",)),
])
def test_kernel_calls_parse_shapes_from_event_names(ctx, fixture, metric,
                                                    wrappers):
    kernel = [e for e in _ops(fixture)
              if e[0].startswith(tuple(f"%_{w}_query_impl"
                                       for w in wrappers))]
    calls = fused_query.calls(_ops(fixture), wrappers)
    assert len(calls) == len(kernel) > 0
    # 8 padded rows x C=1024 candidates, N=64, survivor width 40, int8 codes
    # (and the float32 delta segment's calls)
    assert {c[:5] for c in calls} <= {(8, 1024, 64, 40, 1),
                                     (8, 1024, 64, 40, 4)}
    assert sum(c[5] for c in calls) == pytest.approx(
        sum(e[2] for e in kernel) / 1e9)
    share = benchmod.load_module(
        os.path.join(benchmod.PACKAGE_DIR, "metrics", f"{metric}.py"),
        "roof").read(ctx)
    assert 0 < share <= 100


def test_programs_per_batch_counts_module_starts(ctx, fixture):
    ppb = benchmod.load_module(
        os.path.join(benchmod.PACKAGE_DIR, "metrics",
                     "fanout.programs_per_batch.py"), "ppb")
    mods = reduce.device_lines(fixture, reduce.MODULES_LINE)["/device:TPU:0"]
    (b0, b1, rows), = ctx.batch_spans_ns()
    assert rows == 8
    want = sum(1 for _, s, _ in mods if b0 <= s < b1)
    assert ppb.read(ctx) == want > 0


def test_top_ops_sum_by_name(fixture):
    ops = _ops(fixture)
    top = reduce.top_ops(ops, top=3)
    assert len(top) == 3
    assert top[0][1] >= top[1][1] >= top[2][1]
    name = top[0][0]
    assert top[0][1] == pytest.approx(
        sum(d for n, _, d in ops if n == name) / 1e9)


def test_clock_maps_perf_counter_onto_the_trace(fixture):
    clock = reduce.Clock(*fixture["clock"])
    assert clock.ns(fixture["clock"][1]) == fixture["clock"][0]
    assert clock.ns(fixture["clock"][1] + 0.5) == pytest.approx(
        fixture["clock"][0] + 0.5e9)


def test_xplane_reader_finds_the_window_marker(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(xplane.MARKER):
        f(x).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    plain = xplane.read(xplane.find(str(tmp_path)))
    start, dur = plain["marker"]
    assert dur >= 0.01e9
    # the CPU has no device planes: nothing to reduce, and readers say so
    assert reduce.busy_s(plain) == 0.0
