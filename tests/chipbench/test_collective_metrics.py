"""The four-chip cell's fan-out metrics (``fanout.collective_ms`` from the
``query.collective`` span, ``collective.all_gather_ms`` from the device
trace), checked on synthetic spans and a synthetic two-plane trace against
hand counts, and silent where there is nothing to read."""

import os

import pytest

from chipbench import bench as benchmod
from chipbench import run
from chipbench.trace import reduce

T_PERF0 = 100.0            # perf_counter seconds at the window's start
NS0 = 5e9                  # the same instant on the trace's clock
GATHER_G = ("%all-gather = s32[32,10]{1,0:T(8,128)S(1)} "
            "all-gather(%fusion.1304), channel_id=2")
GATHER_D = ("%all-gather.1 = f32[32,10]{1,0:T(8,128)S(1)} "
            "all-gather(%copy.10667), channel_id=3")
KERNEL = "%_fused_query_impl.6 = (f32[8,1,10]{2,1,0}) custom-call()"


def _reader(name):
    return benchmod.load_module(
        os.path.join(benchmod.PACKAGE_DIR, "metrics", f"{name}.py"),
        f"collective.{name}")


def _span(sid, name, t0, t1, parent=None, **attrs):
    return {"trace_id": "t", "span_id": sid, "parent_id": parent,
            "name": name, "t0": T_PERF0 + t0, "t1": T_PERF0 + t1,
            "thread": 1, "attrs": attrs}


def _spans():
    """Three batches (seconds from the window's start) whose sharded
    program calls take 20 ms; 50 ms; 10 + 30 ms (two calls, which a
    reader must sum); and a call outside any batch, which counts for
    none."""
    return [
        _span(1, "batch", 0.0, 1.0, rows_padded=8),
        _span(2, "index.lock_wait", 0.0, 0.001, parent=1, op="query"),
        _span(3, "query.collective", 0.001, 0.021, parent=1, devices=4),
        _span(10, "batch", 2.0, 3.0, rows_padded=8),
        _span(11, "query.collective", 2.0, 2.05, parent=10, devices=4),
        _span(20, "batch", 4.0, 5.0, rows_padded=32),
        _span(21, "query.collective", 4.0, 4.01, parent=20, devices=4),
        _span(22, "query.collective", 4.1, 4.13, parent=20, devices=4),
        _span(30, "query.collective", 5.5, 5.9, devices=4),
    ]


def _ev(name, a_s, ms):
    return [name, NS0 + a_s * 1e9, ms * 1e6]


def _plain():
    """Two chips over a 6 s window.  In batch 1 chip 0 gathers for
    0.2 + 0.1 ms and chip 1 for 0.4 + 0.3 ms; in batch 2, 0.5 and 0.7 ms;
    in batch 3, 1.0 and 2.0 ms; batch 4 is left without a gather; a gather
    at 5.5 s lies outside every batch.  Kernel events never count."""
    chip0 = [_ev(KERNEL, 0.01, 5.0), _ev(GATHER_G, 0.02, 0.2),
             _ev(GATHER_D, 0.021, 0.1), _ev(GATHER_G, 2.01, 0.5),
             _ev(KERNEL, 4.0, 3.0), _ev(GATHER_D, 4.2, 1.0),
             _ev(GATHER_G, 5.5, 9.0)]
    chip1 = [_ev(KERNEL, 0.01, 6.0), _ev(GATHER_G, 0.02, 0.4),
             _ev(GATHER_D, 0.021, 0.3), _ev(GATHER_G, 2.01, 0.7),
             _ev(GATHER_D, 4.2, 2.0), _ev(GATHER_G, 5.5, 9.0)]
    return {"marker": [NS0, 6e9],
            "lines": {"/device:TPU:0|XLA Ops": chip0,
                      "/device:TPU:1|XLA Ops": chip1,
                      "/device:TPU:0|XLA Modules": [],
                      "/device:TPU:1|XLA Modules": []}}


def _ctx(span_list, plain):
    return run.Context(config={}, traffic={}, records=[], spans=span_list,
                       plain=plain, clock=reduce.Clock(NS0, T_PERF0),
                       peaks=None,
                       busy_s=reduce.busy_s(plain) if plain else 0.0,
                       window_s=6.0)


def test_collective_ms_is_the_median_call_time_per_batch():
    # per batch: 20 ms, 50 ms, 10 + 30 ms; the call outside a batch
    # (400 ms) counts for none
    got = _reader("fanout.collective_ms").read(_ctx(_spans(), _plain()))
    assert got == pytest.approx(40.0)


def test_all_gather_ms_sums_per_batch_and_averages_the_chips():
    batches = _spans() + [_span(40, "batch", 5.0, 5.2, rows_padded=8)]
    got = _reader("collective.all_gather_ms").read(_ctx(batches, _plain()))
    # batch 1: (0.3 + 0.7) / 2 = 0.5 ms; batch 2: (0.5 + 0.7) / 2 = 0.6;
    # batch 3: (1.0 + 2.0) / 2 = 1.5; batch 4 has none and is left out
    assert got == pytest.approx(0.6)


def test_all_gather_ms_of_one_batch_on_one_chip():
    plain = _plain()
    del plain["lines"]["/device:TPU:1|XLA Ops"]
    got = _reader("collective.all_gather_ms").read(
        _ctx(_spans()[:3], plain))
    assert got == pytest.approx(0.3)


@pytest.mark.parametrize("metric", ["fanout.collective_ms",
                                    "collective.all_gather_ms"])
def test_silent_where_there_is_nothing_to_read(metric):
    """The one-chip cells: ``query.segments`` in place of
    ``query.collective``, and no all-gather on the device."""
    one_chip = [dict(s, name="query.segments")
                if s["name"] == "query.collective" else s
                for s in _spans()]
    plain = _plain()
    plain["lines"] = {key: [e for e in evs if e[0] == KERNEL]
                      for key, evs in plain["lines"].items()}
    reader = _reader(metric)
    assert reader.read(_ctx(one_chip, plain)) is None
    assert reader.read(_ctx([], None)) is None
