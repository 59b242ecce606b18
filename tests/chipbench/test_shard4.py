"""The four-chip cell (``w2q-shard4-search-open``) rehearsed on the CPU, and
the guards that keep a cell from running unsharded.

The device count is fixed when JAX starts, so the whole runs go in one
subprocess on four virtual CPU devices
(``--xla_force_host_platform_device_count=4``, as
``tests/test_sharded_serve.py`` runs its multi-device cases), on a
``tinybench`` root: a sound traced run, its control, each fault the cell
can have under the timed path, and the cell with its mesh on more devices
than it has chips.  The guards of ``server.build_registry`` refuse before
any device is touched, so they run in this process."""

import json
import os
import subprocess
import sys
import textwrap

import pytest
import tinybench

from chipbench import server

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "w2q-shard4-search-open"
SEED = 2 ** 33 + 21

SCRIPT = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [REPO, HERE]
    import tinybench
    from chipbench import bench as benchmod
    from chipbench import control, faults, run
    from repro.core import distributed

    bench = benchmod.Benchmark(tinybench.make_root(sys.argv[1]))
    cell = bench.cell(CELL)
    out = {}

    def one(seed, trace, **kw):
        return run.run_cell(bench, cell, seed, 1.0, trace, require_tpu=False,
                            t_start=time.perf_counter(), **kw)

    seen = {}

    def inspect(spans, layout, **_):
        seen.update(spans=sorted({s["name"] for s in spans}), layout=layout)

    got = one(SEED, True, inspect=inspect)
    out["sound"] = dict(seen, correct=got["correct"], device=got["device"],
                        checks=got["checks"], metrics=got["metrics"])
    out["control"] = control.readings(bench, cell, SEED + 1, 1.0,
                                      require_tpu=False)
    for name in ("alter_answers", "half_batch", "drop_exchange"):
        undo = []

        def patch(owner, attr, value):
            undo.append((owner, attr, getattr(owner, attr)))
            setattr(owner, attr, value)

        faults.FAULTS[name](patch)
        try:
            got = one(SEED + 2, False)
        finally:
            for owner, attr, value in reversed(undo):
                setattr(owner, attr, value)
            distributed._sharded_segment_query_fn.cache_clear()
        out[name] = {"correct": got["correct"], "checks": got["checks"]}

    bench.workloads[CELL]["chips"] = 1
    try:
        run.run_cell(bench, bench.cell(CELL), SEED, 1.0, False,
                     require_tpu=False, t_start=time.perf_counter(),
                     inspect=lambda **_: out.update(reached_the_check=True))
    except RuntimeError as e:
        out["one_chip_refused"] = str(e)
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(tinybench.REPO, "src"))
    head = (f"REPO = {tinybench.REPO!r}\nHERE = {HERE!r}\n"
            f"CELL = {CELL!r}\nSEED = {SEED!r}\n")
    proc = subprocess.run(
        [sys.executable, "-c", head + SCRIPT,
         str(tmp_path_factory.mktemp("shard4"))],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_and_sharded(runs):
    sound = runs["sound"]
    assert sound["correct"], sound["checks"]
    assert sound["device"]["count"] == 4
    assert len(sound["device"]["memory_peak_bytes_per_device"]) == 4
    assert sound["layout"]["n_dev"] == 4
    assert sound["layout"]["n_sealed"] >= 4
    assert all(sound["layout"]["assignment"])     # every chip holds some
    assert "query.collective" in sound["spans"]
    assert "query.segments" not in sound["spans"]
    assert sound["metrics"]["fanout.collective_ms"]["value"] > 0
    assert sound["checks"]["compiled_in_window"]["value"] == 0


def test_control_fails_the_limit_the_program_passes(runs):
    got = runs["control"]
    assert got["correct"], got["checks"]
    limit = got["checks"]["dist_err"]["limit"]
    assert got["controls"]["bf16"]["correct"] is False
    assert got["controls"]["bf16"]["dist_err"] > limit


@pytest.mark.parametrize("fault,caught_by", [
    ("alter_answers", "dist_err"),
    ("half_batch", "dist_err"),
    ("drop_exchange", "readback_miss"),
])
def test_broken_path_is_not_correct(runs, fault, caught_by):
    assert runs[fault]["correct"] is False
    c = runs[fault]["checks"][caught_by]
    assert c["value"] > c["limit"]


def test_mesh_on_more_devices_than_chips_fails_before_the_window(runs):
    assert "reached_the_check" not in runs
    assert "4 devices" in runs["one_chip_refused"]


def _config(name):
    with open(os.path.join(tinybench.REPO, "chipbench", "configs",
                           f"{name}.json"), encoding="utf-8") as f:
        return json.load(f)


def test_a_configuration_without_mesh_serves_on_one_device(tmp_path):
    config = _config("w2-quantile-262k")
    assert "mesh" not in config
    registry, sv = server.build_registry(config, str(tmp_path / "wal"))
    assert registry._mesh is None
    assert sv.index.shard_layout() is None


@pytest.mark.parametrize("change,says", [
    (lambda c: c["mesh"].update(devices=2), "2 devices"),
    (lambda c: c["spec"].update(shard_axis="data"), "'data'"),
    (lambda c: c["spec"].pop("shard_axis"), "None"),
    (lambda c: c.pop("mesh"), "'serve'"),
])
def test_a_mesh_that_does_not_fit_the_cell_is_refused(tmp_path, change,
                                                     says):
    config = _config("w2-quantile-262k-shard4")
    change(config)
    with pytest.raises(RuntimeError, match=says):
        server.build_registry(config, str(tmp_path / "wal"), chips=4)
