"""Load generation: seeded schedules, lateness accounting, the child
process over a stand-in server, and refusal to run without a TPU."""

import json
import os
import socketserver
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from chipbench import bench as benchmod
from chipbench.loadgen import schedule

OPEN = {"mode": "open", "query": {"rate_per_s": 10.0, "rows": 1},
        "writes": {"rate_per_s": 8.0, "rows": 8, "ops": ["insert", "delete"]},
        "connections": 8}
BIG_SEED = 2 ** 40 + 12345


def test_same_seed_same_plan_other_seed_same_work():
    victims = np.arange(10_000)
    a = schedule.build(OPEN, 30.0, BIG_SEED, victims)
    b = schedule.build(OPEN, 30.0, BIG_SEED, victims)
    c = schedule.build(OPEN, 30.0, BIG_SEED + 1, victims)
    assert a == b
    assert [r["due"] for r in a["requests"]] != \
        [r["due"] for r in c["requests"]]
    for plan in (a, c):
        kinds = [r["kind"] for r in plan["requests"]]
        assert kinds.count("query") == 300
        assert kinds.count("insert") == kinds.count("delete") == 120
        dues = [r["due"] for r in plan["requests"]]
        assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 30.0
    # the same gaps between queries, in another order
    for kind in ("query",):
        ga, gc = (np.diff([0.0] + [r["due"] for r in plan["requests"]
                                   if r["kind"] == kind]) for plan in (a, c))
        assert sorted(ga) == pytest.approx(sorted(gc))
        assert list(ga) != pytest.approx(list(gc))
    pools = schedule.pool_rows(OPEN, 30.0)
    assert pools == {"queries": 300, "inserts": 960}


def test_deletes_take_distinct_victims_in_order():
    victims = np.arange(100, 2000)
    plan = schedule.build(OPEN, 30.0, 7, victims)
    gone = [g for r in plan["requests"] if r["kind"] == "delete"
            for g in r["gids"]]
    assert gone == list(range(100, 100 + 960))


def test_closed_plan_gives_each_client_its_own_rows():
    closed = {"mode": "closed", "clients": 8, "query": {"rows": 1},
              "pool_per_client": 256}
    plan = schedule.build(closed, 30.0, 1, np.arange(10))
    assert plan["clients"][0] == [0, 256] and plan["clients"][7] == \
        [1792, 2048]
    assert schedule.pool_rows(closed, 30.0)["queries"] == 2048


def test_lateness_is_send_minus_due():
    recs = [{"due": 1.0, "send": 1.0 + d / 1e3} for d in range(100)]
    recs.append({"due": None, "send": 5.0})          # closed-loop record
    late = schedule.lateness(recs)
    assert late["n"] == 100
    assert late["p50_ms"] == pytest.approx(50.0)
    assert late["p99_ms"] == pytest.approx(98.0)
    assert late["max_ms"] == pytest.approx(99.0)


class _Stub(socketserver.StreamRequestHandler):
    """Answers every frame after 20 ms, as a server would."""

    def handle(self):
        for line in self.rfile:
            msg = json.loads(line)
            time.sleep(0.02)
            if msg["op"] == "query":
                n = len(msg["queries"])
                resp = {"id": msg["id"], "ok": True, "gids": [[1]] * n,
                        "dists": [[0.5]] * n}
            elif msg["op"] == "insert":
                resp = {"id": msg["id"], "ok": True,
                        "gids": list(range(len(msg["embeddings"])))}
            else:
                resp = {"id": msg["id"], "ok": True,
                        "n_deleted": len(msg["gids"])}
            self.wfile.write(json.dumps(resp).encode() + b"\n")
            self.wfile.flush()


class _Server(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True


def test_child_times_each_request_from_when_it_was_due(tmp_path):
    server = _Server(("127.0.0.1", 0), _Stub)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        traffic = dict(OPEN, query={"rate_per_s": 20.0, "rows": 1})
        plan = schedule.build(traffic, 1.0, 3, np.arange(100))
        plan.update(host="127.0.0.1", port=server.server_address[1],
                    tenant="t", k=1, n_probes=1)
        (tmp_path / "plan.json").write_text(json.dumps(plan))
        np.save(tmp_path / "queries.npy", np.zeros((20, 4), np.float32))
        np.save(tmp_path / "inserts.npy", np.zeros((32, 4), np.float32))
        # the child must not import JAX: it would take the chip
        code = ("import sys; from chipbench.loadgen import child; "
                "rc = child.main([sys.argv[1]]); "
                "assert 'jax' not in sys.modules; sys.exit(rc)")
        proc = subprocess.run(
            [sys.executable, "-c", code, str(tmp_path)], input="go\n",
            capture_output=True, text=True, timeout=60,
            cwd=benchmod.ROOT)
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.split() == ["ready", "closed", "done"]
        assert "lateness" in proc.stderr
        res = json.loads((tmp_path / "results.json").read_text())
        recs = res["records"]
        assert len(recs) == 28 and all(r["ok"] for r in recs)
        for r in recs:
            assert r["send"] >= r["due"] - 1e-3
            assert r["recv"] - r["due"] >= 0.02
        assert res["lateness"]["n"] == 28
    finally:
        server.shutdown()
        server.server_close()


def _run_cli(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "w2q-stream-open", "--seed", str(BIG_SEED), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    proc = _run_cli(benchmod.ROOT, {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_a_run_with_only_the_benchmark_files_fails(tmp_path):
    import shutil
    shutil.copytree(os.path.join(benchmod.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(benchmod.ROOT, "BENCHMARK.json"), tmp_path)
    proc = _run_cli(str(tmp_path), {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
