"""Live-traffic acceptance harness for the network serving front-end.

The server runs as a **real subprocess** (``launch/serve --listen``) and
is driven by concurrent :class:`FrontendClient` connections -- genuine
wall-clock deadlines, genuine sockets, genuine signals:

* concurrent multi-tenant traffic across all three paper tenants (basis,
  QMC, Wasserstein) is answered **bit-identically** to direct library
  queries against an in-process registry built from the same
  ``default_specs`` and the same insert order (invariant 9: the network
  layer is invisible);
* under overload (tiny quotas, many clients) the server answers with
  explicit backpressure -- nonzero structured rejects carrying
  ``retry_after_ms``, queue depth bounded by admission -- instead of
  queueing unboundedly;
* SIGTERM drains gracefully: every *accepted* request is answered before
  exit (no stream ever sees a dropped connection mid-request; the drain
  report shows ``settled == admitted``), new requests are refused with
  ``shutting_down``, and the process exits 0;
* tenant lifecycle over the wire: ``load`` a fourth tenant, serve it,
  ``unload`` it (drained, WAL-audited), after which it rejects as
  ``unknown_tenant``.

The server subprocess pins one CPU device; the comparison registry runs
in the pytest process on either CI matrix leg (tenants are unsharded, so
results are device-count independent).
"""

import dataclasses
import os
import re
import signal
import subprocess
import sys
import threading
import time

import numpy as np

from repro.launch.serve import default_specs
from repro.serve import ServableRegistry
from repro.serve.client import FrontendClient, wait_ready

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOST = "127.0.0.1"
N_DIMS = 16
SEG_CAP = 256
TENANTS = ("l1-qmc", "l2-basis", "w2-quantile")


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    # the launcher turns the persistent compile cache on; tests keep it off
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return env


class _Server:
    """One ``launch/serve --listen`` subprocess, port parsed from stdout."""

    def __init__(self, *extra, timeout_s=120):
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.serve",
             "--listen", f"{HOST}:0", "--n-dims", str(N_DIMS),
             "--segment-capacity", str(SEG_CAP), *extra],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=_env())
        self.lines = []
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()
        self.port = self._wait_port(timeout_s)
        wait_ready(HOST, self.port, timeout_s=timeout_s)

    def _read(self):
        for line in self.proc.stdout:
            self.lines.append(line.rstrip("\n"))

    def _wait_port(self, timeout_s):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            for ln in list(self.lines):
                m = re.search(r"listening on [\d.]+:(\d+)", ln)
                if m:
                    return int(m.group(1))
            if self.proc.poll() is not None:
                raise RuntimeError("server died during startup:\n"
                                   + self.proc.stderr.read())
            time.sleep(0.05)
        raise TimeoutError("no '[frontend] listening on' line in "
                           f"{timeout_s}s; got {self.lines}")

    def client(self, timeout_s=60.0) -> FrontendClient:
        return FrontendClient(HOST, self.port, timeout_s=timeout_s)

    def stop(self, timeout_s=60) -> int:
        """SIGTERM (if still alive) + wait; returns the exit code."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(timeout=10)
            raise
        self._reader.join(timeout=5)
        return rc


def _corpora(seed=7, n=48):
    rng = np.random.default_rng(seed)
    return {t: rng.normal(size=(n, N_DIMS)).astype(np.float32)
            for t in TENANTS}


def test_live_multitenant_parity_and_lifecycle():
    srv = _Server()
    try:
        corpora = _corpora()
        # sequential inserts per tenant (one client) -> deterministic gid
        # order, the precondition for bitwise parity with the direct build
        with srv.client() as c:
            gids = {t: c.insert(t, corpora[t]) for t in TENANTS}
        for t in TENANTS:
            assert gids[t].tolist() == list(range(48))

        # concurrent query phase: two client threads per tenant, mixed
        # batch sizes, so the batcher coalesces across connections
        qrng = np.random.default_rng(11)
        slices = ([0, 1, 2], [5, 6, 7, 8, 9], list(range(17, 25)))
        qsets = {t: [corpora[t][s] + qrng.normal(
                        scale=0.05, size=(len(s), N_DIMS)).astype(np.float32)
                     for s in slices] for t in TENANTS}
        results, errors = {}, []

        def run(tenant, worker):
            try:
                with srv.client() as c:
                    for qi, q in enumerate(qsets[tenant]):
                        results[(tenant, worker, qi)] = c.query_arrays(
                            tenant, q, k=5, n_probes=2)
            except Exception as e:           # noqa: BLE001
                errors.append(repr(e))

        threads = [threading.Thread(target=run, args=(t, w))
                   for t in TENANTS for w in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert not errors, errors
        assert len(results) == len(TENANTS) * 2 * len(slices)

        # invariant 9: wire answers == direct library answers, bitwise.
        # Same specs, same arrays, same insert order -> same index state.
        reg = ServableRegistry()
        for spec in default_specs(n_dims=N_DIMS, segment_capacity=SEG_CAP):
            reg.register(spec)
        for t in TENANTS:
            assert reg.get(t).insert(corpora[t]).tolist() == \
                gids[t].tolist()
        for (tenant, _w, qi), (ids, dists) in results.items():
            want_i, want_d = reg.get(tenant).index.query(
                qsets[tenant][qi], 5, n_probes=2)
            assert (np.asarray(want_i) == ids).all(), (tenant, qi)
            assert (np.asarray(want_d, np.float32) == dists).all(), \
                (tenant, qi)

        # health + stats endpoints surface lifecycle state, ServingStats
        # and the obs metrics catalog over the wire
        with srv.client() as c:
            h = c.health()
            assert set(h["tenants"]) == set(TENANTS)
            assert all(v["state"] == "ready"
                       for v in h["tenants"].values())
            assert h["draining"] is False
            assert h["totals"]["admitted"] >= len(results)
            st = c.stats()
            assert "frontend_requests_total" in st["catalog"]
            assert "serve_queries_total" in st["catalog"]
            for t in TENANTS:
                assert "qps" in st["report"][t]["stats"]
            assert any(k.startswith("frontend_requests_total")
                       for k in st["metrics"])

            # tenant lifecycle over the wire: load -> serve -> unload
            extra_spec = dataclasses.asdict(dataclasses.replace(
                default_specs(n_dims=N_DIMS,
                              segment_capacity=SEG_CAP)[0], name="extra"))
            assert c.load(extra_spec)["state"] == "ready"
            assert c.health()["tenants"]["extra"]["state"] == "ready"
            c.insert("extra", corpora["l2-basis"][:8])
            ids, _ = c.query_arrays("extra", corpora["l2-basis"][:3], k=2)
            assert ids.shape == (3, 2)
            r = c.unload("extra")
            assert r["state"] == "unloaded" and r["drained"] is True
            resp = c.query("extra", corpora["l2-basis"][:3], k=2)
            assert resp["ok"] is False
            assert resp["code"] == "unknown_tenant"
            assert "extra" not in c.health()["tenants"]
    finally:
        assert srv.stop() == 0


def test_backpressure_under_overload():
    """Tiny quotas + many concurrent clients -> nonzero structured
    rejects with retry_after_ms, bounded admission, and valid answers for
    everything accepted."""
    srv = _Server("--max-inflight", "4", "--queue-depth", "2",
                  "--max-delay-ms", "40")
    try:
        corpus = np.random.default_rng(0).normal(
            size=(64, N_DIMS)).astype(np.float32)
        with srv.client() as c:
            c.insert("l2-basis", corpus)
            c.query_arrays("l2-basis", corpus[:8], k=3)   # warm the jit

        oks, rejects = [], []
        lock = threading.Lock()

        def blast(seed):
            rng = np.random.default_rng(seed)
            with srv.client() as c:
                for _ in range(8):
                    rows = corpus[rng.integers(0, 56, size=8)]
                    r = c.query("l2-basis", rows, k=3)
                    with lock:
                        (oks if r.get("ok") else rejects).append(r)

        threads = [threading.Thread(target=blast, args=(s,))
                   for s in range(12)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)

        assert rejects, "overload must produce nonzero rejects"
        assert {r["code"] for r in rejects} <= {"overloaded", "queue_full"}
        # explicit backpressure: every retryable reject says when to retry
        assert all(r.get("retry_after_ms", 0) > 0 for r in rejects)
        for r in oks:
            assert len(r["gids"]) == 8 and len(r["gids"][0]) == 3
        with srv.client() as c:
            h = c.health()
            # everything settled after the storm; the quota held
            assert h["tenants"]["l2-basis"]["inflight"] == 0
            assert h["tenants"]["l2-basis"]["queue_depth"] == 0
            st = c.stats()
            wire_rejects = sum(
                v for k, v in st["metrics"].items()
                if k.startswith("frontend_rejects_total")
                and "l2-basis" in k)
            assert wire_rejects == len(rejects)
    finally:
        assert srv.stop() == 0


def test_sigterm_graceful_drain_loses_no_accepted_request():
    """Continuous multi-tenant streams + SIGTERM mid-flight: every stream
    sees clean answers up to exactly one ``shutting_down`` reject, never
    a dropped connection; the drain report proves settled == admitted."""
    srv = _Server("--max-delay-ms", "10")
    try:
        corpora = _corpora(seed=3, n=32)
        with srv.client() as c:
            for t in TENANTS:
                c.insert(t, corpora[t])
                c.query_arrays(t, corpora[t][:4], k=3)    # warm the jit

        lock = threading.Lock()
        stats = {"ok": 0, "drain_rejects": 0}
        errors = []

        def stream(tenant, seed):
            rng = np.random.default_rng(seed)
            try:
                with srv.client() as c:
                    while True:
                        q = corpora[tenant][rng.integers(0, 32, size=4)]
                        r = c.query(tenant, q, k=3)
                        if r.get("ok"):
                            assert len(r["gids"]) == 4
                            with lock:
                                stats["ok"] += 1
                        else:
                            # the drain signal: structured reject, then
                            # the client hangs up -- never a dead socket
                            assert r["code"] == "shutting_down", r
                            with lock:
                                stats["drain_rejects"] += 1
                            return
            except Exception as e:           # noqa: BLE001
                errors.append(f"{tenant}: {e!r}")

        threads = [threading.Thread(target=stream, args=(t, 100 + i))
                   for i, t in enumerate(TENANTS) for _ in range(2)]
        for th in threads:
            th.start()
        time.sleep(1.0)                      # let traffic flow
        srv.proc.send_signal(signal.SIGTERM)
        for th in threads:
            th.join(timeout=60)

        rc = srv.stop()
        assert rc == 0
        assert not errors, errors
        assert stats["ok"] > 0
        assert stats["drain_rejects"] == len(threads)
        drained = [ln for ln in srv.lines if "drained:" in ln]
        assert drained, srv.lines
        m = re.search(r"admitted=(\d+) settled=(\d+) rejected=(\d+) "
                      r"inflight=(\d+)", drained[0])
        assert m is not None, drained[0]
        # the no-lost-request guarantee, from the server's own ledger
        assert m.group(1) == m.group(2)
        assert m.group(4) == "0"
    finally:
        if srv.proc.poll() is None:
            srv.proc.kill()
