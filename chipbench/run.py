"""Run one benchmark cell on the accelerator this process finds.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process holds the chip and runs the server; a child process that never
imports JAX sends the cell's traffic over the wire:

1. set-up: the data (one jitted call from ``--seed``), the tenant's registry
   with its WAL, the bulk load, the wire front-end, warm-up of every chunk
   shape and write shape the traffic reaches, the child's connections;
2. the window: ``--seconds`` of traffic (``chipbench.loadgen``); with
   ``--trace 1`` the profiler records it;
3. after it: the held-out recall probe and the read-backs through the same
   wire entry, the peak memory of each chip the cell uses, then the
   program's state is dropped and the plain reference
   (``chipbench.reference``) judges every answer (``chipbench.check``).

The load generator gets a core of its own and the server's threads the
others, so the server's host work cannot hold back a send.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, ``load`` (how late the load generator sent, how often each
side was preempted, the sender's CPU time and page faults, how late the
server's ticker woke and its garbage collections; ``chipbench.host``),
and last ``checks``: each compared number with its limit, which also end
standard error.  With no TPU, or fewer chips than the cell asks for, it
exits 1 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

from . import bench as benchmod  # noqa: E402
from . import check as checkmod  # noqa: E402
from . import host as hostmod  # noqa: E402
from . import reference, server, wire  # noqa: E402
from .loadgen import child, schedule  # noqa: E402
from .trace import reduce, xplane  # noqa: E402

CACHE_DIR = ".jax_cache"          # persistent compile cache, in the checkout
KERNEL_PATHS = ("kernel_mode", "query_backend", "hash_backend")
CHILD_READY_S = 120.0
CHILD_DONE_S = 90.0
# spans that time a wait (queueing, a client's whole request), not work on
# the host: an idle gap is named by the work span open at the time
WAIT_SPANS = ("admission", "request")


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def say(*parts) -> None:
    print(f"[chipbench +{time.perf_counter() - T_START:.1f}s]", *parts,
          file=sys.stderr, flush=True)


def p95(values) -> float:
    """Nearest-rank 95th percentile."""
    v = sorted(values)
    return v[max(0, math.ceil(0.95 * len(v)) - 1)] if v else 0.0


def peaks_for(root: str, kind: str) -> dict:
    with open(os.path.join(root, "chipbench", "peaks.json"),
              encoding="utf-8") as f:
        devices = json.load(f)["devices"]
    if kind not in devices:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return devices[kind]


def make_rows(bench, config: dict, seed: int, sizes: dict) -> dict:
    """Every row a run uses, from the seed in one jitted call on the
    device, split into pools in a fixed order (items first, so the loaded
    data does not depend on the window's length)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    data = dict(config["data"])
    gen = bench.data_generator(data.pop("kind"))
    total = sum(sizes.values())
    words = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(2)
    key = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                   impl="threefry2x32")
    n_dims = config["spec"]["n_dims"]
    rows = np.asarray(jax.jit(
        lambda kk: gen.generate(kk, total, n_dims, data))(key))
    out, start = {}, 0
    for name, n in sizes.items():
        out[name] = rows[start:start + n]
        start += n
    return out


def split_cpus():
    """(server's cpus, load generator's cpu) from this process's cpus, or
    (None, None) where there is only one."""
    cpus = sorted(os.sched_getaffinity(0))
    if len(cpus) < 2:
        return None, None
    return set(cpus[:-1]), cpus[-1]


def pin_threads(cpus) -> None:
    """Every thread of this process onto ``cpus``; threads started later
    inherit it from theirs."""
    for tid in os.listdir("/proc/self/task"):
        try:
            os.sched_setaffinity(int(tid), cpus)
        except OSError:          # a thread that has ended meanwhile
            pass


def preempted() -> int:
    """Involuntary context switches of this process so far."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw


class Context:
    """What a per-layer metric reader (``chipbench/metrics``) may read."""

    def __init__(self, *, config, traffic, records, spans, plain, clock,
                 peaks, busy_s, window_s):
        self.config = config
        self.traffic = traffic
        self.records = records
        self.spans = spans
        self.plain = plain
        self.clock = clock
        self.peaks = peaks
        self.busy_s = busy_s
        self.window_s = window_s

    def batch_spans_ns(self):
        """(start_ns, end_ns, rows_padded) of each ``batch`` span, on the
        trace's clock."""
        if self.clock is None:
            return []
        return [(self.clock.ns(s["t0"]), self.clock.ns(s["t1"]),
                 int(s["attrs"]["rows_padded"]))
                for s in self.spans if s["name"] == "batch"]


def _query_all(conn, tenant, rows, per_request, k, n_probes, answers):
    """Ask ``rows`` through the wire, ``per_request`` rows at a time;
    returns the served gids (n, k), adding every answer to ``answers``."""
    import numpy as np

    out = []
    for s in range(0, rows.shape[0], per_request):
        part = rows[s:s + per_request]
        sent = time.perf_counter()
        got = wire.answer_arrays(conn.query(tenant, part, k, n_probes))
        if got is None:
            raise RuntimeError("a query after the window was refused")
        answers.add(part, got[0], got[1], sent)
        out.append(got[0])
    return np.concatenate(out) if out else np.zeros((0, k), np.int32)


def warm_inserts(config: dict, traffic: dict, window_rows: int) -> list:
    """Rows of each warm-up insert: one more live segment per insert, as
    many as the window's inserts will open, plus the one the window starts
    in; then one insert of a write's rows at an offset into the new delta,
    where every insert of the window lands.  The fan-out concatenates and
    merges one shard per live segment, so every segment count the window
    reaches is a shape to compile."""
    writes = traffic.get("writes")
    if not writes:
        return []
    cap = int(config["spec"]["segment_capacity"])
    w_rows = int(writes["rows"])
    seals = math.ceil((2 * w_rows + window_rows) / cap) - 1
    fill = int(config["items"]) % cap or cap
    sizes = []
    for _ in range(1 + seals):
        sizes.append(cap - fill + w_rows)
        fill = w_rows
    return sizes + [w_rows]


def warm_up(conn, tenant, warm_rows, traffic, spec, dance, loaded, perm,
            k, n_probes, ledger, answers) -> set:
    """Compile, before the window, every shape the window will run.

    Queries of each chunk shape the traffic reaches, at each live segment
    count the window will see: now, then after each insert of ``dance``
    (each seals the delta and opens one more segment).  Deletes of 1 to
    ``rows`` gids within one segment (the live-mask scatter is compiled
    per count).  Then the dance's rows are deleted, so the window starts
    with the loaded segments live and its first insert opens the next.
    Returns the gids deleted."""
    chunks = traffic["warm_chunks"]
    queries = warm_rows[:sum(chunks)]

    def ask_each_chunk():
        at = 0
        for chunk in chunks:
            _query_all(conn, tenant, queries[at:at + chunk], chunk, k,
                       n_probes, answers)
            at += chunk

    def ok(resp):
        if not resp.get("ok"):
            raise RuntimeError(f"warm-up write refused: {resp}")
        return resp

    ask_each_chunk()
    at, deleted, inserted = sum(chunks), set(), []
    for i, n in enumerate(dance):
        part = warm_rows[at:at + n]
        at += n
        gids = ok(conn.insert(tenant, part))["gids"]
        ledger.acknowledge(gids, part)
        inserted += gids
        if i < len(dance) - 1:       # the last one opens no segment
            ask_each_chunk()
    if dance:
        cap = int(spec["segment_capacity"])
        w_rows = int(traffic["writes"]["rows"])
        for n, seg in enumerate(perm[:w_rows] % (len(loaded) // cap),
                                start=1):
            gone = [int(g) for g in loaded[seg * cap:seg * cap + n]]
            ok(conn.delete(tenant, gone))
            ledger.delete(gone, time.perf_counter())
            deleted.update(gone)
        ok(conn.delete(tenant, inserted))
        ledger.delete(inserted, time.perf_counter())
        deleted.update(int(g) for g in inserted)
    return deleted


def run_cell(bench, cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, t_start: float = T_START,
             inspect=None) -> dict:
    """One run of ``cell``; returns the result object (``checks`` last).

    ``require_tpu=False`` lets the tests drive a run on the CPU;
    ``inspect(answers=, ledger=, config=, inputs=, spans=, layout=)``, when
    given, sees what the reference judged, the other inputs of
    ``check.check``, the program's spans of the window and the index's
    ``shard_layout`` (``chipbench.control`` reads its controls there)."""
    src = os.path.join(bench.root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)

    import jax
    import numpy as np

    if require_tpu:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(bench.root, CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                     f"JAX found {len(devices)} {dev.platform} device(s)")
    peaks = peaks_for(bench.root, dev.device_kind) if require_tpu else None

    from repro.kernels import dispatch
    from repro.obs import trace as obs_trace

    desc = dispatch.describe()
    say(f"jax {jax.__version__} devices={devices} "
        f"dispatch={json.dumps(desc, sort_keys=True)}")
    if require_tpu:
        off = [p for p in KERNEL_PATHS if desc[p] != "compiled"]
        if off:
            raise NoChip(f"kernel paths not compiled on the chip: {off}")
    tracer = obs_trace.tracer()
    saved = (tracer.sample_rate, tracer._ring.maxlen)
    obs_trace.configure(sample_rate=1.0 if trace else 0.0, buffer=1 << 18)
    compiles = server.CompileLog()

    config, traffic = cell.config, cell.traffic
    spec = config["spec"]
    tenant, p = spec["name"], float(spec["p"])
    k, n_probes = config["query"]["k"], config["query"]["n_probes"]
    writes = traffic.get("writes")
    w_rows = int(writes["rows"]) if writes else 0
    pools = schedule.pool_rows(traffic, seconds)
    dance = warm_inserts(config, traffic, pools["inserts"])
    sizes = {"items": int(config["items"]),
             "warm": sum(traffic["warm_chunks"]) + sum(dance),
             "queries": pools["queries"], "inserts": pools["inserts"],
             "probe": int(traffic["probe_rows"])}
    rows = make_rows(bench, config, seed, sizes)
    say(f"data rows={sum(sizes.values())} {compiles.line()}")

    workdir = tempfile.mkdtemp(prefix="chipbench_")
    proc = None
    all_cpus = os.sched_getaffinity(0)
    try:
        registry, sv = server.build_registry(
            config, os.path.join(workdir, "wal"), cell.chips)
        loaded = server.bulk_load(sv, rows["items"],
                                  int(config["load_rows_per_call"]), say)
        layout = sv.index.shard_layout()
        if layout is not None:
            say(f"serve mesh n_dev={layout['n_dev']} "
                f"per_dev={layout['per_dev']} n_sealed={layout['n_sealed']}")
        ledger = checkmod.Ledger()
        ledger.acknowledge(loaded, rows["items"])
        answers = checkmod.Answers()
        fe = server.BackgroundFrontend(registry)
        conn = wire.Connection(fe.host, fe.port)

        perm = schedule.rng(seed, 20).permutation(len(loaded))
        warm_deleted = warm_up(conn, tenant, rows["warm"], traffic, spec,
                               dance, loaded, perm, k, n_probes, ledger,
                               answers)
        say(f"warmed chunks={traffic['warm_chunks']} inserts={dance} "
            f"{compiles.line()}")

        victims = np.asarray([g for g in loaded[perm]
                              if int(g) not in warm_deleted], np.int64)
        plan = schedule.build(traffic, seconds, seed, victims)
        server_cpus, child_cpu = split_cpus()
        plan.update(host=fe.host, port=fe.port, tenant=tenant, k=k,
                    n_probes=n_probes, cpu=child_cpu)
        with open(os.path.join(workdir, "plan.json"), "w",
                  encoding="utf-8") as f:
            json.dump(plan, f)
        np.save(os.path.join(workdir, "queries.npy"), rows["queries"])
        np.save(os.path.join(workdir, "inserts.npy"), rows["inserts"])
        proc = subprocess.Popen(
            [sys.executable, "-m", "chipbench.loadgen.child", workdir],
            cwd=bench.root, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True)

        lines = _Lines(proc.stdout)

        def child_says(want: str, timeout_s: float) -> None:
            line = lines.next(timeout_s)
            if line != want:
                raise RuntimeError(f"load generator said {line!r}, "
                                   f"expected {want!r}")

        child_says("ready", CHILD_READY_S)
        if server_cpus:
            pin_threads(server_cpus)
        before = compiles.programs
        log_dir = os.path.join(workdir, "profile")
        if trace:
            jax.profiler.start_trace(log_dir)
        marker = (jax.profiler.TraceAnnotation(xplane.MARKER) if trace
                  else contextlib.nullcontext())
        with marker:
            t_marker = time.perf_counter()
            proc.stdin.write("go\n")
            proc.stdin.flush()
            t_window0 = time.perf_counter()
            switches0 = preempted()
            with hostmod.Watch() as watch:
                child_says("closed", seconds + 60.0)
            t_window1 = time.perf_counter()
            server_switches = preempted() - switches0
        if trace:
            jax.profiler.stop_trace()
        in_window = compiles.programs - before
        say(f"window seconds={t_window1 - t_window0:.3f} "
            f"programs_compiled_in_window={in_window} "
            f"{compiles.names[before:before + in_window]} {compiles.line()}")
        child_says("done", CHILD_DONE_S + seconds)
        proc.wait(timeout=30)
        with open(os.path.join(workdir, "results.json"),
                  encoding="utf-8") as f:
            results = json.load(f)
        records = results["records"]

        lost = 0
        for r in records:
            if str(r.get("code", "")).startswith("lost"):
                lost += 1
            if not r["ok"]:
                continue
            a, b = r.get("rows", (0, 0))
            if r["kind"] == "insert":
                ledger.acknowledge(r["ack_gids"], rows["inserts"][a:b])
            elif r["kind"] == "delete":
                ledger.delete(r["gids"], r["recv"])
            else:
                answers.add(rows["queries"][a:b], r["gids"], r["dists"],
                            r["send"])

        # after the window, through the same entry: the recall probe and
        # the read-backs of sampled acknowledged writes (and of loaded
        # items, where the traffic asks)
        probe_gids = _query_all(conn, tenant, rows["probe"],
                                int(traffic["probe_request_rows"]), k,
                                n_probes, answers)
        pick = schedule.rng(seed, 30)
        n_check = int(traffic.get("check_rows", 0))
        n_check_del = int(traffic.get("check_deletes", 0))
        readback_miss = deleted_served = 0
        ins = [(g, rows["inserts"][r["rows"][0] + i])
               for r in records if r["kind"] == "insert" and r["ok"]
               for i, g in enumerate(r["ack_gids"])]
        if ins and n_check:
            sel = pick.choice(len(ins), min(n_check, len(ins)),
                              replace=False)
            want = np.asarray([ins[i][0] for i in sel])
            got = _query_all(conn, tenant,
                             np.stack([ins[i][1] for i in sel]), w_rows, k,
                             n_probes, answers)
            dists = np.concatenate(answers.dists)[-len(sel):]
            readback_miss = int(((got[:, 0] != want)
                                 | (dists[:, 0] != 0)).sum())
        dels = [int(g) for r in records if r["kind"] == "delete" and r["ok"]
                for g in r["gids"]]
        if dels and n_check_del:
            sel = pick.choice(len(dels), min(n_check_del, len(dels)),
                              replace=False)
            gone = np.asarray([dels[i] for i in sel])
            got = _query_all(conn, tenant, ledger.rows_of(gone), w_rows, k,
                             n_probes, answers)
            deleted_served = int((got == gone[:, None]).any(axis=1).sum())
        n_check_loaded = int(traffic.get("check_loaded", 0))
        if n_check_loaded:
            live = loaded[~np.isin(loaded, list(ledger.deleted_at))]
            want = live[pick.choice(len(live), min(n_check_loaded,
                                                   len(live)),
                                    replace=False)]
            got = _query_all(conn, tenant, ledger.rows_of(want),
                             int(traffic["probe_request_rows"]), k,
                             n_probes, answers)
            dists = np.concatenate(answers.dists)[-len(want):]
            readback_miss += int(((got[:, 0] != want)
                                  | (dists[:, 0] != 0)).sum())
        # the peak of every chip the cell uses; the fullest is reported
        peaks_each = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                      for d in devices[:cell.chips]]
        peak = max((b for b in peaks_each if b is not None), default=None)
        spans = [s for s in obs_trace.tracer().spans()
                 if t_window0 <= s["t0"] <= t_window1]
        conn.close()
        fe.close()
        del fe, sv, registry
        gc.collect()

        # the reference judges, with the program's state gone
        live_rows, live_gids = ledger.live()
        exact = reference.brute_force_topk(rows["probe"], live_rows,
                                           live_gids, k, p)
        recall = float(np.mean([np.isin(e, g).mean()
                                for e, g in zip(exact, probe_gids)]))
        inputs = dict(p=p, lost=lost, readback_miss=readback_miss,
                      deleted_served=deleted_served,
                      compiled_in_window=in_window, limits=config["limits"])
        checks = checkmod.check(answers, ledger, **inputs)

        attempted = len(records)
        failed = sum(1 for r in records if not r["ok"])
        t_close = results["t0"] + seconds
        # a failed or lost request counts as the longest wait the load
        # generator can see: the window and its drain
        missing_ms = (seconds + child.DRAIN_S) * 1e3
        q_lat, w_lat, q_rows = [], [], 0
        for r in records:
            t_from = r["due"] if r.get("due") is not None else r["send"]
            lat = (r["recv"] - t_from) * 1e3 if r["ok"] else missing_ms
            (q_lat if r["kind"] == "query" else w_lat).append(lat)
            if r["kind"] == "query" and r["ok"] and r["recv"] <= t_close:
                q_rows += r["rows"][1] - r["rows"][0]
        e2e = {"query_p95_ms": (p95(q_lat), "ms"),
               "query_rows_per_s": (q_rows / seconds, "rows/s"),
               "write_p95_ms": (p95(w_lat), "ms"),
               "recall_at_10": (recall, "fraction"),
               "setup_s": (t_window0 - t_start, "s")}
        say(f"queries={len(q_lat)} writes={len(w_lat)} "
            f"query_p50_ms={sorted(q_lat)[len(q_lat) // 2] if q_lat else 0!r}"
            f" lost={lost} failed={failed} recall_at_10={recall!r}")

        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": peak,
                  "memory_peak_bytes_per_device": peaks_each}
        out = {"correct": all(c["ok"] for c in checks.values()),
               "attempted": attempted, "failed": failed}
        if not trace:
            out["metrics"] = {m.name: {"value": e2e[m.name][0],
                                       "unit": m.unit}
                              for m in cell.end_to_end}
        else:
            plain = xplane.read(xplane.find(log_dir))
            t0_ns, t1_ns = reduce.window(plain)
            window_s = (t1_ns - t0_ns) / 1e9
            busy = reduce.busy_s(plain)
            clock = reduce.Clock(t0_ns, t_marker)
            ctx = Context(config=config, traffic=traffic, records=records,
                          spans=spans, plain=plain, clock=clock, peaks=peaks,
                          busy_s=busy, window_s=window_s)
            readers = bench.metric_readers(cell)
            out["metrics"] = {}
            for m in cell.per_layer:
                value = readers[m.name].read(ctx)
                if value is not None:
                    out["metrics"][m.name] = {"value": value, "unit": m.unit}
            device.update(busy_s=busy, window_s=window_s)
            ops = [ev for evs in reduce.device_lines(
                plain, reduce.OPS_LINE).values() for ev in evs]
            host = [(s["name"], clock.ns(s["t0"]), clock.ns(s["t1"]))
                    for s in spans if s["name"] not in WAIT_SPANS]
            out["breakdown"] = {
                "device_ops": reduce.top_ops(ops),
                "idle_gaps": reduce.label_gaps(
                    reduce.idle_gaps(ops, t0_ns, t1_ns), host)}
            say("trace events " + " ".join(
                f"{key}={len(evs)}" for key, evs in plain["lines"].items()))
        out["device"] = device
        late = results["lateness"]
        out["load"] = {"late_p50_ms": late["p50_ms"],
                       "late_p99_ms": late["p99_ms"],
                       "late_max_ms": late["max_ms"],
                       "late_put_max_ms": late.get("put_max_ms"),
                       "sender_preempted": results["preempted"],
                       "server_preempted": server_switches,
                       "sender_cpu_ms": results.get("cpu_ms"),
                       "sender_faults": results.get("faults"),
                       **watch.summary()}
        say("load " + json.dumps(out["load"]))
        out["checks"] = {name: {"value": c["value"], "limit": c["limit"]}
                         for name, c in checks.items()}
        if inspect is not None:
            inspect(answers=answers, ledger=ledger, config=config,
                    inputs=inputs, spans=spans, layout=layout)
        for name, c in checks.items():
            say(f"check {name}={c['value']!r} limit={c['limit']!r} "
                f"{'ok' if c['ok'] else 'FAILED'}")
        return out
    finally:
        pin_threads(all_cpus)
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        shutil.rmtree(workdir, ignore_errors=True)
        compiles.close()
        obs_trace.configure(sample_rate=saved[0], buffer=saved[1])


class _Lines:
    """The child's standard output, line by line, read on a thread so a
    wait can time out."""

    def __init__(self, stream):
        self._q: "queue.Queue" = queue.Queue()
        self._t = threading.Thread(target=self._pump, args=(stream,),
                                   daemon=True)
        self._t.start()

    def _pump(self, stream) -> None:
        for line in stream:
            self._q.put(line.strip())
        self._q.put(None)

    def next(self, timeout_s: float):
        try:
            return self._q.get(timeout=timeout_s)
        except queue.Empty:
            raise TimeoutError(f"load generator silent for {timeout_s} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = benchmod.Benchmark()
    try:
        cell = bench.cell(args.workload)
        out = run_cell(bench, cell, args.seed, args.seconds,
                       bool(args.trace))
    except (NoChip, FileNotFoundError, KeyError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out, allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
