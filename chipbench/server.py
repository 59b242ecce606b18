"""The system under test, as the benchmark runs it in its own process.

The chip belongs to one process, so the server runs here: the tenant's
``ServableRegistry`` (write-ahead log on, over a serve mesh where the
configuration states one), loaded in bulk through ``Servable.insert``,
served by ``repro.serve.frontend.Frontend`` on an asyncio loop in a daemon
thread.  ``CompileLog`` and ``BackgroundFrontend`` are copies of the same
pieces of ``chip_smoke.py``.
"""

from __future__ import annotations

import asyncio
import threading
import time


class CompileLog:
    """Seconds XLA spent compiling (or fetching from the persistent cache)
    and the cache's hits and misses, from JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        self.names = []          # fun_name of each program compiled
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1
            self.names.append(fun_name)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def close(self) -> None:
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)

    def line(self) -> str:
        return (f"compile_s={self.seconds:.3f} programs={self.programs} "
                f"cache_hits={self.hits} cache_misses={self.misses}")


class BackgroundFrontend:
    """``serve.frontend.Frontend`` on an asyncio loop in a daemon thread of
    this process."""

    def __init__(self, registry):
        from repro.serve.frontend import Frontend
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True, name="frontend-loop")
        self.thread.start()
        self.frontend = Frontend(registry, drain_timeout_s=60.0)
        self.host, self.port = self._run(
            self.frontend.start("127.0.0.1", 0))

    def _run(self, coro, timeout_s: float = 600.0):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(
            timeout_s)

    def close(self) -> None:
        try:
            self._run(self.frontend.shutdown())
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(timeout=60)
            self.loop.close()


def build_registry(config: dict, wal_dir: str, chips: int = 1):
    """The configuration's one tenant, registered with its WAL.

    A configuration with ``"mesh": {"axis": ..., "devices": n}`` serves the
    tenant SPMD over a serve mesh of ``n`` devices, as ``launch.serve
    --shard n`` does (``launch.mesh.make_serve_mesh``); the run is refused
    unless ``n`` is the cell's ``chips``, the spec shards over that axis,
    and the registered index is laid out over all ``n`` devices.  Without
    ``mesh`` the tenant stays on one device."""
    from repro.serve import ServableRegistry, ServableSpec

    spec = dict(config["spec"])
    spec["chunk_sizes"] = tuple(spec["chunk_sizes"])
    axis = config["mesh"]["axis"] if "mesh" in config else None
    if spec.get("shard_axis") != axis:
        raise RuntimeError(f"the spec shards over {spec.get('shard_axis')!r},"
                           f" the configuration's mesh axis is {axis!r}")
    mesh = None
    if axis is not None:
        from repro.launch.mesh import make_serve_mesh

        n_dev = int(config["mesh"]["devices"])
        if n_dev != chips:
            raise RuntimeError(f"the configuration's mesh has {n_dev} "
                               f"devices, the cell runs on {chips} chip(s)")
        mesh = make_serve_mesh(n_dev, axis)
    registry = ServableRegistry(
        mesh=mesh, wal_dir=wal_dir,
        fsync_every=config["guarantees"]["fsync_every"])
    sv = registry.register(ServableSpec(**spec))
    if sv.spec.precision != config["precision"]:
        raise RuntimeError(f"tenant serves {sv.spec.precision}, the "
                           f"configuration states {config['precision']}")
    if mesh is not None:
        layout = sv.index.shard_layout()
        if layout is None or layout["n_dev"] != n_dev:
            raise RuntimeError(f"the tenant is not sharded over {n_dev} "
                               f"devices: layout {layout}")
    return registry, sv


def bulk_load(sv, rows, per_call: int, say) -> "tuple":
    """Insert ``rows`` in calls of ``per_call`` rows; returns their gids."""
    import numpy as np

    t0 = time.perf_counter()
    gids = [sv.insert(rows[s:s + per_call])
            for s in range(0, rows.shape[0], per_call)]
    occ = sv.index.occupancy()
    say(f"loaded items={sum(s['n_live'] for s in occ)} "
        f"segments={len(occ)} sealed={sum(1 for s in occ if s['sealed'])} "
        f"seconds={time.perf_counter() - t0:.3f}")
    return np.concatenate(gids)
