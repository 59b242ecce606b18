"""Read a JAX profiler trace into the plain form ``reduce`` works on.

The plain form is a dict::

    {"marker": [start_ns, dur_ns],           # the window's host annotation
     "lines": {"<plane>|<line>": [[name, start_ns, dur_ns], ...], ...}}

with every device event (planes named ``/device:...``) that overlaps the
marker, on the trace's own clock.  ``MARKER`` is the name of the
``jax.profiler.TraceAnnotation`` the harness opens around the window; its
start, read on the host's ``time.perf_counter`` as well, ties the
program's host spans to the trace's clock.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List

MARKER = "chipbench.window"


def find(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def read(path: str) -> dict:
    """The plain form of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    marker = None
    device: Dict[str, List[list]] = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARKER:
                        marker = [float(ev.start_ns), float(ev.duration_ns)]
            continue
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            device[f"{plane.name}|{line.name}"] = [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events]
    if marker is None:
        raise ValueError(f"no {MARKER!r} annotation in {path}")
    t0, t1 = marker[0], marker[0] + marker[1]
    lines = {key: [e for e in evs if e[1] < t1 and e[1] + e[2] > t0]
             for key, evs in device.items()}
    return {"marker": marker, "lines": lines}
