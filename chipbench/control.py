"""The readings a cell's limits are set from.

    python3 -m chipbench.control --workload <cell> --seeds 11,12,13 \
        --seconds 20 [--fault <name>]

For each seed, one whole run of the cell in this process (set-up, window,
read-backs), then one JSON line with:

* ``correct`` and ``checks``: the run's own compared numbers, as the
  benchmark's run reports them (a sound run gives the lower readings);
* ``controls``: for each control of the configuration's precision
  (``check.CONTROLS``), the same answers with their distances recomputed by
  the plain reference in that lower precision, put through the same
  ``check``: its ``correct`` (which has to be false) and ``dist_err``.

With ``--fault`` a fault of ``chipbench.faults`` is planted under the
timed path first, and ``checks`` gives what it reads.  The benchmark's own
runs never compute a control or plant a fault.  Like ``chipbench.run``, it
needs the cell's chips.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from . import bench as benchmod
from . import check as checkmod
from . import faults, run


def readings(bench, cell, seed: int, seconds: float, **kw) -> dict:
    """One run's compared numbers and its controls' readings."""
    controls = {}

    def inspect(answers, ledger, config, inputs, **_):
        for kind in checkmod.CONTROLS[config["precision"]]:
            low = checkmod.control_answers(answers, ledger, p=inputs["p"],
                                           kind=kind)
            got = checkmod.check(low, ledger, **inputs)
            controls[kind] = {
                "correct": all(c["ok"] for c in got.values()),
                "dist_err": got["dist_err"]["value"]}

    out = run.run_cell(bench, cell, seed, seconds, False, inspect=inspect,
                       t_start=time.perf_counter(), **kw)
    return {"seed": seed, "correct": out["correct"],
            "checks": out["checks"], "controls": controls}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, e.g. 11,12,13")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    bench = benchmod.Benchmark()
    cell = bench.cell(args.workload)
    if args.fault:
        sys.path.insert(0, os.path.join(bench.root, "src"))
        faults.FAULTS[args.fault](setattr)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            got = readings(bench, cell, seed, args.seconds)
        except run.NoChip as e:
            print(f"chipbench.control: {e}", file=sys.stderr)
            return 1
        print(json.dumps(dict(got, fault=args.fault)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
