#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: sound runs of a cell over
many seeds and runs of its configuration's control, in one process.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 --control-seeds 4,5,6

Each run is an ordinary run of the cell (set-up, a window of ``--seconds``,
the comparison with the reference); a control run replaces the knobs its
configuration's ``control`` names, which breaks one guarantee the
configuration states.  One JSON line per run gives the numbers compared.
The benchmark's own runs never run this.  Needs a TPU, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.run import _paths, load_cell, require_chips  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    _paths()
    cell = load_cell(args.workload)
    require_chips(int(cell["chips"]))
    from chipbench.drivers import stream

    control = cell["config_data"]["control"]["cluster"]
    runs = [(int(s), None) for s in args.seeds.split(",") if s] + [
        (int(s), control) for s in args.control_seeds.split(",") if s
    ]
    for seed, override in runs:
        rec = stream.run(
            cell, seed=seed, seconds=args.seconds, trace=False,
            t_start=time.perf_counter(), override=override,
        )
        print(json.dumps({
            "workload": args.workload,
            "seed": seed,
            "control": override is not None,
            "correct": rec["correct"],
            "passes": [[p["rows"], p["cut"]] for p in rec["passes"]],
            "checks": {k: v["value"] for k, v in rec["checks"].items()},
            "end_to_end": rec["end_to_end"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
