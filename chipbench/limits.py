"""Read what decides ``correct`` on many seeds in one process.

    python3 chipbench/limits.py --workload <cell> --seeds 1,2,3 --seconds 6 [--control]

Set-up is most of a run, so the readings a limit is set from (the program's
dozen seeds, the control's three or more) share one server: for each seed a
short window of the cell's own traffic at the cell's own load, then the
harness's own comparison and verdict (``check.compare``, ``check.verdict``)
over the sample.  ``--control`` switches on the configuration's
lower-precision path, which has to come out not correct.  Prints one JSON
line per seed with ``correct`` and each number beside its limit; never part
of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from chipbench import check, run  # noqa: E402
from chipbench.files import Cell, load_module  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    cfg = cell.config
    _, served = run.bring_up(cell, "tpu", args.control)
    try:
        reference = load_module("references", cfg["reference"]).Reference(cfg)
        for seed in (int(s) for s in args.seeds.split(",")):
            load = run.drive_window(cell, served, seed, args.seconds, False,
                                    time.time())
            records, never = load["records"], int(load["never"][0])
            attempted = len(records) + never
            failed = int((records[:, 3] == 0).sum()) + never
            compared = check.compare(cfg, cell.traffic, seed, load["sample"],
                                     load["answers"], reference)
            print(json.dumps({
                "workload": args.workload, "control": args.control,
                "seed": seed, "attempted": attempted, "failed": failed,
                "sampled": len(load["sample"]),
                "correct": check.verdict(compared, attempted, failed, never),
                "compared": compared}), flush=True)
    finally:
        served.stop()
        shutil.rmtree(run.SCRATCH, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
