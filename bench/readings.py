"""Readings that the comparison limits are set from.

    python3 bench/readings.py --workload ingest-distinct --seeds 1,2,3 \
        --seconds 5 --control high,bf16

For each seed, one process runs the cell's set-up, a window of
``--seconds`` through the timed path and the check, as a run does, then
puts the reference computed at each ``--control`` precision in the
program's place and checks that too. Prints one JSON line per seed with
both sets of compared numbers and how many frames each failed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", default="high")
    args = ap.parse_args(argv)
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    from bench import harness

    catalog = harness.Catalog(ROOT)
    harness.use_compile_cache(ROOT)
    cell = catalog.cell(args.workload)
    config = catalog.config(cell["config"])
    traffic = catalog.traffic(cell["traffic"])
    kind = catalog.load_kind(traffic["kind"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        drv = kind(config, traffic, seed)
        drv.setup(args.seconds)
        drv.window(args.seconds)
        drv.record()
        drv.release()
        attempted, failed, prog = drv.check()
        ctrl = {}
        for c in args.control.split(","):
            _, c_failed, found = drv.check(c)
            ctrl[c] = dict(found, failed=c_failed)
        print(json.dumps({"seed": seed, "attempted": attempted,
                          "failed": failed, "program": prog,
                          "control": ctrl}), flush=True)
        del drv
    return 0


if __name__ == "__main__":
    sys.exit(main())
