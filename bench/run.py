"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. It refuses to run unless JAX finds a TPU
with as many chips as the cell asks for. Set-up makes the frames, the
weights and the fitted thresholds from ``--seed`` and warms every program
shape; then the cell's load runs for ``--seconds``; then what the window
produced is compared with the plain reference. The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and
``checks`` last); the last lines of standard error give each compared
number beside its limit.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no system under test: {ROOT / 'src' / 'repro'} "
                    f"is missing")
    from bench import harness

    try:
        catalog = harness.Catalog(ROOT)
        cell = catalog.cell(args.workload)
    except harness.UnknownName as e:
        return fail(str(e))
    harness.use_compile_cache(ROOT)
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        return fail(f"JAX found no accelerator: {e}")
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU, but JAX found platform "
                    f"{devices[0].platform!r} ({len(devices)} device(s)); "
                    f"no result is printed off the chip")
    if len(devices) < cell["chips"]:
        return fail(f"cell {args.workload!r} needs {cell['chips']} chips, "
                    f"JAX found {len(devices)}")
    result = harness.run_cell(catalog, args.workload, args.seed,
                              args.seconds, bool(args.trace), t0=T0)
    harness.report_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
