#!/usr/bin/env python3
"""Read the lower-precision control of a cell on the chip, at the cell's
own size and load.

    python3 chipbench/tests/control_chip.py --workload <cell> --seed <n> \\
        --seconds <s> [--controls fp8,int8] [--out <dir>]

One run of the cell as ``chipbench/run.py`` makes it (same build, traffic,
window and sample of finished requests); after the window each named
control (default: the configuration's ``control``) is read at the served
positions and judged by the same comparison in the program's place.  The
last line is the run's result with ``controls`` beside ``checks``: the
program's ``correct`` should read true and each control's false.  Not a
benchmark run.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    spec = importlib.util.spec_from_file_location("chipbench_run",
                                                  BENCH_DIR / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--out", default="",
                    help="directory for per-request records, the served "
                         "gaps and the trace")
    args = ap.parse_args(argv)
    bench = run.load_bench()
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((run.ROOT / conf["file"]).read_text())
    controls = args.controls.split(",") if args.controls else [cfg["control"]]
    try:
        result = run.run_cell(args, controls=controls, out_dir=args.out)
    except run.NoChip as e:
        run.log(str(e))
        return 3
    for c, r in result["controls"].items():
        for key, v in r["checks"].items():
            run.log(f"control {c} {key} {v['value']:.6f} limit {v['limit']}"
                    f" correct {r['correct']}")
    for key, c in result["checks"].items():
        run.log(f"{key} {c['value']:.6f} limit {c['limit']}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
