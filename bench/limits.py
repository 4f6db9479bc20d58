#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (run on the chip).

    python bench/limits.py --workload <cell> --seeds 1,2,3 --seconds 8 --out <file>

In one process, for each seed: the cell's program at the cell's own load
for a short window, its compared numbers against the plain reference
("program"), and the same numbers for the control, the reference in the
precision below the configuration's put in the program's place on the
same inputs ("control").  The cell's driver (``readings``) makes them.
One JSON line per seed goes to ``--out``.  The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    try:
        ctx = harness.start(ROOT, args.workload, seed=0, seconds=args.seconds, trace=False,
                            process_start=time.monotonic())
    except harness.BenchError as e:
        print(f"limits: {e}", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    driver = harness.driver(ctx.cell.bench_dir, ctx.cell.config["system"])
    args.out.parent.mkdir(parents=True, exist_ok=True)
    with args.out.open("a") as f:
        for row in driver.readings(ctx, seeds):
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
