#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest offered rate
with no growing backlog (run on the chip).

    python bench/sweep.py --workload <cell> --rates 1,1.5,2 --seconds 51 --seeds 1,2

One process sets the cell up once (weights from the first seed) and then
runs one window per rate and seed, with the cell's traffic at that rate
and that seed's prompt tokens (the order of the schedule is the same for
every seed, ``bench/traffic.py``).  Per window it
prints the tails, the drain the stragglers needed after the window, and
how TTFT grew from the window's first half to its second: a backlog that
grows shows as a long drain and a second half far slower than the first.
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        ctx = harness.start(ROOT, args.workload, seed=seeds[0], seconds=args.seconds,
                            trace=False, process_start=time.monotonic())
    except harness.BenchError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    import numpy as np

    from bench import traffic as gen
    from bench import windowed
    from bench.drivers import lm

    cell = ctx.cell
    lmc = lm.LMCell(ctx)
    lmc.warm()
    for rate, seed in itertools.product([float(r) for r in args.rates.split(",")], seeds):
        tr = dict(lmc.tr, rate_per_s=rate)
        sched = gen.open_loop(tr, cell.config["vocab_size"], seed, args.seconds)
        win = lmc.window(sched)
        ttft, gaps, failed, _ = lm.tally(sched, win)
        half = sched.due_s < args.seconds / 2
        first, second = float(np.median(ttft[half])), float(np.median(ttft[~half]))
        print(json.dumps({
            "rate_per_s": rate,
            "seed": seed,
            "requests": len(sched),
            "failed": len(failed),
            **lm.tails(ttft, gaps),
            "ttft_ms_p50_first_half": first * 1e3,
            "ttft_ms_p50_second_half": second * 1e3,
            "ttft_p50_second_over_first": second / first,
            "drain_s": win["t_end"] - win["t_close"],
            "slot_occupancy": windowed.occupancy(win["before"], win["after"]),
            "queue_wait_ms_mean": windowed.idle_mean_ms(win["before"], win["after"]),
        }), flush=True)
    lmc.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
