#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line of stdout.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  One process: set up (build, load
weights, warm every shape the cell's traffic uses), measure for
``--seconds``, check the outputs against the plain reference, print.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The run exits 2 and prints no result when JAX's first device is
not a TPU or there are fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(jax, memory_peak_bytes) -> dict:
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": memory_peak_bytes,
    }


def main(argv=None) -> int:
    args = parse(argv)
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import harness

    try:
        ctx = harness.start(
            ROOT, args.workload, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), process_start=PROCESS_START,
        )
    except (harness.BenchError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        line = run_cell(ctx)
    except Exception:  # noqa: BLE001 - report, print no result
        traceback.print_exc()
        return 1
    print(json.dumps(line), flush=True)
    return 0


def finite_or_none(x: float):
    return float(x) if math.isfinite(x) else None


def run_cell(ctx) -> dict:
    """Drive one cell through its driver and assemble the result line."""
    import jax

    from bench import harness

    cell = ctx.cell
    jax.monitoring.register_event_duration_secs_listener(ctx.compile_clock)
    out = harness.driver(cell.bench_dir, cell.config["system"]).run(ctx)
    device = device_info(jax, out.memory_peak_bytes)
    line = {"correct": False, "attempted": out.attempted, "failed": out.failed}
    if ctx.trace:
        tr = ctx.load_trace()
        window = tr.window()
        device["busy_s"] = tr.busy_seconds(window)
        device["window_s"] = (window[1] - window[0]) * 1e-9
        reading = harness.Reading(
            cell.name, out.facts, out.before, out.after, tr,
            harness.peaks(cell.bench_dir, device["kind"]),
        )
        metrics = {}
        for m in cell.per_layer:
            value = harness.metric_reader(cell.bench_dir, m["name"])(reading)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        line["metrics"] = metrics
        line["device"] = device
        line["breakdown"] = tr.breakdown(window)
    else:
        if ctx.require_tpu:
            harness.peaks(cell.bench_dir, device["kind"])  # an unknown chip is an error
        line["metrics"] = {
            m["name"]: {"value": float(out.end_to_end[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end
        }
        line["device"] = device
    for note in out.notes:
        harness.log(note)
    for c in out.checks:
        harness.log(f"[check] {c.name} = {c.value!r} (limit {c.limit!r}) {'ok' if c.ok else 'FAILED'}")
    line["correct"] = bool(out.checks) and all(c.ok for c in out.checks)
    line["checks"] = {
        c.name: {"value": finite_or_none(c.value), "limit": c.limit} for c in out.checks
    }
    return line


if __name__ == "__main__":
    sys.exit(main())
