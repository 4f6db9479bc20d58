#!/usr/bin/env python3
"""Where a traced run's device idle time went, by what the program's own
threads were inside of.

    python bench/idle_split.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does and prints one JSON line:
the window's device idle share (%) split over the ``repro.*`` spans the
program puts around its own work, and, per balancer tag, the window's mean
queue delay in its three parts (``summary()['wait_split']``).  Each idle
instant goes to the first class of ``CLASSES`` whose span covers it on
some thread; what none covers is ``waiting`` (every thread waits on
another: hand-offs, wake-ups, locks).  A program without these spans reads
all idle as waiting, and one without the counters gives no waits.

The result line of ``bench/run.py`` cannot carry the split: the trace it
reads (``bench/trace.py``) keeps only the ``bench.*`` host spans.
"""
from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Sequence, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.trace import DEVICE_PLANE, Interval, Trace, clip, total, union  # noqa: E402

PROGRAM_PREFIX = "repro."
CLASSES = (
    ("serving", "repro.dispatch.serve"),
    ("stepping", "repro.runner.step"),
    ("coalescing", "repro.dispatch.coalesce"),
)
WAIT_PARTS = ("dispatch_wait_s", "handoff_s", "coalesce_s")


@dataclass
class ProgramSpan:
    name: str
    start: float
    end: float
    thread: Tuple[str, int]  # (plane name, position of the line in it)
    stats: Dict[str, Any]


def program_spans(path) -> List[ProgramSpan]:
    """The ``repro.*`` host events of an ``.xplane.pb``.  A thread is keyed
    by its line's position in its plane: every thread's line may carry the
    same name."""
    from jax.profiler import ProfileData

    out: List[ProgramSpan] = []
    for plane in ProfileData.from_file(str(path)).planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for pos, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    out.append(ProgramSpan(
                        e.name, e.start_ns, e.start_ns + e.duration_ns,
                        (plane.name, pos), dict(e.stats),
                    ))
    return out


def complement(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that no interval covers."""
    out: List[Interval] = []
    at = window[0]
    for a, b in union(clip(intervals, window)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if at < window[1]:
        out.append((at, window[1]))
    return out


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """The parts covered by both; each side sorted and disjoint."""
    out: List[Interval] = []
    i = j = 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def partition(tr: Trace, spans: Sequence[ProgramSpan], window: Interval) -> Dict[str, float]:
    """Shares (0 to 1) of ``window`` in which no program ran, split over
    ``CLASSES`` and "waiting".  Averaged over the devices as
    ``Trace.idle_share`` is, so the shares sum to it."""
    covers = [
        (cls, union(clip([(s.start, s.end) for s in spans if s.name == name], window)))
        for cls, name in CLASSES
    ]
    devs = tr.devices()
    out = {cls: 0.0 for cls, _ in CLASSES}
    out["waiting"] = 0.0
    for d in devs or [None]:
        rest = complement(tr.busy_intervals(d) if d else [], window)
        for cls, cover in covers:
            out[cls] += total(intersect(rest, cover))
            rest = intersect(rest, complement(cover, window))
        out["waiting"] += total(rest)
    span_ns = (window[1] - window[0]) * max(len(devs), 1)
    return {cls: ns / span_ns for cls, ns in out.items()}


def waits_ms(before: Dict, after: Dict) -> Dict[str, Dict[str, float]]:
    """Per tag (and ``*`` for all), the window's mean of each part of the
    queue delay in ms; tags with no request completed are left out."""
    from bench.waits import wait_part_ms

    out = {}
    for tag in after.get("wait_split", {}):
        parts = {k[:-2] + "_ms": wait_part_ms(before, after, k, tag) for k in WAIT_PARTS}
        if None not in parts.values():
            out[tag] = parts
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness
    from bench.waits import resume_mean_ms
    from bench.windowed import idle_mean_ms

    ctx = harness.start(ROOT, args.workload, seed=args.seed, seconds=args.seconds,
                        trace=True, process_start=PROCESS_START)
    out = harness.driver(ctx.cell.bench_dir, ctx.cell.config["system"]).run(ctx)
    files = sorted(Path(ctx._profile_dir).rglob("*.xplane.pb"))
    spans = program_spans(files[-1]) if files else []
    tr = ctx.load_trace()
    window = tr.window()
    line = {
        "correct": bool(out.checks) and all(c.ok for c in out.checks),
        "window_s": (window[1] - window[0]) * 1e-9,
        "idle_share": 100.0 * tr.idle_share(window),
        "idle_split": {k: 100.0 * v for k, v in partition(tr, spans, window).items()},
        "idle_ms_mean": idle_mean_ms(out.before, out.after),
        "waits_ms": waits_ms(out.before, out.after),
        "resume_ms_mean": resume_mean_ms(out.before, out.after),
    }
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
