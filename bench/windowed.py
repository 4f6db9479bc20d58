"""Window arithmetic on the balancer's cumulative counters.

``LoadBalancer.summary()`` counts from the balancer's construction, so a
window's value is the difference of two summaries taken at its edges.
Counters that the summary gives as running means (idle time, slot
occupancy) are turned back into sums first.
"""
from __future__ import annotations

from typing import Dict, Optional


def idle_sum_count(summary: Dict) -> tuple:
    n = int(summary.get("n_requests", 0))
    return float(summary.get("mean_idle_s", 0.0)) * n, n


def idle_mean_ms(before: Dict, after: Dict) -> Optional[float]:
    """Mean queue delay (arrival to dispatch) over the requests completed
    in the window, in milliseconds; None if none completed."""
    s0, n0 = idle_sum_count(before)
    s1, n1 = idle_sum_count(after)
    if n1 <= n0:
        return None
    return (s1 - s0) / (n1 - n0) * 1e3


def requests(before: Dict, after: Dict) -> int:
    return int(after.get("n_requests", 0)) - int(before.get("n_requests", 0))


def hist_delta(before: Dict, after: Dict, tag: str) -> Dict[int, int]:
    """Coalesced batch sizes dispatched for ``tag`` in the window."""
    b = before.get("batch_histogram", {}).get(tag, {})
    a = after.get("batch_histogram", {}).get(tag, {})
    out = {}
    for size, count in a.items():
        d = int(count) - int(b.get(size, 0))
        if d:
            out[int(size)] = d
    return out


def rows_mean(hist: Dict[int, int]) -> Optional[float]:
    batches = sum(hist.values())
    return sum(s * c for s, c in hist.items()) / batches if batches else None


def pow2(n: int) -> int:
    return 1 << (int(n) - 1).bit_length()


def padded_share(hist: Dict[int, int]) -> Optional[float]:
    """Share of the rows launched that were padding, when each batch of
    ``s`` rows runs at the next power of two."""
    launched = sum(pow2(s) * c for s, c in hist.items())
    real = sum(s * c for s, c in hist.items())
    return (launched - real) / launched if launched else None


def occupancy(before: Dict, after: Dict, server: Optional[str] = None) -> Optional[float]:
    """Mean share of a pool's slots that emitted a token per boundary in the
    window (the only pool, when ``server`` is None)."""
    occ_a = after.get("slot_occupancy", {})
    if server is None:
        if len(occ_a) != 1:
            return None
        server = next(iter(occ_a))
    a = occ_a.get(server)
    b = before.get("slot_occupancy", {}).get(server, {"mean": 0.0, "steps": 0})
    if a is None:
        return None
    cap = float(a["capacity"])
    slot_steps = lambda r: float(r["mean"]) * int(r["steps"]) * cap  # noqa: E731
    steps = int(a["steps"]) - int(b.get("steps", 0))
    if steps <= 0:
        return None
    return (slot_steps(a) - slot_steps(b)) / (steps * cap)
