"""Window arithmetic on the program's wait counters: the three parts of
the queue delay (``summary()['wait_split']``) and the chain runner's
resumption (``summary()['resume']``).  Each reader gives None where the
program keeps no such counter or no request completed in the window."""
from __future__ import annotations

from typing import Dict, Optional


def wait_part_ms(before: Dict, after: Dict, part: str, tag: str = "*") -> Optional[float]:
    """Mean of one part of the queue delay (``dispatch_wait_s``,
    ``handoff_s`` or ``coalesce_s``) over the requests of ``tag``
    completed in the window, in ms."""
    a = after.get("wait_split", {}).get(tag)
    if a is None:
        return None
    b = before.get("wait_split", {}).get(tag, {})
    n = int(a["n"]) - int(b.get("n", 0))
    if n <= 0:
        return None
    return (float(a[part]) - float(b.get(part, 0.0))) / n * 1e3


def resume_mean_ms(before: Dict, after: Dict) -> Optional[float]:
    """Mean time from a request's completion to its client's resumption
    on it in the window, in ms."""
    a = after.get("resume")
    if a is None:
        return None
    b = before.get("resume", {})
    n = int(a["n"]) - int(b.get("n", 0))
    if n <= 0:
        return None
    return (float(a["sum_s"]) - float(b.get("sum_s", 0.0))) / n * 1e3
