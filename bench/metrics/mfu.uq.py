"""Share (%) of the chip's bf16 peak that the window's needed work
reaches over the device's busy time: the operations of every GP, coarse
and fine evaluation completed (``bench/work.py``) over busy seconds times
the peak.  The stencil runs on the vector unit, so this reads far below 1 %."""


def read(r):
    if r.trace is None:
        return None
    busy = r.trace.busy_seconds(r.trace.window())
    if busy <= 0 or not r.facts.get("flops"):
        return None
    return 100.0 * r.facts["flops"] / (busy * r.peaks["bf16_flops_per_s"])
