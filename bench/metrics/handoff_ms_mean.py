"""Mean ms, over the balancer requests completed in the window, from
the dispatch decision to a worker starting on the request (the
thread hand-off): one of the three parts of ``idle_ms_mean``."""
from bench.waits import wait_part_ms


def read(r):
    return wait_part_ms(r.before, r.after, "handoff_s")
