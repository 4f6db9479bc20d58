"""Mean ms, over the balancer requests completed in the window, from
arrival to the dispatch decision that popped the request (the wait
for a free server): one of the three parts of ``idle_ms_mean``."""
from bench.waits import wait_part_ms


def read(r):
    return wait_part_ms(r.before, r.after, "dispatch_wait_s")
