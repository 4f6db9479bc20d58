"""Mean ms from a balancer request's completion to the moment the chain
runner resumed on it, over the requests it resumed on in the window."""
from bench.waits import resume_mean_ms


def read(r):
    return resume_mean_ms(r.before, r.after)
