"""Mean wait of a generation request in the balancer's queue (arrival to
admission into a decode slot), in ms, over the requests completed in the
window."""
from bench.windowed import idle_mean_ms


def read(r):
    return idle_mean_ms(r.before, r.after)
