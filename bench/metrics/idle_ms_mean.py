"""The paper's Fig. 9 idle time: mean queue delay (arrival to dispatch),
in ms, over the balancer requests completed in the window."""
from bench.windowed import idle_mean_ms


def read(r):
    return idle_mean_ms(r.before, r.after)
