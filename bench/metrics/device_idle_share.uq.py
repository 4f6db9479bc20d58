"""Share (%) of the window in which no program ran on the device."""


def read(r):
    return None if r.trace is None else 100.0 * r.trace.idle_share(r.trace.window())
