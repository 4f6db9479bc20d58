"""Device milliseconds of the fine-solve programs per real fine row in the
window.  The programs are the ones the probe spans ran alone before the
window (the coarse level's program carries the same name)."""


def read(r):
    if r.trace is None:
        return None
    rows = r.facts["evals"]["level2"]
    fine = r.trace.modules_within(r.facts["fine_probe_prefix"])
    if not rows or not fine:
        return None
    secs = r.trace.module_seconds(r.trace.window(), lambda name: name in fine)
    return secs * 1e3 / rows if secs > 0 else None
