"""Mean device milliseconds of one fused decode step in the window."""


def read(r):
    if r.trace is None:
        return None
    name = r.facts["decode_program"]
    events = r.trace.module_events(r.trace.window(), lambda n: name in n)
    if not events:
        return None
    return sum(e.end - e.start for e in events) * 1e-6 / len(events)
