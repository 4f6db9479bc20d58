"""Mean share (%) of the paged pool's slots that emitted a token at each
token boundary in the window."""
from bench.windowed import occupancy


def read(r):
    occ = occupancy(r.before, r.after)
    return None if occ is None else 100.0 * occ
