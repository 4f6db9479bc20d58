"""Mean rows of a coalesced fine-level (level2) dispatch in the window."""
from bench.windowed import rows_mean


def read(r):
    return rows_mean(r.facts["batch_sizes"]["level2"])
