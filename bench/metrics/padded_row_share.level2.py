"""Share (%) of the fine-level rows launched in the window that were
power-of-two padding, not requests."""
from bench.windowed import padded_share


def read(r):
    share = padded_share(r.facts["batch_sizes"]["level2"])
    return None if share is None else 100.0 * share
