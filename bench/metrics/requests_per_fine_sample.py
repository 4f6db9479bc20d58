"""Balancer requests per fine-level sample completed in the window (the
ensemble layer's round trips for one unit of the user's work)."""
from bench.windowed import requests


def read(r):
    samples = r.facts.get("fine_samples", 0)
    return requests(r.before, r.after) / samples if samples else None
