"""The one general traffic generator: a traffic file's parameters plus a
seed give the exact inputs of a run.

A traffic file (``bench/traffic/<mix>.json``) holds a ``kind`` and its
parameters.  Two kinds exist:

* ``mlda_chains``: a closed loop of MCMC chains.  The seed draws each
  chain's start inside the prior box (scaled by ``start_scale``) and
  seeds the chains' own random streams.
* ``open_loop``: independent users.  ``n = floor(rate_per_s * seconds)``
  requests, each with a due time, a prompt length, an output length and
  prompt token ids.

Every seed of an ``open_loop`` mix gets the same schedule: the
inter-arrival gaps, prompt lengths and output lengths are the
distributions' quantiles at ``(i + 0.5) / n``, put in one fixed order
(:data:`ORDER`).  The seed draws only the prompt token ids (and, in the
driver, the weights), so two seeds ask for the same work at the same
times, and a run-to-run difference comes from the system.  Under queueing
the order alone moves the latency tails by more than a bound can hold: a
seed that shuffled the order changed the work.
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np


def seed_words(seed: int) -> np.random.SeedSequence:
    """A seed sequence for any whole number, however large (the driver's
    seeds run past 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.SeedSequence(int(seed))


def int31(seed: int, salt: int = 0) -> int:
    """A 31-bit integer derived from ``seed``: what JAX keys and the
    program's own integer seeds can hold."""
    word = np.random.SeedSequence([int(seed), int(salt)]).generate_state(1)[0]
    return int(word) & 0x7FFFFFFF


# ---------------------------------------------------------------------------
# closed loop: MCMC chains
# ---------------------------------------------------------------------------
def chain_starts(params: Dict[str, Any], prior_lo, prior_hi, seed: int) -> np.ndarray:
    """``(chains, d)`` float32 start states, uniform in the prior box
    shrunk about its centre by ``start_scale``."""
    rng = np.random.default_rng(seed_words(seed).spawn(2)[0])
    lo = np.asarray(prior_lo, np.float64)
    hi = np.asarray(prior_hi, np.float64)
    mid, half = 0.5 * (lo + hi), 0.5 * (hi - lo) * float(params["start_scale"])
    u = rng.uniform(-1.0, 1.0, size=(int(params["chains"]), lo.size))
    return (mid + u * half).astype(np.float32)


# ---------------------------------------------------------------------------
# open loop: requests on a schedule
# ---------------------------------------------------------------------------
@dataclass
class Schedule:
    """One run's requests, in due order."""

    due_s: np.ndarray  # (n,) seconds after the window opens
    prompt_len: np.ndarray  # (n,) int
    new_tokens: np.ndarray  # (n,) int
    prompts: List[np.ndarray]  # n int32 arrays of prompt_len[i] token ids

    def __len__(self) -> int:
        return int(self.due_s.size)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_sizes(spec: Dict[str, Any], n: int) -> np.ndarray:
    """The ``n`` quantiles of a lognormal (``median``, ``sigma``), rounded
    and clipped to ``[min, max]``."""
    z = np.array([statistics.NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.exp(math.log(float(spec["median"])) + float(spec["sigma"]) * z)
    return np.clip(np.rint(x), int(spec["min"]), int(spec["max"])).astype(np.int64)


def exponential_gaps(rate_per_s: float, n: int) -> np.ndarray:
    """The ``n`` quantiles of the exponential inter-arrival time of a
    Poisson process at ``rate_per_s``."""
    return -np.log1p(-_quantiles(n)) / float(rate_per_s)


# The one order of every open-loop schedule's gaps and sizes.
ORDER = 0


def open_loop(params: Dict[str, Any], vocab: int, seed: int, seconds: float) -> Schedule:
    if params.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {params.get('arrivals')!r}")
    rate = float(params["rate_per_s"])
    n = int(math.floor(rate * seconds))
    if n < 1:
        raise ValueError(f"rate {rate}/s over {seconds} s gives no request")
    gaps_ss, prompt_ss, out_ss, _ = seed_words(ORDER).spawn(4)
    tok_ss = seed_words(seed).spawn(4)[3]
    gaps = np.random.default_rng(gaps_ss).permutation(exponential_gaps(rate, n))
    # Request i is due after i + 1 gaps; the sum of the gaps (the last due
    # time) lies just inside the window.
    due = np.cumsum(gaps)
    prompt_len = np.random.default_rng(prompt_ss).permutation(
        lognormal_sizes(params["prompt"], n)
    )
    new_tokens = np.random.default_rng(out_ss).permutation(
        lognormal_sizes(params["output"], n)
    )
    tok_rng = np.random.default_rng(tok_ss)
    prompts = [
        tok_rng.integers(0, vocab, size=int(s), dtype=np.int64).astype(np.int32)
        for s in prompt_len
    ]
    return Schedule(due, prompt_len, new_tokens, prompts)
