"""A whole run with the timed path broken underneath comes out not
correct; the same run unbroken comes out correct.  The look for a chip is
skipped; everything else is the benchmark's own run."""
import numpy as np
import pytest

from bench.run import run_cell
from bench.tests import small


def mlda_fault(label, fn):
    """The fine level's answer altered where it is produced: every wave
    height 0.2 m (5 sigma of the observation noise) too high."""
    if label != "level2":
        return fn
    return lambda thetas: np.asarray(fn(thetas)) + np.array([0.2, 0.0, 0.0, 0.0])


def lm_fault(label, fn):
    """A token altered where it is produced: the decode step emits the
    next vocabulary id after the one it picked."""
    if label != "decode":
        return fn

    def step(state, tokens, active):
        state, nxt = fn(state, tokens, active)
        return state, (np.asarray(nxt) + 1) % small.lm_config()["vocab_size"]

    return step


SERVE_E2E = {"ttft_ms_p50", "ttft_ms_p80", "itl_ms_p99", "setup_s"}


def lm_cell():
    """The small chat cell, reporting the serving metrics."""
    cfg = small.lm_config()
    cfg["weights"]["embed_std"] = 1.0  # logits far apart: a wrong token shows
    c = small.cell(cfg, small.lm_traffic())
    c.end_to_end = small.serving_metrics(c.name)[0] + [{"name": "setup_s", "unit": "s"}]
    return c


@pytest.mark.parametrize("resident", [False, True])
@pytest.mark.parametrize("broken", [False, True])
def test_mlda_run(broken, resident):
    c = small.cell(small.mlda_config(), small.mlda_traffic(resident))
    line = run_cell(small.context(c, seconds=1.0, tamper=mlda_fault if broken else None))
    assert line["correct"] is (not broken)
    assert line["checks"]["fine_gap_sigma"]["value"] > (4.0 if broken else -1.0)
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("broken", [False, True])
def test_lm_run(broken):
    line = run_cell(small.context(lm_cell(), seconds=2.0, tamper=lm_fault if broken else None))
    assert line["correct"] is (not broken)
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == SERVE_E2E
    assert all(0 < m["value"] < float("inf") for m in line["metrics"].values())
