"""Window arithmetic: differences of cumulative telemetry, and tails over
all requests."""
import numpy as np
import pytest

from bench import windowed
from bench.drivers import lm
from bench.traffic import Schedule


def summary(n, mean_idle_s, hist=None, occ=None):
    return {"n_requests": n, "mean_idle_s": mean_idle_s,
            "batch_histogram": hist or {}, "slot_occupancy": occ or {}}


def test_idle_mean_is_the_mean_of_the_window_only():
    # 10 requests at 2 ms before the window, then 30 at 6 ms inside it.
    before = summary(10, 0.002)
    after = summary(40, (10 * 0.002 + 30 * 0.006) / 40)
    assert windowed.idle_mean_ms(before, after) == pytest.approx(6.0)
    assert windowed.idle_mean_ms(after, after) is None


def test_histogram_difference_rows_and_padding():
    before = summary(0, 0, {"level2": {1: 5, 2: 1}})
    after = summary(0, 0, {"level2": {1: 7, 2: 1, 3: 2, 8: 1}})
    hist = windowed.hist_delta(before, after, "level2")
    assert hist == {1: 2, 3: 2, 8: 1}
    assert windowed.rows_mean(hist) == pytest.approx((2 + 6 + 8) / 5)
    # a 3-row batch runs at 4: one padded row each; launched 2 + 8 + 8 = 18
    assert windowed.padded_share(hist) == pytest.approx(2 / 18)
    assert windowed.rows_mean({}) is None and windowed.padded_share({}) is None


def test_occupancy_difference():
    b = {"pool": {"mean": 0.5, "steps": 10, "capacity": 4}}
    a = {"pool": {"mean": (0.5 * 10 * 4 + 4 * 30) / (40 * 4), "steps": 40, "capacity": 4}}
    assert windowed.occupancy(summary(0, 0, occ=b), summary(0, 0, occ=a)) == pytest.approx(1.0)


class _Done:
    def __init__(self, tokens, times):
        self.tokens, self.token_times = tokens, times


class _Gen:
    def __init__(self, res=None, error=None):
        self.res, self.error = res, error

    def result(self, timeout=None):
        if self.error is not None:
            raise self.error
        if self.res is None:
            raise TimeoutError
        return self.res


def tallied():
    """Two requests finished, one never finished, one failed."""
    sched = Schedule(
        due_s=np.array([0.0, 1.0, 2.0, 3.0]),
        prompt_len=np.array([4, 4, 4, 4]),
        new_tokens=np.array([3, 3, 3, 3]),
        prompts=[np.zeros(4, np.int32)] * 4,
    )
    gens = [
        _Gen(_Done([1, 2, 3], [100.5, 100.6, 100.8])),  # ttft 0.5
        _Gen(_Done([1, 2, 3], [101.2, 101.3, 101.4])),  # ttft 0.2
        _Gen(None),  # never finished: counts to the end of the drain
        _Gen(error=RuntimeError("boom")),  # failed
    ]
    return lm.tally(sched, {"t0": 100.0, "t_end": 110.0, "gens": gens})


def test_tails_count_every_request_from_its_due_time():
    ttft, gaps, failed, done = tallied()
    assert ttft == pytest.approx([0.5, 0.2, 8.0, 7.0])
    assert sorted(gaps) == pytest.approx(sorted([0.1, 0.2, 0.1, 0.1]))
    assert failed == [2, 3] and [i for i, _ in done] == [0, 1]
    # the tail is taken over all four requests, the failed ones included
    assert np.quantile(ttft, 0.95) > 7.0


def test_serving_metrics_are_quantiles_of_the_tally():
    ttft, gaps, failed, _ = tallied()
    assert len(ttft) == 4 and len(failed) == 2  # the failed requests are in ttft
    got = lm.tails(ttft, gaps)
    assert list(got) == ["ttft_ms_p50", "ttft_ms_p80", "itl_ms_p99"]
    assert got["ttft_ms_p50"] == pytest.approx(np.quantile(ttft, 0.5) * 1e3)
    assert got["ttft_ms_p80"] == pytest.approx(np.quantile(ttft, 0.8) * 1e3)
    assert got["itl_ms_p99"] == pytest.approx(np.quantile(gaps, 0.99) * 1e3)
    # [0.2, 0.5, 7.0, 8.0]: the median lies between a finished and a failed request
    assert got["ttft_ms_p50"] == pytest.approx(3750.0)
    assert lm.tails(ttft, np.array([]))["itl_ms_p99"] == float("inf")


def test_tail_note_counts_samples_beyond_and_warns_below_ten():
    ttft, gaps, _, _ = tallied()
    assert lm.beyond(ttft, 0.5) == 2 and lm.beyond(ttft, 0.8) == 1
    note = lm.tail_note(ttft, gaps)
    assert "ttft_ms_p80 1" in note and "fewer than 10 beyond" in note
    many = np.arange(100.0)
    assert lm.beyond(many, 0.8) == 20
    assert "fewer" not in lm.tail_note(many, np.arange(2000.0))
