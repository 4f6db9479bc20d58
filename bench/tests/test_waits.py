"""Window arithmetic on the program's wait counters, and the readers of
the dispatcher's and the chain runner's waits."""
import pytest

from bench import harness, idle_split, waits, windowed
from bench.tests.test_windowed import summary
from bench.trace import Trace


def split_summary(n, dispatch_wait_s, handoff_s, coalesce_s, resume=None):
    s = summary(n, (dispatch_wait_s + handoff_s + coalesce_s) / n if n else 0.0)
    s["wait_split"] = {"*": {"n": n, "dispatch_wait_s": dispatch_wait_s,
                             "handoff_s": handoff_s, "coalesce_s": coalesce_s}}
    if resume is not None:
        s["resume"] = {"n": resume[0], "sum_s": resume[1]}
    return s


def test_wait_parts_add_up_to_the_idle_mean():
    before = split_summary(10, 0.010, 0.001, 0.009, resume=(10, 0.002))
    after = split_summary(40, 0.070, 0.004, 0.126, resume=(40, 0.017))
    parts = [waits.wait_part_ms(before, after, k)
             for k in ("dispatch_wait_s", "handoff_s", "coalesce_s")]
    assert parts == [pytest.approx(2.0), pytest.approx(0.1), pytest.approx(3.9)]
    assert sum(parts) == pytest.approx(windowed.idle_mean_ms(before, after))
    assert waits.resume_mean_ms(before, after) == pytest.approx(0.5)
    assert waits.wait_part_ms(after, after, "handoff_s") is None
    assert waits.wait_part_ms(before, after, "handoff_s", tag="level9") is None


def test_waits_per_tag_leave_out_tags_with_nothing_completed():
    before = split_summary(10, 0.010, 0.001, 0.009)
    after = split_summary(40, 0.070, 0.004, 0.126)
    before["wait_split"]["level2"] = dict(before["wait_split"]["*"])
    after["wait_split"]["level2"] = dict(before["wait_split"]["*"])
    assert idle_split.waits_ms(before, after) == {"*": {
        "dispatch_wait_ms": pytest.approx(2.0), "handoff_ms": pytest.approx(0.1),
        "coalesce_ms": pytest.approx(3.9),
    }}
    assert idle_split.waits_ms(summary(10, 0.002), summary(40, 0.006)) == {}


@pytest.mark.parametrize("name", [
    "dispatch_wait_ms_mean", "handoff_ms_mean", "coalesce_wait_ms_mean", "resume_ms_mean",
])
def test_new_readers_find_nothing_in_a_program_without_them(name):
    """A program that keeps no wait split and no resumption counter gives
    these readers nothing to read: None, no error."""
    bare = summary(40, 0.006)
    for tr in (None, Trace({}, [])):
        reading = harness.Reading("cell", {}, summary(10, 0.002), bare, tr, {})
        assert harness.metric_reader(harness.BENCH_DIR, name)(reading) is None


@pytest.mark.parametrize("name,want", [
    ("dispatch_wait_ms_mean", 2.0), ("handoff_ms_mean", 0.1),
    ("coalesce_wait_ms_mean", 3.9), ("resume_ms_mean", 0.5),
])
def test_new_readers_read_the_window(name, want):
    before = split_summary(10, 0.010, 0.001, 0.009, resume=(10, 0.002))
    after = split_summary(40, 0.070, 0.004, 0.126, resume=(40, 0.017))
    reading = harness.Reading("cell", {}, before, after, None, {})
    assert harness.metric_reader(harness.BENCH_DIR, name)(reading) == pytest.approx(want)
