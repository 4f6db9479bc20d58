"""The idle split of ``bench/idle_split.py`` on a constructed
``.xplane.pb``: program spans with their threads and stats, and each idle
instant given to the first class that covers it."""
import pytest

from bench import idle_split
from bench import trace
from bench.tests.test_trace import MS, PS_PER_MS, xspace


def xspace_with_program_spans(path):
    """The device of ``xspace`` (busy 0-10, 20-30, 40-45 ms, so 75 ms
    idle) and two host threads whose lines carry one name.  Runner thread:
    the window, steps 12-18 and 32-36 ms, a wait 50-100 ms.  Worker
    thread: serves 15-25 and 60-70 ms, a coalescing window 34-38 ms."""
    from jax.profiler import ProfileData

    def events(rows):
        out = ""
        for m, a, b, stats in rows:
            st = "".join(f" stats {{ metadata_id: {k} int64_value: {v} }}" for k, v in stats)
            out += (
                f"events {{ metadata_id: {m} offset_ps: {a * PS_PER_MS} "
                f"duration_ps: {(b - a) * PS_PER_MS}{st} }}"
            )
        return out

    text = f"""
    planes {{ id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
        {events([(1, 0, 10, ()), (1, 20, 30, ()), (2, 40, 45, ())])} }}
      event_metadata {{ key: 1 value {{ id: 1 name: "jit_swe_forward_96x96(7)" }} }}
      event_metadata {{ key: 2 value {{ id: 2 name: "jit_swe_forward_288x288(9)" }} }}
    }}
    planes {{ id: 2 name: "/host:CPU"
      lines {{ id: 3 name: "python" timestamp_ns: 0
        {events([(1, 0, 100, ()), (2, 12, 18, ((1, 0), (2, 4))), (2, 32, 36, ((1, 1), (2, -1))),
                 (3, 50, 100, ())])} }}
      lines {{ id: 4 name: "python" timestamp_ns: 0
        {events([(4, 15, 25, ((2, 4), (3, 1))), (5, 34, 38, ((2, 5),)), (4, 60, 70, ((2, 6), (3, 2)))])}
      }}
      event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
      event_metadata {{ key: 2 value {{ id: 2 name: "repro.runner.step" }} }}
      event_metadata {{ key: 3 value {{ id: 3 name: "repro.runner.wait" }} }}
      event_metadata {{ key: 4 value {{ id: 4 name: "repro.dispatch.serve" }} }}
      event_metadata {{ key: 5 value {{ id: 5 name: "repro.dispatch.coalesce" }} }}
      stat_metadata {{ key: 1 value {{ id: 1 name: "chain" }} }}
      stat_metadata {{ key: 2 value {{ id: 2 name: "req" }} }}
      stat_metadata {{ key: 3 value {{ id: 3 name: "rows" }} }}
    }}
    """
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def test_idle_partition_over_program_spans(tmp_path):
    path = xspace_with_program_spans(tmp_path / "p.xplane.pb")
    tr = trace.Trace.from_file(path)
    spans = idle_split.program_spans(path)
    window = tr.window()
    # the trace the breakdown reads keeps the bench spans alone
    assert [s.name for s in tr.spans] == ["bench.window"]
    gaps = tr.breakdown(window)["idle_gaps"]
    assert gaps[0] == ["host outside bench spans", pytest.approx(55e-3)]
    # two threads, told apart by their line's place though both are "python"
    assert {s.thread for s in spans} == {("/host:CPU", 0), ("/host:CPU", 1)}
    serve = [s for s in spans if s.name == "repro.dispatch.serve"]
    assert [s.stats for s in serve] == [{"req": 4, "rows": 1}, {"req": 6, "rows": 2}]
    step = [s for s in spans if s.name == "repro.runner.step"][0]
    assert step.stats == {"chain": 0, "req": 4}  # resumed on the request served
    part = idle_split.partition(tr, spans, window)
    # serving: 15-20 and 60-70; stepping: 12-15 and 32-36 (serve wins 15-18);
    # coalescing: 36-38 (the step wins 34-36); waiting: the other 51 ms
    assert part == {
        "serving": pytest.approx(0.15),
        "stepping": pytest.approx(0.07),
        "coalescing": pytest.approx(0.02),
        "waiting": pytest.approx(0.51),
    }
    assert sum(part.values()) == pytest.approx(tr.idle_share(window), abs=1e-12)
    assert list(part) == ["serving", "stepping", "coalescing", "waiting"]
    # a narrower window: 25-50 ms is idle 30-40 and 45-50 (15 ms)
    part = idle_split.partition(tr, spans, (25 * MS, 50 * MS))
    assert part["stepping"] == pytest.approx(4 / 25)
    assert part["coalescing"] == pytest.approx(2 / 25)
    assert part["waiting"] == pytest.approx(9 / 25)
    assert sum(part.values()) == pytest.approx(tr.idle_share((25 * MS, 50 * MS)))


def test_trace_without_program_spans_is_all_waiting(tmp_path):
    path = xspace(tmp_path / "t.xplane.pb")
    tr = trace.Trace.from_file(path)
    assert idle_split.program_spans(path) == []
    part = idle_split.partition(tr, [], tr.window())
    assert part["waiting"] == pytest.approx(tr.idle_share(tr.window()))
    assert part["serving"] == part["stepping"] == part["coalescing"] == 0.0


def test_interval_complement_and_intersection():
    assert idle_split.complement([(2, 3), (5, 12)], (0, 10)) == [(0, 2), (3, 5)]
    assert idle_split.complement([], (0, 10)) == [(0, 10)]
    assert idle_split.intersect([(0, 3), (5, 8)], [(2, 6), (7, 9)]) == [(2, 3), (5, 6), (7, 8)]
