"""The trace reduction on a small constructed ``.xplane.pb``: busy and
idle share, program device time, probe-named programs, the breakdown.
Nothing here describes a TPU."""
import pytest

from bench import trace

MS = 1_000_000  # ns
PS_PER_MS = 1_000_000_000


def xspace(path):
    """Device: programs A (0-10 ms), A again (20-30), B (40-45), and an
    operation line that the reduction does not read.  Host: the window 0-100 ms, a probe span 38-46 ms
    around B, a decode span 50-90 ms."""
    from jax.profiler import ProfileData

    def events(pairs):
        return "".join(
            f"events {{ metadata_id: {m} offset_ps: {a * PS_PER_MS} duration_ps: {(b - a) * PS_PER_MS} }}"
            for m, a, b in pairs
        )

    text = f"""
    planes {{ id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {events([(1, 0, 10), (1, 20, 30), (2, 40, 45)])} }}
      lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {events([(3, 1, 9), (3, 20, 30), (4, 40, 45)])} }}
      event_metadata {{ key: 1 value {{ id: 1 name: "jit_forward(7)" }} }}
      event_metadata {{ key: 2 value {{ id: 2 name: "jit_step_j(9)" }} }}
      event_metadata {{ key: 3 value {{ id: 3 name: "fusion.1" }} }}
      event_metadata {{ key: 4 value {{ id: 4 name: "fusion.2" }} }}
    }}
    planes {{ id: 2 name: "/host:CPU"
      lines {{ id: 3 name: "python" timestamp_ns: 0 {events([(1, 0, 100), (2, 38, 46), (3, 50, 90), (4, 0, 100)])} }}
      event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
      event_metadata {{ key: 2 value {{ id: 2 name: "bench.probe.level2.b1" }} }}
      event_metadata {{ key: 3 value {{ id: 3 name: "bench.decode_step" }} }}
      event_metadata {{ key: 4 value {{ id: 4 name: "PjitFunction(forward)" }} }}
    }}
    """
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


@pytest.fixture
def tr(tmp_path):
    return trace.Trace.from_file(xspace(tmp_path / "t.xplane.pb"))


def test_window_and_busy_share(tr):
    window = tr.window()
    assert window == (0, 100 * MS)
    assert tr.busy_seconds(window) == pytest.approx((10 + 10 + 5) * 1e-3)
    assert tr.busy_seconds((25 * MS, 42 * MS)) == pytest.approx(7e-3)
    assert [s.name for s in tr.spans if s.name.startswith("PjitFunction")] == []


def test_program_time_and_probes(tr):
    window = tr.window()
    assert tr.module_seconds(window, lambda n: "forward" in n) == pytest.approx(20e-3)
    assert len(tr.module_events(window, lambda n: "step_j" in n)) == 1
    assert tr.modules_within("bench.probe.level2.") == {"jit_step_j(9)"}
    assert trace.program_name("jit_step_j(9)") == "jit_step_j"


def test_breakdown_names_programs_and_gaps(tr):
    out = tr.breakdown(tr.window())
    assert out["device_ops"][0] == ["jit_forward", pytest.approx(20e-3)]
    labels = {name: secs for name, secs in out["idle_gaps"]}
    # 45-100 ms idle: its middle (72.5 ms) falls in the decode span; the
    # next longest, 10-20 and 30-40 ms, are outside every narrower span
    assert labels["bench.decode_step"] == pytest.approx(55e-3)
    assert out["idle_gaps"][0][1] == pytest.approx(55e-3)
    assert out["idle_gaps"][1] == ["host outside bench spans", pytest.approx(10e-3)]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_interval_union_and_clip():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.clip([(0, 3), (5, 8)], (2, 6)) == [(2, 3), (5, 6)]
    assert trace.total([(0, 3), (5, 8)]) == 6
