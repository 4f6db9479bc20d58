"""The harness finds configurations, traffic mixes and metrics by name,
and the committed BENCHMARK.json keeps to its shape."""
import json
import re
from pathlib import Path

import pytest

from bench import harness
from bench.tests import small

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(obj if isinstance(obj, str) else json.dumps(obj))


def test_new_files_are_found_without_an_edit(tmp_path):
    bench = tmp_path / "bench"
    write(bench / "configs" / "toy.json", {"name": "toy", "system": "toy"})
    write(bench / "traffic" / "steady.json", {"kind": "open_loop", "rate_per_s": 3})
    write(bench / "metrics" / "rows.level9.py", "def read(r):\n    return r.facts['rows'] * 2\n")
    write(bench / "drivers" / "toy.py", "def run(ctx):\n    return 'driven ' + ctx\n")
    write(tmp_path / "BENCHMARK.json", {
        "paths": ["bench"],
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.steady", "config": "toy", "traffic": "steady", "chips": 1}],
        "end_to_end": [
            {"name": "rate", "unit": "1/s", "workloads": ["toy.steady"]},
            {"name": "other", "unit": "s", "workloads": ["elsewhere"]},
            {"name": "setup_s", "unit": "s"},
        ],
        "per_layer": [
            {"name": "rows.level9", "unit": "rows", "moves": "rate"},
            {"name": "skipped", "unit": "rows", "moves": "other"},
        ],
    })
    cell = harness.find_cell(tmp_path, "toy.steady")
    assert cell.config["system"] == "toy" and cell.traffic["rate_per_s"] == 3
    assert [m["name"] for m in cell.end_to_end] == ["rate", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["rows.level9"]
    reading = harness.Reading("toy.steady", {"rows": 21}, {}, {}, None, {})
    assert harness.metric_reader(cell.bench_dir, "rows.level9")(reading) == 42
    assert harness.driver(cell.bench_dir, "toy").run("it") == "driven it"
    with pytest.raises(harness.BenchError):
        harness.find_cell(tmp_path, "no.such.cell")


def test_unknown_device_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.peaks(harness.BENCH_DIR, "cpu")
    assert harness.peaks(harness.BENCH_DIR, "TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_committed_benchmark_resolves_every_cell():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert (harness.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
    layers = {m["layer"] for m in spec["per_layer"]}
    assert all("\n" not in x and 0 < len(x) <= 200 for x in layers)
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:  # a metric lists the cells that report it, never none
            assert m["workloads"] and set(m["workloads"]) <= cells, m["name"]
    for w in spec["workloads"]:
        cell = harness.find_cell(ROOT, w["name"])
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        assert (harness.BENCH_DIR / "drivers" / f"{cell.config['system']}.py").is_file()
        assert len(w["why"]) <= 200


SERVE_E2E = {"ttft_ms_p50", "ttft_ms_p80", "itl_ms_p99"}
SERVE_LAYER = {"decode_step_ms.serve", "queue_wait_ms_mean.serve", "slot_occupancy.serve",
               "mfu.serve", "device_idle_share.serve"}


def with_serving_cell(tmp_path, cell: str):
    """The committed BENCHMARK.json with a serving cell and its metrics
    added, as the PR that brings such a cell adds them."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "qwen2-0.5b", "file": "bench/configs/qwen2-0.5b.json"})
    spec["workloads"].append(
        {"name": cell, "config": "qwen2-0.5b", "traffic": "chat-steady", "chips": 1}
    )
    e2e, per_layer = small.serving_metrics(cell)
    spec["end_to_end"][-1:-1] = e2e  # setup_s stays last
    spec["per_layer"] += per_layer
    write(tmp_path / "BENCHMARK.json", spec)
    (tmp_path / "bench").symlink_to(harness.BENCH_DIR)
    return harness.find_cell(tmp_path, cell)


def test_serving_cell_resolves_to_the_serving_metrics(tmp_path):
    cell = with_serving_cell(tmp_path, "qwen2-0.5b.chat")
    assert {m["name"] for m in cell.end_to_end} == SERVE_E2E | {"setup_s"}
    assert {m["name"] for m in cell.per_layer} == SERVE_LAYER
    e2e = {m["name"]: m for m in cell.end_to_end}
    for m in cell.per_layer:
        assert m["moves"] in SERVE_E2E
        assert (harness.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
    for name in SERVE_E2E:
        assert (e2e[name]["unit"], e2e[name]["better"], e2e[name]["source"]) == (
            "ms", "lower", "host_clock")
        assert 0.01 <= e2e[name]["bound"] <= 0.25
    assert cell.config["system"] == "lm"


def test_mlda_cell_keeps_its_metrics():
    cell = harness.find_cell(ROOT, "mlda-paper-5chains")
    assert [m["name"] for m in cell.end_to_end] == ["fine_samples_per_s", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert names >= {
        "requests_per_fine_sample", "idle_ms_mean", "batch_rows_mean.level2",
        "padded_row_share.level2", "fine_solve_ms_per_row", "mfu.uq", "device_idle_share.uq",
        "dispatch_wait_ms_mean", "handoff_ms_mean", "coalesce_wait_ms_mean", "resume_ms_mean",
    }
    assert not names & SERVE_LAYER


def test_libtpu_flags_are_added_not_replaced():
    env = {"LIBTPU_INIT_ARGS": "--keep=1"}
    harness.prepare_environment(env)
    harness.prepare_environment(env)
    assert env["LIBTPU_INIT_ARGS"].split() == ["--keep=1", *harness.LIBTPU_FLAGS]


def test_no_tpu_no_result():
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", "mlda-paper-5chains",
         "--seed", str(2**33), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 2 and out.stdout == ""
    assert "TPU" in out.stderr
