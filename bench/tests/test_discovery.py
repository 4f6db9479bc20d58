"""The harness finds configurations, traffic mixes and metrics by name,
and the committed BENCHMARK.json keeps to its shape."""
import json
import re
from pathlib import Path

import pytest

from bench import harness

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
    for w in spec["workloads"]:
        cell = harness.find_cell(ROOT, w["name"])
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        assert (harness.BENCH_DIR / "drivers" / f"{cell.config['system']}.py").is_file()
        assert len(w["why"]) <= 200


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
