"""The harness finds cells and metrics by name, so a later change adds
them as files and ``BENCHMARK.json`` entries; and the command refuses any
machine it cannot measure on."""

import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

FAKE_DRIVER = '''
from harness import Run

def run(spec):
    spec.window.start()
    spec.window.stop()
    return Run(end_to_end={"setup_s": 1.5, "serve_tokens_per_s": 10.0,
                           "serve_request_p95_s": 2.0},
               attempted=3, failed=0, checks={"answer_gap": (0.0, 1.0)},
               memory_peak_bytes=0, work={"answer": spec.traffic["answer"]})
'''

FAKE_METRIC = '''
def read(ctx):
    return ctx.run.work["answer"]
'''


def test_a_cell_and_a_metric_added_as_files(tmp_path):
    bench = json.loads(json.dumps(BENCHMARK))
    bench["workloads"].append({"name": "added-cell", "config": "h2o-danube-1.8b",
                               "traffic": "added", "chips": 1, "why": "test"})
    bench["end_to_end"][1]["workloads"].append("added-cell")
    bench["end_to_end"][2]["workloads"].append("added-cell")
    bench["per_layer"].append({"name": "answer.added", "unit": "1",
                               "better": "higher", "source": "program_counter",
                               "layer": "engine", "moves": "serve_tokens_per_s",
                               "workloads": ["added-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "benchmarks").mkdir()
    shutil.copytree(BENCH / "configs", tmp_path / "benchmarks/chip/configs")
    added = tmp_path / "bench"
    for sub in ("traffic", "drivers", "metrics"):
        (added / sub).mkdir(parents=True)
    (added / "traffic/added.json").write_text(
        json.dumps({"driver": "fake", "answer": 42}))
    (added / "drivers/fake.py").write_text(FAKE_DRIVER)
    (added / "metrics/answer.added.py").write_text(FAKE_METRIC)

    cell = harness.load_cell("added-cell", root=tmp_path, bench_dir=added)
    assert cell.config["name"] == "h2o-danube-1.8b"
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "serve_tokens_per_s", "serve_request_p95_s"]
    assert [m["name"] for m in cell.per_layer] == ["answer.added"]
    result = harness.run_cell(cell, 7, 1.0, False, time.perf_counter(),
                              rehearsal=True)
    assert result["correct"] is True
    assert result["metrics"]["serve_tokens_per_s"] == {
        "value": 10.0, "unit": "tokens/s"}
    assert list(result)[-1] == "checks"
    reader = harness.load_module(added / "metrics/answer.added.py")
    ctx = harness.Context(config=cell.config, traffic=cell.traffic,
                          run=harness.Run({}, 0, 0, {}, 0, {"answer": 42}),
                          window_s=1.0, compiles=0, peaks=None, trace=None)
    assert reader.read(ctx) == 42


def test_every_name_in_the_benchmark_has_its_file():
    assert BENCHMARK["paths"] == ["benchmarks/chip"]
    for c in BENCHMARK["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    reported = {}
    for w in BENCHMARK["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (BENCH / "drivers" / f"{cell.traffic['driver']}.py").is_file()
        reported[w["name"]] = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported[w["name"]]
        assert len(reported[w["name"]]) >= 2 and cell.per_layer
    for m in BENCHMARK["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        for w in m["workloads"]:
            assert m["moves"] in reported[w], (m["name"], w)
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCHMARK[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


def _run_py(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "danube-serve-chat", "--seed", "3000000000", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _has_result_line(stdout: str) -> bool:
    return any(line.startswith("{") for line in stdout.splitlines())


def test_run_refuses_a_machine_without_a_tpu():
    out = _run_py(ROOT)
    assert out.returncode == 2, out.stderr
    assert "TPUs only" in out.stderr
    assert not _has_result_line(out.stdout)


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks/chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_py(tmp_path)
    assert out.returncode != 0
    assert not _has_result_line(out.stdout)


@pytest.mark.parametrize("values, q, expected", [
    ([3.0], 95, 3.0),
    (list(range(1, 21)), 95, 19),
    (list(range(1, 101)), 95, 95),
])
def test_nearest_rank_percentile(values, q, expected):
    assert harness.percentile(values, q) == expected
