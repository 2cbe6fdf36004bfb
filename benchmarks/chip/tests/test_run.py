"""The command refuses to measure anything but the chip it was given."""
import os
import shutil
import subprocess
import sys

from conftest import CHIP

ROOT = CHIP.parents[1]
ARGS = ["--workload", "internlm2-1.8b.chat", "--seed", str(2**33 + 3),
        "--seconds", "1", "--trace", "0"]


def run_in(root, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *ARGS], cwd=root,
        env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_without_a_result():
    p = run_in(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_in(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr


def test_every_metric_has_a_reader_and_known_cells():
    import json

    import readers

    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        assert callable(readers.reader(m["name"])), m["name"]
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
