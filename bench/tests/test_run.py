"""The command refuses to run without a TPU or without the program, and
finds every part of a cell by name."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec
from bench.spec import ROOT

CMD = [sys.executable, "bench/run.py", "--workload",
       "smollm-360m.chat-prefix-sat", "--seed", "3", "--seconds", "1",
       "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_fails_without_tpu():
    p = _run(ROOT)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_cell_resolves():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.e2e and cell.layer
        assert any(m.name == "setup_s" for m in cell.e2e)
        for m in cell.e2e + cell.layer:
            if m.name != "setup_s":
                assert callable(spec.metric_reader(m.name))


@pytest.mark.parametrize("reader", ["throwaway.chat", "throwaway"])
def test_new_metric_is_found_by_name(tmp_path, reader):
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.load_benchmark()
    bench["workloads"].append(dict(bench["workloads"][0], name="other.cell"))
    bench["per_layer"].append({
        "name": "throwaway.chat", "unit": "n", "better": "higher",
        "source": "program_counter", "layer": "device", "moves": "itl_p95_ms",
        "workloads": ["smollm-360m.chat-prefix-sat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "metrics" / f"{reader}.py").write_text(
        "def read(run):\n    return 42.0 if run else None\n")
    cell = spec.load_cell("smollm-360m.chat-prefix-sat", root)
    assert "throwaway.chat" in [m.name for m in cell.layer]
    other = spec.load_cell("other.cell", root)
    assert "throwaway.chat" not in [m.name for m in other.layer]
    assert spec.metric_reader("throwaway.chat", root)(object()) == 42.0
